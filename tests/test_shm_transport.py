"""Shared-memory same-host transport tests (rpc/shm_transport.py, ISSUE 6).

Covers the ring protocol itself (framing, wrap, oversized frames,
teardown), the negotiation/downgrade matrix (same host accept, host
mismatch, PSDT_SHM=0, /dev/shm unavailable, reference server
UNIMPLEMENTED, mid-flight failure), and the fused data plane riding the
rings end to end — byte-tracked against the TCP path and hammered under
PSDT_LOCK_CHECK=1.
"""

import socket
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.config import ParameterServerConfig
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc import shm_transport as st
from parameter_server_distributed_tpu.rpc.data_plane import (
    PreEncodedParameterUpdate, PSClient, encode_parameter_records)
from parameter_server_distributed_tpu.server.ps_service import ParameterServer
from parameter_server_distributed_tpu.utils.buffers import exported


def _ring_pair(capacity=1 << 20, doorbell=True):
    seg = st._create_segment(f"psdt-test-{time.monotonic_ns()}",
                             64 + capacity)
    if doorbell:
        a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
        prod = st.ShmRing(seg, capacity, st._Doorbell(a))
        cons = st.ShmRing(seg, capacity, st._Doorbell(b))
    else:
        prod = st.ShmRing(seg, capacity)
        cons = st.ShmRing(seg, capacity)
    return seg, prod, cons


def _cleanup(seg):
    try:
        seg.close()
        seg.unlink()
    except (OSError, BufferError):
        pass


class _Bytes:
    """Bytes that already exist, as a message a ring can send."""

    def __init__(self, data):
        self.data = data

    def encoded_size(self):
        return len(self.data)

    def encode_into(self, writer):
        writer.write(self.data)


def _write(ring, data, deadline):
    ring.write_message(_Bytes(data), deadline, "rpc/client/encode")


# ---------------------------------------------------------------- ring unit

@pytest.mark.parametrize("doorbell", [True, False],
                         ids=["doorbell", "polling"])
def test_ring_frame_roundtrip_and_wrap(doorbell):
    """Frames round-trip exactly, including across the wrap boundary
    (with and without the doorbell socket — the polling fallback must
    stay correct)."""
    seg, prod, cons = _ring_pair(capacity=8192, doorbell=doorbell)
    try:
        rng = np.random.default_rng(0)
        payloads = [rng.bytes(n) for n in (1, 100, 3000, 5000, 0, 7777)]
        got = []

        def consume():
            for _ in payloads:
                got.append(cons.read_frame(time.monotonic() + 20))

        th = threading.Thread(target=consume, daemon=True, name="t-cons")
        th.start()
        for p in payloads:
            _write(prod, p, time.monotonic() + 20)
        th.join(timeout=20)
        assert not th.is_alive()
        assert got == payloads
    finally:
        _cleanup(seg)


def test_ring_frame_larger_than_capacity_streams_through():
    """A frame bigger than the whole ring streams through in blocks —
    the oversized-chunk case (single tensor above the chunk budget)."""
    seg, prod, cons = _ring_pair(capacity=64 << 10)
    try:
        big = np.random.default_rng(1).bytes(1 << 20)
        out = []
        th = threading.Thread(
            target=lambda: out.append(cons.read_frame(
                time.monotonic() + 30)),
            daemon=True, name="t-cons")
        th.start()
        _write(prod, big, time.monotonic() + 30)
        th.join(timeout=30)
        assert out and out[0] == big
    finally:
        _cleanup(seg)


def test_ring_empty_data_frame_distinct_from_end_marker():
    """A zero-length DATA frame (a fully-default GradientUpdate encodes
    to b'' under proto3 elision) must round-trip as b'', distinct from
    the end-of-stream marker (None)."""
    seg, prod, cons = _ring_pair()
    try:
        got = []

        def consume():
            while True:
                frame = cons.read_frame(time.monotonic() + 10)
                got.append(frame)
                if frame is None:
                    return

        th = threading.Thread(target=consume, daemon=True, name="t-cons")
        th.start()
        _write(prod, b"", time.monotonic() + 10)
        _write(prod, b"x", time.monotonic() + 10)
        prod.write_end(time.monotonic() + 10)
        th.join(timeout=10)
        assert got == [b"", b"x", None]
    finally:
        _cleanup(seg)


def test_ring_close_unblocks_waiters_and_timeout_raises():
    seg, prod, cons = _ring_pair()
    try:
        with pytest.raises(st.ShmTransportError, match="timeout"):
            cons.read_frame(time.monotonic() + 0.2)
        errs = []

        def blocked_read():
            try:
                cons.read_frame(time.monotonic() + 30)
            except st.ShmTransportError as exc:
                errs.append(exc)

        th = threading.Thread(target=blocked_read, daemon=True,
                              name="t-cons")
        th.start()
        time.sleep(0.05)
        prod.close()
        th.join(timeout=5)
        assert not th.is_alive() and errs
    finally:
        _cleanup(seg)


# ---------------------------------------------------- ring consume side

def _shm_counters():
    snap = obs_stats.REGISTRY.snapshot()["counters"]
    return (snap.get("rpc.shm.frames", 0),
            snap.get("rpc.shm.frame_allocs", 0))


def _send(prod, payloads, end=True):
    """Write ``payloads`` (and the end marker) from a thread of its own."""
    def produce():
        for p in payloads:
            _write(prod, p, time.monotonic() + 30)
        if end:
            prod.write_end(time.monotonic() + 30)

    th = threading.Thread(target=produce, daemon=True, name="t-prod")
    th.start()
    return th


def _consume_group(cons):
    """Read one frame group as the data plane does: take what is needed
    out of a frame, let go of it, ask for the next."""
    got = []
    while True:
        frame = cons.read_frame(time.monotonic() + 30)
        if frame is None:
            return got
        assert isinstance(frame, memoryview) and frame.readonly
        got.append(bytes(frame))


CAP = 8192


@pytest.mark.parametrize("native_copy", [True, False],
                         ids=["native", "memoryview"])
@pytest.mark.parametrize("sizes", [
    (100, 3000), (CAP,), (5 * CAP + 17,), (5000, 5000, 5000),
    (0, 1, 0), (CAP - 4, 3, CAP + 1, 0, 2 * CAP)],
    ids=["smaller", "equal", "several_rings", "wraps", "empty_frames",
         "mixed"])
def test_ring_consume_path_sizes(sizes, native_copy):
    """Frames smaller than, equal to and several times the ring, frames
    that wrap, zero-length data frames and the end marker all come out of
    the pooled consume path byte for byte, as read-only views, through
    the native copy and through the memoryview fallback."""
    seg, prod, cons = _ring_pair(capacity=CAP)
    try:
        if not native_copy:
            cons.invalidate()
        elif cons._copy is None:
            pytest.skip("no native library on this machine")
        rng = np.random.default_rng(len(sizes))
        payloads = [rng.bytes(n) for n in sizes]
        frames_before, _ = _shm_counters()
        for _ in range(2):          # the second lap reuses the buffers
            th = _send(prod, payloads)
            assert _consume_group(cons) == payloads
            th.join(timeout=30)
            assert not th.is_alive()
        assert _shm_counters()[0] - frames_before == 2 * len(sizes)
    finally:
        _cleanup(seg)


def test_ring_pool_grows_then_stops_allocating():
    """Two frame sizes in turn: the receive buffers grow to the larger in
    the first group (and a little over), and from then on no frame needs
    an allocation, whichever buffer it falls on (an odd count flips the
    turn)."""
    seg, prod, cons = _ring_pair(capacity=CAP)
    try:
        rng = np.random.default_rng(7)
        payloads = [rng.bytes(n) for n in (1000, 50_000, 1000)]
        _, before = _shm_counters()
        th = _send(prod, payloads)
        assert _consume_group(cons) == payloads
        th.join(timeout=30)
        _, grown = _shm_counters()
        assert grown > before
        sizes = [len(b) for b in cons._pool._slots]
        assert sizes[0] == sizes[1] and 50_000 <= sizes[0] < 60_000
        slots = [id(b) for b in cons._pool._slots]
        for lap in range(3):
            # the same tensors under a header a few bytes longer (the
            # iteration's varint, a trace context) fit the headroom
            payloads = [p + b"\x01" * lap for p in payloads]
            th = _send(prod, payloads)
            assert _consume_group(cons) == payloads
            th.join(timeout=30)
        assert _shm_counters()[1] == grown
        assert [id(b) for b in cons._pool._slots] == slots
    finally:
        _cleanup(seg)


def test_ring_kept_view_keeps_its_buffer():
    """A consumer that keeps an ``np.frombuffer`` view across later reads
    keeps that buffer: its bytes do not change, the ring takes another
    and counts it, and once the view is gone the pool settles again."""
    seg, prod, cons = _ring_pair(capacity=CAP)
    try:
        rng = np.random.default_rng(11)
        payloads = [rng.bytes(20_000) for _ in range(6)]
        th = _send(prod, payloads[:3])
        assert _consume_group(cons) == payloads[:3]     # pool is warm
        th.join(timeout=30)
        _, warm = _shm_counters()
        th = _send(prod, payloads[3:] + payloads)
        frame = cons.read_frame(time.monotonic() + 30)
        kept = np.frombuffer(frame, np.uint8)[10:20]    # a view of a view
        assert not kept.flags.writeable
        held = {id(b) for b in cons._pool._slots if exported(b)}
        assert len(held) == 1
        del frame
        for want in payloads[4:]:
            frame = cons.read_frame(time.monotonic() + 30)
            assert frame == want
        assert kept.tobytes() == payloads[3][10:20]
        # the held buffer left the pool; exactly one fresh one replaced it
        assert _shm_counters()[1] == warm + 1
        assert not held & {id(b) for b in cons._pool._slots}
        del kept, frame
        assert _consume_group(cons) == payloads
        th.join(timeout=30)
        # the replacement was cut to its frame; free again, it grew to the
        # pool's size once, and from there on nothing is allocated
        steady = _shm_counters()[1]
        assert steady <= warm + 2
        th = _send(prod, payloads)
        assert _consume_group(cons) == payloads
        th.join(timeout=30)
        assert _shm_counters()[1] == steady
    finally:
        _cleanup(seg)


def test_frame_view_is_read_only_and_to_array_owns_its_data():
    """What the ring hands out cannot be written, so a decoded f32 tensor
    is copied out exactly once (``Tensor.to_array``: copy iff not
    writeable) and the array the fold gets is its own: refilling the
    buffer with the next frame does not reach it."""
    seg, prod, cons = _ring_pair()
    try:
        values = np.arange(4096, dtype=np.float32)
        update = m.GradientUpdate(
            worker_id=0, iteration=1,
            gradients=[m.Tensor.from_array("w", values.reshape(64, 64))])
        other = m.GradientUpdate(
            worker_id=0, iteration=2,
            gradients=[m.Tensor.from_array("w", -values.reshape(64, 64))])
        for msg in (update, other, other):
            _write(prod, msg.encode(), time.monotonic() + 10)
        frame = cons.read_frame(time.monotonic() + 10)
        assert frame.readonly
        with pytest.raises(TypeError):
            frame[0] = 0
        decoded = m.GradientUpdate.decode(frame)
        wire = decoded.gradients[0].data
        assert not np.asarray(wire).flags.writeable     # still the frame
        arr = decoded.gradients[0].to_array()
        assert arr.flags.writeable and arr.shape == (64, 64)
        assert not np.shares_memory(arr, np.asarray(wire))
        buffers = list(cons._pool._slots)
        del frame, decoded, wire
        assert not any(exported(b) for b in buffers)
        for _ in range(2):          # both buffers refilled
            assert cons.read_frame(time.monotonic() + 10) is not None
        np.testing.assert_array_equal(arr, values.reshape(64, 64))
        arr += 1.0                  # in-place aggregation is the array's own
    finally:
        _cleanup(seg)


def _stream_handler(core, tmp_path):
    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServerService)

    return ParameterServerService(core, CheckpointManager(
        core, directory=str(tmp_path), checkpoint_interval=10**9,
        check_period_s=3600.0)).PushGradientsStream


@pytest.mark.parametrize("stripes", [1, 3], ids=["serial", "striped"])
def test_a_borrowed_multi_chunk_push_leaves_the_pool_alone(tmp_path,
                                                           stripes):
    """A whole multi-chunk push read off a real ring as the server reads
    it (the frame of chunk k still named while frame k+1 is read) and
    folded where it lies: the handler copies nothing out of the frames
    (``rpc.server.decode.copied_bytes`` stands), the sums are exact, and
    from the second exchange on neither receive buffer is replaced: the
    fold has let go of every view before the next frame is asked for."""
    from parameter_server_distributed_tpu.core.optimizer import SGD
    from parameter_server_distributed_tpu.core.ps_core import (
        ParameterServerCore)

    shapes = {f"layer{i}/w": (40, 25 + i) for i in range(8)}
    rng = np.random.default_rng(41)
    core = ParameterServerCore(total_workers=1, optimizer=SGD(1.0),
                               stripes=stripes)
    want = {n: rng.standard_normal(sh).astype(np.float32)
            for n, sh in shapes.items()}
    core.initialize_parameters(want)
    handler = _stream_handler(core, tmp_path)
    copied = obs_stats.counter("rpc.server.decode.copied_bytes")
    seg, prod, cons = _ring_pair(capacity=CAP)
    try:
        def chunks():
            frame = cons.read_frame(time.monotonic() + 30)
            while frame is not None:
                chunk = m.GradientUpdate.decode(frame)
                yield chunk
                frame = cons.read_frame(time.monotonic() + 30)

        allocs = []
        before = copied.value
        for it in range(1, 5):
            grads = {n: rng.standard_normal(sh).astype(np.float32)
                     for n, sh in shapes.items()}
            names = list(grads)
            th = _send(prod, [m.GradientUpdate(
                worker_id=0, iteration=it, gradients=[
                    m.Tensor.from_array(n, grads[n])
                    for n in names[lo:lo + 2]]).encode()
                for lo in range(0, len(names), 2)])
            response = handler(chunks(), None)
            th.join(timeout=30)
            assert response.aggregation_complete, response.message
            allocs.append(_shm_counters()[1])
            for n in want:
                want[n] = want[n] - grads[n]
                np.testing.assert_array_equal(core.get_parameters()[n],
                                              want[n])
        assert copied.value == before
        assert allocs[1:] == [allocs[0]] * 3
        assert not any(exported(b) for b in cons._pool._slots)
    finally:
        _cleanup(seg)


def test_invalidate_during_read_falls_back_then_fails_cleanly():
    """``invalidate()`` under a reader in mid-frame: the rest of the frame
    comes through the memoryview path, and once the segment is unmapped
    the parked reader fails as ShmTransportError, not at a stale
    address."""
    seg, prod, cons = _ring_pair(capacity=CAP)
    payload = np.random.default_rng(3).bytes(3 * CAP)
    got, errs = [], []

    def reader():
        try:
            got.append(bytes(cons.read_frame(time.monotonic() + 30)))
            cons.read_frame(time.monotonic() + 30)
        except st.ShmTransportError as exc:
            errs.append(exc)

    try:
        th = threading.Thread(target=reader, daemon=True, name="t-cons")
        th.start()
        deadline = time.monotonic() + 30
        prod._write_bytes((3 * CAP).to_bytes(4, "little"), deadline)
        prod._write_bytes(payload[:CAP], deadline)      # reader mid-frame
        while cons._head() < 4 + CAP:
            time.sleep(0.001)
        cons.invalidate()
        assert cons._copy is None and cons._base == 0
        prod._write_bytes(payload[CAP:], deadline)
        while not got and th.is_alive():
            time.sleep(0.001)
        assert got == [payload]
        prod.invalidate()
        seg.close()                 # unmap under the parked reader
        th.join(timeout=10)
        assert not th.is_alive() and len(errs) == 1
    finally:
        _cleanup(seg)


# ---------------------------------------------------- ring produce side

def _tensors(wire_dtype):
    """Three tensors whose first payload is long enough for a wrap to fall
    inside it; top-k keeps half so that its payload is, too."""
    rng = np.random.default_rng(wire_dtype)
    return [m.Tensor.from_array(name, rng.standard_normal(shape)
                                .astype(np.float32), wire_dtype=wire_dtype,
                                topk_density=0.5)
            for name, shape in (("a", (700,)), ("bias", (5,)),
                                ("layer/w", (3, 11)))]


def _gradients(wire_dtype):
    return m.GradientUpdate(worker_id=3, iteration=7,
                            gradients=_tensors(wire_dtype),
                            pull_wire_dtype=m.WIRE_BF16,
                            trace_context=b"0123456789abcdef/01234567")


def _response(bodies):
    """A served chunk as the fused handler yields it.  One body is a
    ``bytes``, the other a read-only view of a buffer handed in, which
    is what the serve cache keeps."""
    def take(size):
        return memoryview(bytearray(size))

    made = [bytes(encode_parameter_records(_tensors(m.WIRE_F32), take)),
            encode_parameter_records(_tensors(m.WIRE_BF16), take)]
    return m.PushPullResponse(
        params=PreEncodedParameterUpdate(9, True, made[:bodies]))


MESSAGES = {
    "gradients_f32": lambda: _gradients(m.WIRE_F32),
    "gradients_raw_f32": lambda: _gradients(m.WIRE_RAW_F32),
    "gradients_bf16": lambda: _gradients(m.WIRE_BF16),
    "gradients_int8": lambda: _gradients(m.WIRE_INT8),
    "gradients_topk": lambda: _gradients(m.WIRE_TOPK),
    "response_one_body": lambda: _response(1),
    "response_two_bodies": lambda: _response(2),
    "all_default": m.GradientUpdate,
}
RING_OVER_FRAME = {"frame_smaller": 4.0, "frame_equal": 1.0,
                   "frame_several_rings": 0.2}
# where the ring wraps, in bytes after the frame's first: inside the length
# and headers, inside the first payload (for bf16, int8 and top-k a piece of
# the scratch), late in the frame
WRAP_AFTER = {"wrap_in_header": 6, "wrap_in_payload": 90, "wrap_late": None}


@pytest.mark.parametrize("native_copy", [True, False],
                         ids=["native", "memoryview"])
@pytest.mark.parametrize("wrap", list(WRAP_AFTER))
@pytest.mark.parametrize("ring", list(RING_OVER_FRAME))
@pytest.mark.parametrize("kind", list(MESSAGES))
def test_message_encoded_into_the_ring_is_its_encode(
        kind, ring, wrap, native_copy, monkeypatch):
    """A message sent through the ring arrives byte for byte as its
    ``encode()``, with no encoder output in new memory: every payload
    encoding, a response around one and around several pre-encoded
    bodies, a fully-default message (a zero-length frame), each followed
    by the end marker; frames smaller than, equal to and several times
    the ring; the wrap inside the headers, a payload or the scratch;
    through the native copy and the memoryview fallback."""
    # a small scratch, so that payloads of a few KB pack through several
    # pieces of it
    monkeypatch.setattr(st._RingWriter, "_SCRATCH", 512)
    message = MESSAGES[kind]()
    expected = message.encode()
    if kind == "all_default":
        assert expected == b""
    frame = 4 + len(expected)
    capacity = max(16, int(frame * RING_OVER_FRAME[ring]))
    seg, prod, cons = _ring_pair(capacity=capacity)
    try:
        if not native_copy:
            prod.invalidate()
        elif prod._copy is None:
            pytest.skip("no native library on this machine")
        after = WRAP_AFTER[wrap]
        lead = capacity - (after if after is not None else capacity // 3)
        lead %= capacity
        if lead >= 4:       # a frame before it puts the tail where we want
            th = _send(prod, [bytes(lead - 4)], end=False)
            assert len(cons.read_frame(time.monotonic() + 30)) == lead - 4
            th.join(timeout=30)
            assert prod._tail() == lead

        def produce():
            deadline = time.monotonic() + 30
            prod.write_message(message, deadline, "test/encode")
            prod.write_end(deadline)

        fresh = obs_stats.counter("rpc.wire.fresh_bytes")
        before = fresh.value
        th = threading.Thread(target=produce, daemon=True, name="t-prod")
        th.start()
        assert _consume_group(cons) == [expected]
        th.join(timeout=30)
        assert not th.is_alive()
        assert fresh.value == before
        assert prod._tail() == (lead if lead >= 4 else 0) + frame + 4
    finally:
        _cleanup(seg)


def test_large_cast_goes_through_the_scratch_in_pieces():
    """At the thresholds the data plane runs with: a bf16 payload of 6 MB
    through a 4 MB scratch and a 1 MB ring, beside a bias of one short
    piece and an int8 payload packed whole.  The scratch is the ring
    end's own and is allocated once."""
    rng = np.random.default_rng(5)
    message = m.GradientUpdate(worker_id=1, iteration=2, gradients=[
        m.Tensor.from_array("w", rng.standard_normal(3 << 20)
                            .astype(np.float32), wire_dtype=m.WIRE_BF16),
        m.Tensor.from_array("b", rng.standard_normal(9)
                            .astype(np.float32), wire_dtype=m.WIRE_BF16),
        m.Tensor.from_array("q", rng.standard_normal(1 << 20)
                            .astype(np.float32), wire_dtype=m.WIRE_INT8)])
    expected = message.encode()
    seg, prod, cons = _ring_pair(capacity=1 << 20)
    try:
        for _ in range(2):
            th = threading.Thread(
                target=prod.write_message, daemon=True,
                args=(message, time.monotonic() + 60, "test/encode"))
            th.start()
            assert bytes(cons.read_frame(time.monotonic() + 60)) == expected
            th.join(timeout=60)
            assert not th.is_alive()
            scratch = prod._scratch
            assert len(scratch) == st._RingWriter._SCRATCH
        assert prod._scratch is scratch
    finally:
        _cleanup(seg)


def test_packed_frame_is_encode_and_copy_legs_in_turn(monkeypatch):
    """What is left of encoding has spans of its own and no time under
    the copy leg: the sizes before the frame's ``rpc/shm/copy`` opens,
    and each piece's pack with the copy leg closed, so the legs of a
    frame lie one after the other; a frame that packs nothing is one
    encode span and one copy span."""
    from parameter_server_distributed_tpu.obs import trace as obs_trace

    monkeypatch.setattr(st._RingWriter, "_SCRATCH", 512)
    values = np.arange(600, dtype=np.float32)
    seg, prod, cons = _ring_pair(capacity=1 << 16)
    obs_trace.clear()
    obs_trace.enable(True)
    try:
        for wire_dtype, pieces in ((m.WIRE_RAW_F32, 0), (m.WIRE_BF16, 3)):
            message = m.GradientUpdate(worker_id=1, gradients=[
                m.Tensor.from_array("w", values, wire_dtype=wire_dtype)])
            prod.write_message(message, time.monotonic() + 5, "test/encode")
            legs = sorted(obs_trace.spans(), key=lambda s: s["ts"])
            assert cons.read_frame(time.monotonic() + 5) == message.encode()
            obs_trace.clear()
            assert [s["name"] for s in legs] == \
                ["test/encode", "rpc/shm/copy"] * (1 + pieces)
            assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-4
                       for a, b in zip(legs, legs[1:]))
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
        _cleanup(seg)


def test_message_that_miscounts_its_size_tears_the_frame():
    """``encode_into`` writing another count than ``encoded_size()`` said
    cannot be repaired (the length went out first): it raises."""
    class Short(m.GradientUpdate):
        def encoded_size(self):
            return super().encoded_size() + 1

    seg, prod, cons = _ring_pair(capacity=1 << 16)
    try:
        with pytest.raises(RuntimeError, match="encoded_size"):
            prod.write_message(Short(worker_id=1), time.monotonic() + 5,
                               "test/encode")
    finally:
        _cleanup(seg)


# ------------------------------------------------------------- negotiation

@pytest.fixture
def ps(tmp_path):
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=str(tmp_path), learning_rate=0.5,
        autosave_period_s=3600.0))
    port = server.start()
    yield server, port
    server.stop()


def _seed(client, n=16):
    w0 = np.arange(n, dtype=np.float32)
    push = client.push_gradients(m.GradientUpdate(
        worker_id=0, iteration=0,
        gradients=[m.Tensor.from_array("w", w0)]))
    assert push.success, push.message
    return w0


def test_same_host_negotiation_and_fused_rounds(ps):
    """Acceptance: same-host fused rounds negotiate the rings, move the
    payload through shared memory (rpc.shm.bytes grows), and produce
    results identical to the TCP path."""
    _, port = ps
    before = obs_stats.REGISTRY.snapshot()["counters"].get(
        "rpc.shm.bytes", 0)
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        grads = [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))]
        for it in (1, 2, 3):
            push, params = client.push_pull(0, it, grads)
            assert push.success and params is not None and params.ready
            np.testing.assert_allclose(
                params.parameters[0].to_array(), w0 - 0.05 * it,
                rtol=1e-6)
        assert client.shm_active
        assert client._fused_ok is True
    after = obs_stats.REGISTRY.snapshot()["counters"].get(
        "rpc.shm.bytes", 0)
    assert after > before


def test_shm_and_tcp_rounds_bit_identical(tmp_path):
    """The transport must be invisible: the same push sequence over shm
    and over TCP (PSDT_SHM=0) yields bit-identical served parameters."""
    import os

    results = {}
    for shm_on in (True, False):
        os.environ["PSDT_SHM"] = "1" if shm_on else "0"
        try:
            server = ParameterServer(ParameterServerConfig(
                bind_address="127.0.0.1", port=0, total_workers=1,
                checkpoint_dir=str(tmp_path / f"shm{shm_on}"),
                learning_rate=0.5, autosave_period_s=3600.0))
            port = server.start()
            try:
                with PSClient(f"127.0.0.1:{port}") as client:
                    _seed(client, 64)
                    grads = [m.Tensor.from_array(
                        "w", np.linspace(-1, 1, 64, dtype=np.float32))]
                    push, params = client.push_pull(0, 1, grads)
                    assert push.success and params is not None
                    assert client.shm_active is shm_on
                    results[shm_on] = params.parameters[0].to_array()
            finally:
                server.stop()
        finally:
            os.environ.pop("PSDT_SHM", None)
    assert results[True].tobytes() == results[False].tobytes()


def test_all_default_empty_push_round_over_shm(ps):
    """The sharded-topology empty barrier contribution at worker 0 /
    iteration 0 encodes to b'' — it must complete a fused round over the
    rings (the END sentinel is out-of-band), not hang or desync."""
    _, port = ps
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        # establish the shm connection with a normal round first
        push, params = client.push_pull(
            0, 1, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
        assert push.success and client.shm_active
        # all-default chunk: worker 0, iteration 0, no tensors -> b''
        push, params = client.push_pull(0, 0, [], timeout=20.0)
        assert push is not None  # stale rejection is fine; hanging is not
        assert client.shm_active  # connection survived the round
        # and the connection still serves normal rounds afterwards
        push, params = client.push_pull(
            0, 2, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
        assert push.success and params is not None
        np.testing.assert_allclose(params.parameters[0].to_array(),
                                   w0 - 0.10, rtol=1e-6)


def test_client_disconnect_reaps_server_segments(ps):
    """Closing the client frees the server-side segments promptly (no
    /dev/shm accretion under elastic worker churn)."""
    server, port = ps
    client = PSClient(f"127.0.0.1:{port}")
    _seed(client)
    push, _ = client.push_pull(
        0, 1, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
    assert push.success and client.shm_active
    assert len(server.service.shm_server._conns) == 1
    client.close()
    deadline = time.monotonic() + 10
    while (server.service.shm_server._conns
           and time.monotonic() < deadline):
        time.sleep(0.05)
    assert server.service.shm_server._conns == []


def test_host_mismatch_refused_and_downgrades(ps, monkeypatch):
    """A client reporting a different host/boot-id is refused; the fused
    round rides TCP and the downgrade is permanent (one fallback count,
    no re-negotiation)."""
    _, port = ps
    monkeypatch.setattr(st, "host_id", lambda: "elsewhere/deadbeef")
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        push, params = client.push_pull(
            0, 1, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
        assert push.success and params is not None
        assert not client.shm_active and client._shm_ok is False
        np.testing.assert_allclose(params.parameters[0].to_array(),
                                   w0 - 0.05, rtol=1e-6)


def test_psdt_shm_0_disables_both_ends(ps, monkeypatch):
    _, port = ps
    monkeypatch.setenv("PSDT_SHM", "0")
    with PSClient(f"127.0.0.1:{port}") as client:
        _seed(client)
        push, params = client.push_pull(
            0, 1, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
        assert push.success and params is not None
        # client-side gate: negotiation never even attempted
        assert client._shm_ok is None and not client.shm_active


def test_dev_shm_unavailable_refused_and_downgrades(ps, monkeypatch):
    """Segment creation failing server-side (no /dev/shm, exhausted)
    refuses the negotiation; the client downgrades permanently with zero
    failed steps."""
    _, port = ps

    def boom(name, size):
        raise OSError("No space left on device")

    monkeypatch.setattr(st, "_create_segment", boom)
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        push, params = client.push_pull(
            0, 1, [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))])
        assert push.success and params is not None
        assert client._shm_ok is False
        np.testing.assert_allclose(params.parameters[0].to_array(),
                                   w0 - 0.05, rtol=1e-6)


def test_reference_server_unimplemented_downgrades(tmp_path):
    """A reference-shaped PS (5 unary RPCs, no NegotiateShm) answers
    UNIMPLEMENTED: permanent TCP downgrade, push still lands."""
    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)
    from parameter_server_distributed_tpu.core.ps_core import (
        ParameterServerCore)
    from parameter_server_distributed_tpu.rpc.service import (bind_service,
                                                              make_server)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServerService)

    core = ParameterServerCore(total_workers=1)
    core.initialize_parameters({"w": np.ones(4, np.float32)})
    service = ParameterServerService(
        core, CheckpointManager(core, directory=str(tmp_path),
                                checkpoint_interval=100,
                                check_period_s=600.0))
    server = make_server()
    bind_service(server, m.PARAMETER_SERVER_SERVICE,
                 m.PARAMETER_SERVER_METHODS, service)  # unary only
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        with PSClient(f"127.0.0.1:{port}") as client:
            push, params = client.push_pull(
                0, 1, [m.Tensor.from_array("w", np.full(4, 0.5,
                                                        np.float32))])
            assert push.success
            assert params is None  # unary fallback: caller polls + pulls
            assert client._shm_ok is False
    finally:
        server.stop(0)
        service.shm_server.close()


def test_midflight_shm_failure_downgrades_and_replays(ps):
    """Killing the rings under a live connection: the NEXT fused round
    catches the transport error, downgrades permanently, counts a
    fallback, and replays over TCP — zero failed steps."""
    server, port = ps
    before = obs_stats.REGISTRY.snapshot()["counters"].get(
        "rpc.shm.fallback", 0)
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        grads = [m.Tensor.from_array("w", np.full(16, 0.1, np.float32))]
        push, params = client.push_pull(0, 1, grads)
        assert push.success and client.shm_active
        # sabotage: server tears down every shm connection
        server.service.shm_server.close()
        push, params = client.push_pull(0, 2, grads)
        assert push.success and params is not None
        assert client._shm_ok is False and not client.shm_active
        np.testing.assert_allclose(params.parameters[0].to_array(),
                                   w0 - 0.10, rtol=1e-6)
    after = obs_stats.REGISTRY.snapshot()["counters"].get(
        "rpc.shm.fallback", 0)
    assert after == before + 1


@pytest.mark.lockcheck
def test_concurrent_fused_rounds_over_shm_lockcheck(tmp_path):
    """Two same-host workers close a 2-wide barrier over their own shm
    connections while a third thread hammers unary pulls — under
    PSDT_LOCK_CHECK=1, so any lock-order violation in the new
    ring/registry locks raises instead of deadlocking."""
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_dir=str(tmp_path), learning_rate=0.5,
        autosave_period_s=3600.0))
    port = server.start()
    try:
        server.core.initialize_parameters(
            {"w": np.zeros(1024, np.float32)})
        clients = [PSClient(f"127.0.0.1:{port}") for _ in range(2)]
        errors: list = []

        def run_worker(wid: int):
            try:
                grads = [m.Tensor.from_array(
                    "w", np.full(1024, float(wid + 1), np.float32))]
                for it in range(1, 6):
                    push, params = clients[wid].push_pull(wid, it, grads)
                    assert push.success, push.message
                    assert params is not None and params.ready
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=run_worker, args=(wid,),
                                    daemon=True, name=f"t-worker-{wid}")
                   for wid in range(2)]
        for th in threads:
            th.start()
        with PSClient(f"127.0.0.1:{port}") as puller:
            for _ in range(10):
                puller.pull_parameters(m.PullRequest(worker_id=9))
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
        if errors:
            raise errors[0]
        assert all(c.shm_active for c in clients)
        # 5 barriers x mean(1, 2) * lr 0.5 applied from zeros
        np.testing.assert_allclose(
            server.core.get_parameters()["w"],
            np.full(1024, -0.75 * 5, np.float32), rtol=1e-5)
        for c in clients:
            c.close()
    finally:
        server.stop()


@pytest.mark.parametrize("consumer", ["raises", "stops_early"])
def test_client_consumer_mid_response_leaves_connection_usable(
        tmp_path, monkeypatch, consumer):
    """The response is consumed as it arrives, inside the round lock.  A
    consumer that raises in the middle of it latches the rings closed
    (the next round on the same PSClient replays over TCP); one that
    stops reading early has the rest drained for it (the next round
    rides the rings).  Either way the connection is never half-read."""
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", "256")    # 64 floats
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=str(tmp_path), learning_rate=0.5,
        autosave_period_s=3600.0))
    port = server.start()
    names = [f"w{i}" for i in range(4)]
    store = {n: np.full(64, float(i), np.float32)
             for i, n in enumerate(names)}
    server.core.initialize_parameters(store)

    def grads():
        return [m.Tensor.from_array(n, np.full(64, 0.1, np.float32))
                for n in names]

    def check(params, rounds):
        got = {t.name: t.to_array() for t in params.parameters}
        for n in names:
            np.testing.assert_allclose(got[n], store[n] - 0.05 * rounds,
                                       rtol=1e-5)

    try:
        with PSClient(f"127.0.0.1:{port}") as client:
            push, params = client.push_pull(0, 1, grads())
            assert push.success and client.shm_active
            check(params, 1)
            if consumer == "raises":
                seen = []

                def boom(tensors):
                    seen.append(len(tensors))
                    if len(seen) == 2:
                        raise RuntimeError("converter failed")

                with pytest.raises(RuntimeError, match="converter failed"):
                    client.push_pull(0, 2, grads(), on_chunk=boom)
                assert len(seen) == 2       # mid-response: 4 chunks came
                conn = client._shm_conn
                assert conn.c2s.closed and conn.s2c.closed
            else:
                chunks = [m.GradientUpdate(worker_id=0, iteration=2,
                                           gradients=grads())]
                first = client._shm_conn.round_trip(
                    iter(chunks), 20.0, lambda answer: bytes(next(answer)))
                assert m.PushPullResponse.decode(first).push.success
            # the push of round 2 landed either way; round 3 must work
            push, params = client.push_pull(0, 3, grads(), timeout=20.0)
            assert push.success and params is not None and params.ready
            check(params, 3)
            assert client.shm_active is (consumer == "stops_early")
    finally:
        server.stop()


@pytest.mark.parametrize("side", ["client", "server"])
def test_encoder_raising_mid_frame_latches_the_rings(ps, monkeypatch, side):
    """A frame's length goes out before its bytes, so an ``encode_into``
    that raises halfway leaves a torn frame.  On the worker's side the
    error reaches the caller as the gRPC path's would, both rings are
    latched and the retry rides TCP; on the server's the handler thread
    latches them, the worker sees a transport error and replays the same
    round over TCP.  No round is lost either way."""
    server, port = ps
    with PSClient(f"127.0.0.1:{port}") as client:
        w0 = _seed(client)
        good = m.Tensor.from_array("w", np.full(16, 0.1, np.float32))
        push, params = client.push_pull(0, 1, [good])
        assert push.success and client.shm_active
        conn = client._shm_conn
        if side == "client":
            class Torn(m.Tensor):
                def encode_into(self, writer):
                    writer.write(b"\x0a\x01w")      # some of it is out
                    raise RuntimeError("encoder failed")

            torn = Torn(name="w", shape=[16], data=good.data)
            with pytest.raises(RuntimeError, match="encoder failed"):
                client.push_pull(0, 2, [torn])
            assert conn.c2s.closed and conn.s2c.closed
        else:
            real = PreEncodedParameterUpdate.encode_into
            calls = []

            def once(self, writer):
                calls.append(1)
                if len(calls) == 1:
                    writer.write(b"\x08")
                    raise RuntimeError("encoder failed")
                real(self, writer)

            monkeypatch.setattr(PreEncodedParameterUpdate, "encode_into",
                                once)
        push, params = client.push_pull(0, 2, [good], timeout=20.0)
        assert push.success and params is not None and params.ready
        if side == "server":    # torn over shm, whole over TCP
            assert len(calls) == 2
        assert conn.c2s.closed and conn.s2c.closed
        assert not client.shm_active
        np.testing.assert_allclose(params.parameters[0].to_array(),
                                   w0 - 0.10, rtol=1e-6)


# ------------------------------------------------- spans and the wide move

WIDE_CAP = 16 << 20              # a quarter of it, the span: 4 MB, 3 pieces
SPAN = WIDE_CAP // 4
WIDE_FRAMES = (0, 1, SPAN - 1, SPAN, SPAN + 1, 3 * WIDE_CAP + 7)
# where the first frame starts in the ring: flat, so that the wrap falls
# inside a span's first piece, and inside a later piece of a later span
WIDE_STARTS = {"flat": 0, "wrap_in_first_piece": WIDE_CAP - 1000,
               "wrap_in_later_piece": WIDE_CAP - SPAN - SPAN // 2 - 3}

_CHILD_READER = """
import hashlib, sys, time
from parameter_server_distributed_tpu.rpc import shm_transport as st
st._MAX_WIDTH = 3
seg = st._attach_segment(sys.argv[1])
ring = st.ShmRing(seg, int(sys.argv[2]),
                  st._Doorbell(st._doorbell_connect(sys.argv[3])))
while True:
    frame = ring.read_frame(time.monotonic() + 120)
    if frame is None:
        break
    print(len(frame), hashlib.sha256(frame).hexdigest(), flush=True)
    del frame
"""


@pytest.mark.parametrize("offset", [0, 3], ids=["aligned", "view_at_3"])
@pytest.mark.parametrize("start", list(WIDE_STARTS))
@pytest.mark.parametrize("mode", ["threads", "processes", "memoryview"])
def test_frames_through_the_wide_move_byte_for_byte(monkeypatch, mode,
                                                    start, offset):
    """Frames of 0, 1, span - 1, span, span + 1 and 3 x ring + 7 bytes,
    from sources at an odd address, through a ring whose spans are cut over
    three threads: between two threads, between two processes, and through
    the memoryview path (no native library), which moves the same spans."""
    import hashlib
    import subprocess
    import sys

    from parameter_server_distributed_tpu import native

    if mode == "memoryview":
        monkeypatch.setattr(native, "copy_fn", lambda: None)
    elif native.copy_fn() is None:
        pytest.skip("no native library on this machine")
    monkeypatch.setattr(st, "_MAX_WIDTH", 3)
    rng = np.random.default_rng(len(start) + offset)
    backing = rng.integers(0, 256, max(WIDE_FRAMES) + offset, dtype=np.uint8)
    payloads = [memoryview(backing)[offset:offset + n] for n in WIDE_FRAMES]
    seg = st._create_segment(f"psdt-test-{time.monotonic_ns()}",
                             64 + WIDE_CAP)
    child = None
    try:
        # where the first frame starts, set before any end looks
        blank = st.ShmRing(seg, WIDE_CAP)
        blank._set_head(WIDE_STARTS[start])
        blank._set_tail(WIDE_STARTS[start])
        del blank
        if mode == "processes":
            listener, addr = st._doorbell_listener()
            child = subprocess.Popen(
                [sys.executable, "-c", _CHILD_READER, seg.name,
                 str(WIDE_CAP), addr], stdout=subprocess.PIPE, text=True)
            listener.settimeout(60)
            sock, _ = listener.accept()
            listener.close()
            prod, cons = st.ShmRing(seg, WIDE_CAP, st._Doorbell(sock)), None
        else:
            a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
            prod = st.ShmRing(seg, WIDE_CAP, st._Doorbell(a))
            cons = st.ShmRing(seg, WIDE_CAP, st._Doorbell(b))
            assert (cons._copy is None) == (mode == "memoryview")
        wide = obs_stats.counter("rpc.shm.wide_bytes")
        before = wide.value
        th = _send(prod, payloads)
        if cons is not None:
            got = _consume_group(cons)
            assert [len(g) for g in got] == list(WIDE_FRAMES)
            assert all(g == p for g, p in zip(got, payloads))
        else:
            said, _ = child.communicate(timeout=120)
            assert child.returncode == 0
            assert said.split() == [
                word for p in payloads
                for word in (str(len(p)), hashlib.sha256(p).hexdigest())]
        th.join(timeout=60)
        assert not th.is_alive()
        if mode == "memoryview":
            assert wide.value == before
        else:   # every span of the four frames of a span or more
            assert wide.value - before >= (2 - (cons is None)) * sum(
                n for n in WIDE_FRAMES if n >= SPAN)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
        del prod, cons
        _cleanup(seg)


def test_wide_bytes_count_large_payloads_at_both_ends_and_no_control_frames(
        monkeypatch):
    """``rpc.shm.wide_bytes`` grows by a 64 MB payload's bytes at the
    writing end and again at the reading end (its length prefix is not
    wide), and by nothing for a run of 200-byte control frames."""
    from parameter_server_distributed_tpu import native

    if native.copy_fn() is None:
        pytest.skip("no native library on this machine")
    monkeypatch.setattr(st, "_MAX_WIDTH", 2)
    wide = obs_stats.counter("rpc.shm.wide_bytes")
    moved = obs_stats.counter("rpc.shm.bytes")
    seg, prod, cons = _ring_pair(capacity=WIDE_CAP)
    try:
        big = np.random.default_rng(7).bytes(64 << 20)
        for payloads, grows in (([big], 2 * len(big)),
                                ([bytes([i]) * 200 for i in range(50)], 0)):
            wide_before, moved_before = wide.value, moved.value
            th = _send(prod, payloads)
            assert _consume_group(cons) == payloads
            th.join(timeout=60)
            assert not th.is_alive()
            assert wide.value - wide_before == grows
            assert moved.value - moved_before == 2 * (
                sum(4 + len(p) for p in payloads) + 4)
    finally:
        _cleanup(seg)


@pytest.mark.parametrize("how", ["invalidate_then_unmap", "close"])
def test_teardown_under_a_writer_in_mid_frame_fails_cleanly(how):
    """A writer parked in the middle of a frame larger than the ring:
    ``invalidate()`` sends the rest of its spans through the memoryview
    path, which fails as ShmTransportError once the segment is unmapped
    (never a native call at a stale address: the ISSUE 8 rule);
    ``close()`` wakes it with the same error."""
    seg, prod, cons = _ring_pair(capacity=CAP)
    payload = np.random.default_rng(11).bytes(3 * CAP)
    errs = []

    def writer():
        try:
            _write(prod, payload, time.monotonic() + 30)
        except st.ShmTransportError as exc:
            errs.append(exc)

    try:
        def written(least):
            deadline = time.monotonic() + 20
            while prod._tail() < least and time.monotonic() < deadline:
                time.sleep(0.001)
            assert prod._tail() >= least

        th = threading.Thread(target=writer, daemon=True, name="t-prod")
        th.start()
        written(CAP - CAP // 4)     # less than a span is free: it parks
        if how == "close":
            cons.close()
        else:
            prod.invalidate()
            assert prod._copy is None and prod._base == 0
            # a quarter ring through the memoryview path, then the unmap
            cons._read_into(bytearray(CAP // 2), CAP // 2,
                            time.monotonic() + 30)
            written(CAP + CAP // 4)
            cons.invalidate()
            seg.close()
        th.join(timeout=10)
        assert not th.is_alive() and len(errs) == 1
    finally:
        _cleanup(seg)
