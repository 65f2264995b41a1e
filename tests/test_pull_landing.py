"""A served parameter lands where the step uploads it (ISSUE 44).

The worker's decode (``Worker._chunk_converter``) copies each served
tensor from its frame into the trainer's next upload buffer
(``Trainer.lend_store``) in one pass, and ``Trainer._pack`` uploads a store
that lies there without copying it.  Held here: the values are
``from_wire``'s bit for bit on every wire; the two buffers alternate and
nothing is allocated in steady state; whoever keeps a store keeps its
bytes and the trainer allocates in its place; everything the layout does
not know falls back and is counted; a retry never shares memory with a
failed attempt's stragglers; the real ring's frame pool is left alone;
and the step computes the same bits from a store in place as from a copy.
"""

import threading

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import (ParameterServerConfig,
                                                     WorkerConfig)
from parameter_server_distributed_tpu.core.tensor import from_wire
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc.data_plane import PSClient
from parameter_server_distributed_tpu.server.ps_service import ParameterServer
from parameter_server_distributed_tpu.utils.buffers import exported
from parameter_server_distributed_tpu.worker.trainer import Trainer
from parameter_server_distributed_tpu.worker.worker import Worker

SHAPES = {"emb/w": (7, 6), "head/b": (6,), "layer/w": (6, 6), "scale": (),
          "void": (0, 3)}
PAYLOAD = 4 * sum(int(np.prod(s)) for s in SHAPES.values())

_fresh = obs_stats.counter("worker.pull.fresh_bytes")
_copied = obs_stats.counter("worker.pack.copied_bytes")


class _Model:
    """A few tensors by name: two matrices, a vector, a scalar and an
    empty one, under a loss every one of them moves."""

    def __init__(self, shapes=SHAPES):
        self.shapes = shapes

    def init_params(self, seed: int):
        return _store(seed, self.shapes)

    @staticmethod
    def loss(params, batch):
        hidden = jnp.tanh(batch @ params["emb/w"] @ params["layer/w"]
                          + params["head/b"]) * params["scale"]
        return jnp.mean(hidden ** 2) + jnp.sum(params["void"])


def _store(seed, shapes=SHAPES):
    rng = np.random.default_rng(seed)
    return {name: rng.standard_normal(shape).astype(np.float32)
            for name, shape in sorted(shapes.items())}


def _batch(seed):
    return np.random.default_rng(1000 + seed).standard_normal(
        (8, 7)).astype(np.float32)


def _worker(trainer):
    return Worker(WorkerConfig(), trainer=trainer, batches=iter(()),
                  start_heartbeat=False)


def _served(store, wire_dtype=m.WIRE_F32, chunks=2):
    """``store`` as a pull's chunks of tensors DECODED from their encoded
    frames (read-only views of the frame on the float32 wire)."""
    names = list(store)
    per = -(-len(names) // chunks)
    return [m.ParameterUpdate.decode(m.ParameterUpdate(
        iteration=1, ready=True, parameters=[
            m.Tensor.from_array(n, store[n], wire_dtype=wire_dtype)
            for n in names[lo:lo + per]]).encode()).parameters
        for lo in range(0, len(names), per)]


def _land(worker, chunks):
    local = {}
    convert = worker._chunk_converter(local)
    for chunk in chunks:
        convert(chunk)
    return local


def _address(array):
    return array.__array_interface__["data"][0]


def _in_an_upload_buffer(trainer, array):
    return any(0 <= _address(array) - _address(np.frombuffer(buf, np.uint8))
               < len(buf) for buf in trainer._pack_bufs if buf is not None)


def _bits(store):
    return {name: (np.asarray(a).shape, np.asarray(a, np.float32).tobytes())
            for name, a in store.items()}


def _wired(store, wire_dtype=m.WIRE_F32):
    """What ``from_wire`` makes of ``store`` served (a scalar comes back
    as one element, a packed wire rounded)."""
    want = {}
    for chunk in _served(store, wire_dtype):
        want.update(from_wire(chunk))
    return _bits(want)


# ------------------------------------------------------------- the landing

@pytest.mark.parametrize("wire", ["f32", "bf16", "int8", "topk", "float64"])
def test_a_landed_store_is_from_wire_bit_for_bit(wire):
    """Every wire, a float64 tag, a scalar and an empty tensor: the store
    the converter leaves in lent memory holds ``from_wire``'s shapes and
    float32 bits, and the step packs none of it again."""
    store = _store(3)
    if wire == "float64":
        store = {n: a.astype(np.float64) for n, a in store.items()}
    wire_dtype = m.WIRE_DTYPE_NAMES.get(wire, m.WIRE_F32)
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    want = _wired(store, wire_dtype)
    fresh = _fresh.value
    got = _land(worker, _served(store, wire_dtype))
    assert _bits(got) == want
    assert all(a.dtype == np.float32 for a in got.values() if a.size)
    # one upload buffer allocated; beside it only what the wire had to
    # unpack (packed encodings, the float64 upcast) or an empty tensor
    unpacked = {"f32": 0, "float64": 2 * PAYLOAD}.get(wire, PAYLOAD)
    assert _fresh.value - fresh == 4 * trainer._padded_in + unpacked
    copied = _copied.value
    flat = trainer._pack(got)
    assert _copied.value == copied
    for name, off, size, _shape, _dtype in trainer._layout:
        assert flat[off:off + size].tobytes() == want[name][1]


def test_the_two_buffers_alternate_and_nothing_is_allocated_after_them():
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    at, fresh = [], []
    for r in range(6):
        store = _store(r)
        landed = _land(worker, _served(store))
        at.append({n: _address(a) for n, a in landed.items() if a.size})
        copied = _copied.value
        trainer._pack(landed)
        assert _copied.value == copied
        assert _bits(landed) == _wired(store)
        fresh.append(_fresh.value)
        del landed
    assert at[0] != at[1] and not set(at[0].values()) & set(at[1].values())
    assert at[2:] == [at[0], at[1], at[0], at[1]]
    assert fresh[1:] == [fresh[1]] * 5
    assert fresh[1] - fresh[0] == 4 * trainer._padded_in


@pytest.mark.parametrize("cut", [lambda s: s, lambda s: s["emb/w"][2, 1:4]],
                         ids=["store", "slice_of_one_tensor"])
def test_a_kept_store_keeps_its_bytes_and_costs_one_buffer(cut):
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    for r in range(2):                    # both buffers exist
        trainer._pack(_land(worker, _served(_store(r))))
    store = _store(7)
    landed = _land(worker, _served(store))
    trainer._pack(landed)
    kept = cut(landed)
    want = _wired(store) if isinstance(kept, dict) else _bits(
        {"x": cut(store)})
    del landed
    fresh = _fresh.value
    for r in range(3):
        trainer._pack(_land(worker, _served(_store(10 + r))))
        held = kept if isinstance(kept, dict) else {"x": kept}
        assert _bits(held) == want
    assert _fresh.value - fresh == 4 * trainer._padded_in
    del kept, held
    fresh = _fresh.value
    for r in range(3):
        trainer._pack(_land(worker, _served(_store(20 + r))))
    assert _fresh.value == fresh


def test_what_the_layout_does_not_know_falls_back_and_is_counted():
    """Unknown names and wrong sizes are ``to_array`` as ever, writable
    and the worker's own; a partial pull lands what came; an empty pull
    lands nothing."""
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    trainer._pack(_land(worker, _served(_store(0))))
    trainer._pack(_land(worker, _served(_store(1))))
    odd = {"emb/w": np.arange(5, dtype=np.float32),       # another size
           "stranger": np.arange(9, dtype=np.float32),    # another name
           "head/b": _store(2)["head/b"]}                 # lands
    fresh = _fresh.value
    got = _land(worker, _served(odd, chunks=1))
    assert _fresh.value - fresh == 4 * (5 + 9)
    assert _bits(got) == _wired(odd)
    assert got["emb/w"].flags.writeable and got["stranger"].flags.writeable
    assert _in_an_upload_buffer(trainer, got["head/b"])
    assert not _in_an_upload_buffer(trainer, got["emb/w"])
    assert not _in_an_upload_buffer(trainer, got["stranger"])
    del got
    fresh = _fresh.value
    assert _land(worker, []) == {} and _land(worker, [[]]) == {}
    partial = _land(worker, _served({"layer/w": _store(4)["layer/w"]}, chunks=1))
    assert _bits(partial) == _wired({"layer/w": _store(4)["layer/w"]})
    assert _fresh.value == fresh


def test_a_trainer_that_lends_nothing_leaves_the_worker_on_from_wire():
    class Duck:
        """A user's trainer: ``compute_gradients`` and nothing else."""

    store = _store(5)
    fresh = _fresh.value
    got = _land(_worker(Duck()), _served(store))
    assert _bits(got) == _wired(store)
    assert all(a.flags.writeable for a in got.values())
    assert _fresh.value - fresh == PAYLOAD


def test_a_retry_never_shares_memory_with_the_failed_attempts_straggler():
    """A sharded pull's attempt fails with one shard's thread still
    streaming: that thread keeps its converter, so its loan stays its own
    and the retry's store lies elsewhere; once the straggler is gone the
    buffer is lent again without an allocation."""
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    for r in range(2):
        trainer._pack(_land(worker, _served(_store(r))))
    fresh = _fresh.value
    first = {}
    failed = worker._chunk_converter(first)
    head, tail = _served(_store(30))
    failed(head)                       # ... and the attempt fails here
    store = _store(31)
    retried = _land(worker, _served(store))
    assert _fresh.value - fresh == 4 * trainer._padded_in
    go = threading.Event()
    straggler = threading.Thread(
        target=lambda: (go.wait(10), failed(tail)))
    straggler.start()
    go.set()
    straggler.join(10)
    assert not straggler.is_alive() and set(first) == set(_store(30))
    assert _bits(retried) == _wired(store)
    assert not ({_address(a) for a in first.values() if a.size}
                & {_address(a) for a in retried.values() if a.size})
    trainer._pack(retried)
    del failed, first, straggler, retried
    fresh = _fresh.value
    for r in range(3):
        trainer._pack(_land(worker, _served(_store(40 + r))))
    assert _fresh.value == fresh


@pytest.fixture
def served_over_shm(tmp_path, monkeypatch):
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", "96")   # frames of 96 B
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=str(tmp_path), learning_rate=0.5,
        autosave_period_s=3600.0))
    port = server.start()
    client = PSClient(f"127.0.0.1:{port}")
    try:
        yield server, client
    finally:
        client.close()
        server.stop()


def test_through_the_real_ring_the_frame_pool_is_left_alone(
        served_over_shm):
    """Real fused rounds over the shared-memory rings, several frames a
    serve, each frame's buffer refilled as soon as the next is asked for:
    the converter has let go of every view by then, so neither ring end
    takes a new receive buffer from the second exchange on, and the store
    the worker holds is the server's bit for bit."""
    server, client = served_over_shm
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    worker._ps = client
    server.core.initialize_parameters(
        {n: a for n, a in _store(50).items() if a.size})
    allocs, fresh = [], []
    for it in range(1, 6):
        grads = {n: a for n, a in _store(50 + it).items() if a.size}
        push, store = worker._fused_push_pull(it, grads)
        assert push.success and store is not None, push.message
        assert client.shm_active
        assert _bits(store) == _wired(server.core.get_parameters())
        copied = _copied.value
        trainer._pack({**store, "void": np.zeros((0, 3), np.float32)})
        assert _copied.value == copied
        allocs.append(obs_stats.counter("rpc.shm.frame_allocs").value)
        fresh.append(_fresh.value)
        del store
    assert allocs[1:] == [allocs[1]] * 4
    assert fresh[1:] == [fresh[1]] * 4


# ---------------------------------------------------------------- the pack

def _sgd(store, grads):
    return {n: (a - np.float32(0.1) * grads[n]).astype(np.float32)
            for n, a in store.items()}


def test_a_store_in_place_gives_the_copying_paths_bits_over_three_rounds():
    """The aliasing hazard ``_pack`` names: on the CPU client an upload
    may alias its buffer while the step runs and the next store is
    written.  Round by round, a trainer fed landed stores (the next one
    landed BEFORE this round's gradients are read) and one fed private
    copies compute the same loss and gradients to the bit, the first
    without copying a byte."""
    in_place, copying = Trainer(_Model()), Trainer(_Model())
    worker = _worker(in_place)
    store = _store(60)
    landed = _land(worker, _served(store))
    for r in range(3):
        copied = _copied.value
        buckets = in_place.compute_gradient_buckets(landed, _batch(r),
                                                    bucket_bytes=64)
        assert _copied.value == copied
        want_grads, want_loss = copying.compute_gradients(
            {n: a.copy() for n, a in store.items()}, _batch(r))
        assert _copied.value - copied == PAYLOAD
        # the serve of this round lands while the step's output is
        # still on the device, over the buffer the step did NOT upload
        ahead = _sgd(store, want_grads)
        landed_next = _land(worker, _served(ahead))
        assert buckets.loss == want_loss
        got = dict(buckets)
        assert _bits(got) == _bits(want_grads)
        store, landed = ahead, landed_next


def _mesh_trainer():
    from parameter_server_distributed_tpu.parallel.mesh import MeshConfig

    trainer = Trainer(_Model(), mesh_config=MeshConfig(fsdp=4))
    assert trainer._padded_in > trainer._packed_size   # a padded tail
    return trainer


@pytest.mark.parametrize("case", ["foreign", "half_in_place",
                                  "other_buffer", "padded_mesh"])
def test_the_pack_copies_exactly_what_is_not_in_place(case):
    make = _mesh_trainer if case == "padded_mesh" else lambda: Trainer(_Model())
    trainer, reference = make(), make()
    worker = _worker(trainer)
    store, batch = _store(70), _batch(0)
    want_grads, want_loss = reference.compute_gradients(store, batch)
    landed = _land(worker, _served(store))
    expect = 0
    if case == "foreign":
        landed = {n: a.copy() for n, a in store.items()}
        expect = 4 * trainer._packed_size
    elif case == "half_in_place":
        for name in ("emb/w", "scale"):
            landed[name] = store[name].copy()
        expect = 4 * (42 + 1)
    elif case == "other_buffer":
        # uploaded once already: the store now lies in the buffer whose
        # turn it is NOT, and is still uploaded from there
        trainer._pack(landed)
        assert _address(trainer.lend_store()["emb/w"]) != _address(
            landed["emb/w"])
    copied = _copied.value
    grads, loss = trainer.compute_gradients(landed, batch)
    assert _copied.value - copied == expect
    assert loss == want_loss and _bits(grads) == _bits(want_grads)
    if case == "padded_mesh":
        flat = trainer._pack(landed)
        assert not flat[trainer._packed_size:].any()


def test_a_store_made_elsewhere_never_lands_on_a_store_somebody_holds():
    """``round_checks`` and every test hand the trainer a store it did
    not lend while the worker still holds the one it pulled: the copy
    goes to another buffer and the pulled store keeps its bytes."""
    trainer = Trainer(_Model())
    worker = _worker(trainer)
    store = _store(80)
    held = _land(worker, _served(store))
    fresh = _fresh.value
    for r in range(3):
        trainer.compute_gradients(_store(81 + r), _batch(r))
        assert _bits(held) == _wired(store)
    assert _fresh.value - fresh == 2 * 4 * trainer._padded_in
    assert not any(exported(buf) for buf in trainer._pack_bufs)
