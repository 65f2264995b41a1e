"""Wire-format codec tests.

Golden byte vectors were produced with protoc-generated Python gencode for
the reference IDL (proto/parameter_server.proto, proto/coordinator.proto) and
verified byte-identical in both directions; they are embedded here so the
test suite needs no protoc/grpc_tools at runtime.
"""

import numpy as np
import pytest

from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc import wire

GOLDENS = {
    "tensor": "0a086c61796572302f77120202031a180000c03f000010c0000000000000704095bfd633000000bf",
    "gradient_update": "080310111a280a086c61796572302f77120202031a180000c03f000010c0000000000000704095bfd633000000bf1a140a01621201031a0ccdcccc3dcdcc4c3e9a99993e",
    "push_response": "080112026f6b1811200128043004",
    "pull_negative": "08ffffffffffffffffff01",
    "worker_info": "0807120831302e302e302e35189687032208776f726b65722d37",
    "heartbeat": "08071002",
    "heartbeat_resp": "080110bb948ba98533",
    "list_workers": "0a1a0807120831302e302e302e35189687032208776f726b65722d371001",
    "load_ckpt": "080112066c6f61646564180322280a086c61796572302f77120202031a180000c03f000010c0000000000000704095bfd633000000bf",
}


def _tensor():
    return m.Tensor(name="layer0/w", shape=[2, 3],
                    data=np.array([1.5, -2.25, 0.0, 3.75, 1e-7, -0.5], np.float32),
                    dtype=0)


def _golden_msgs():
    t = _tensor()
    return {
        "tensor": t,
        "gradient_update": m.GradientUpdate(
            worker_id=3, iteration=17,
            gradients=[t, m.Tensor.from_array("b", np.array([0.1, 0.2, 0.3], np.float32))]),
        "push_response": m.PushResponse(success=True, message="ok", iteration=17,
                                        aggregation_complete=True, workers_received=4,
                                        total_workers=4),
        "pull_negative": m.PullRequest(worker_id=-1, iteration=0),
        "worker_info": m.WorkerInfo(worker_id=7, address="10.0.0.5", port=50070,
                                    hostname="worker-7"),
        "heartbeat": m.HeartbeatRequest(worker_id=7, status=m.WorkerStatus.CHECKPOINTING),
        "heartbeat_resp": m.HeartbeatResponse(success=True, timestamp=1753775000123),
        "list_workers": m.ListWorkersResponse(
            workers=[m.WorkerInfo(worker_id=7, address="10.0.0.5", port=50070,
                                  hostname="worker-7")],
            total_workers=1),
        "load_ckpt": m.LoadCheckpointResponse(success=True, message="loaded", epoch=3,
                                              parameters=[t]),
    }


@pytest.mark.parametrize("key", sorted(GOLDENS))
def test_encode_matches_protoc_golden(key):
    msg = _golden_msgs()[key]
    assert msg.encode().hex() == GOLDENS[key]


@pytest.mark.parametrize("key", sorted(GOLDENS))
def test_decode_golden_roundtrip(key):
    msg = _golden_msgs()[key]
    decoded = type(msg).decode(bytes.fromhex(GOLDENS[key]))
    assert decoded == msg
    assert decoded.encode().hex() == GOLDENS[key]


def test_varint_roundtrip():
    for v in [0, 1, 127, 128, 300, 2**31 - 1, 2**63 - 1, 2**64 - 1]:
        buf = wire.encode_varint(v)
        out, pos = wire.decode_varint(buf, 0)
        assert out == v and pos == len(buf)


def test_negative_int32_ten_byte_varint():
    req = m.PullRequest(worker_id=-1)
    assert req.encode() == bytes.fromhex("08ffffffffffffffffff01")
    assert m.PullRequest.decode(req.encode()).worker_id == -1


def test_default_elision():
    # proto3: default-valued scalar fields are omitted
    assert m.PushResponse().encode() == b""
    assert m.PullRequest(worker_id=0, iteration=0).encode() == b""


def test_unknown_field_skipped():
    # field 99 varint prepended — decoder must skip it
    extra = wire.encode_varint((99 << 3) | 0) + wire.encode_varint(42)
    body = extra + m.PullRequest(worker_id=5, iteration=2).encode()
    msg = m.PullRequest.decode(body)
    assert msg.worker_id == 5 and msg.iteration == 2


def test_unpacked_repeated_scalars_accepted():
    # proto3 decoders must accept unpacked encodings of packed fields:
    # shape as two separate varint fields, data as two separate fixed32 fields
    import struct
    body = b"".join([
        wire.encode_varint((2 << 3) | 0), wire.encode_varint(2),
        wire.encode_varint((2 << 3) | 0), wire.encode_varint(3),
        wire.encode_varint((3 << 3) | 5), struct.pack("<f", 1.5),
        wire.encode_varint((3 << 3) | 5), struct.pack("<f", 2.5),
    ])
    t = m.Tensor.decode(body)
    assert t.shape == [2, 3]
    np.testing.assert_array_equal(np.asarray(t.data), np.array([1.5, 2.5], np.float32))


def test_tensor_array_roundtrip(rng):
    arr = rng.standard_normal((4, 8, 3)).astype(np.float32)
    t = m.Tensor.from_array("x", arr)
    rt = m.Tensor.decode(t.encode())
    np.testing.assert_array_equal(rt.to_array(), arr)
    assert rt.name == "x" and rt.shape == [4, 8, 3]


def test_large_tensor_fast_path(rng):
    arr = rng.standard_normal((512, 512)).astype(np.float32)
    t = m.Tensor.from_array("big", arr)
    encoded = t.encode()
    rt = m.Tensor.decode(encoded)
    np.testing.assert_array_equal(rt.to_array(), arr)
    # wire size ≈ 4 bytes/element + small header
    assert len(encoded) < arr.size * 4 + 64


def test_empty_messages():
    assert m.ListWorkersRequest().encode() == b""
    assert isinstance(m.ListWorkersRequest.decode(b""), m.ListWorkersRequest)


# ---------------------------------------------------------------------------
# Packed-payload transport extension (Tensor fields 5/6, PullRequest field 3).
# The roundtrip tests take `each_codec` (tests/conftest.py): every run covers
# BOTH the numpy oracle (PSDT_NATIVE=0) and the native C++ codec, so the
# fallback path can never rot.
# ---------------------------------------------------------------------------

def test_raw_f32_packed_roundtrip_exact(rng, each_codec):
    arr = rng.standard_normal((64, 32)).astype(np.float32)
    t = m.Tensor.from_array("x", arr, wire_dtype=m.WIRE_RAW_F32)
    rt = m.Tensor.decode(t.encode())
    np.testing.assert_array_equal(rt.to_array(), arr)
    assert rt.packed_dtype == m.WIRE_RAW_F32
    assert np.asarray(rt.data).size == 0  # payload rides in field 5 only


def test_bf16_packed_halves_bytes_and_rounds_rne(rng, each_codec):
    import ml_dtypes

    arr = rng.standard_normal((256, 64)).astype(np.float32)
    f32 = m.Tensor.from_array("x", arr).encode()
    bf16 = m.Tensor.from_array("x", arr, wire_dtype=m.WIRE_BF16).encode()
    assert len(bf16) < len(f32) * 0.55  # ~half the payload
    rt = m.Tensor.decode(bf16).to_array()
    expected = arr.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(rt, expected)
    # bf16 keeps 8 exponent bits: values survive with ~3 decimal digits
    np.testing.assert_allclose(rt, arr, rtol=8e-3)


def test_reference_schema_skips_packed_fields(rng):
    """A reference peer (fields 1-4 only) must skip fields 5/6 cleanly per
    proto3 unknown-field rules."""

    class ReferenceTensor(wire.Message):
        FIELDS = m.Tensor.FIELDS[:4]

    arr = rng.standard_normal((8,)).astype(np.float32)
    encoded = m.Tensor.from_array("x", arr, wire_dtype=m.WIRE_BF16).encode()
    ref = ReferenceTensor.decode(encoded)
    assert ref.name == "x" and ref.shape == [8]
    assert np.asarray(ref.data).size == 0  # payload invisible, no crash


def test_pull_request_wire_dtype_default_elided():
    # a default-encoding PullRequest stays byte-identical to the reference's
    assert m.PullRequest(worker_id=1, iteration=2).encode() == \
        m.PullRequest(worker_id=1, iteration=2, wire_dtype=m.WIRE_F32).encode()
    rt = m.PullRequest.decode(
        m.PullRequest(worker_id=1, iteration=2, wire_dtype=m.WIRE_BF16).encode())
    assert rt.wire_dtype == m.WIRE_BF16


def test_int8_packed_quarter_bytes_and_error_bound(rng, each_codec):
    arr = rng.standard_normal((128, 64)).astype(np.float32) * 3.0
    f32 = m.Tensor.from_array("g", arr).encode()
    int8 = m.Tensor.from_array("g", arr, wire_dtype=m.WIRE_INT8).encode()
    assert len(int8) < len(f32) * 0.3  # ~quarter the payload
    rt = m.Tensor.decode(int8).to_array()
    scale = np.abs(arr).max() / 127.0
    assert np.abs(rt - arr).max() <= scale * 0.5 + 1e-7  # round-to-nearest
    # zeros encode/decode cleanly (scale guard)
    z = m.Tensor.from_array("z", np.zeros(16, np.float32),
                            wire_dtype=m.WIRE_INT8)
    np.testing.assert_array_equal(m.Tensor.decode(z.encode()).to_array(),
                                  np.zeros(16, np.float32))


def test_topk_packed_sparse_roundtrip(rng, each_codec):
    """WIRE_TOPK keeps exactly the k largest-|value| entries (bf16-
    precision values at their original indices, zeros elsewhere) and the
    payload shrinks with the density."""
    arr = rng.standard_normal((64, 32)).astype(np.float32)
    t = m.Tensor.from_array("g", arr, wire_dtype=m.WIRE_TOPK,
                            topk_density=0.1)
    rt = m.Tensor.decode(t.encode()).to_array()
    k = max(1, round(arr.size * 0.1))
    flat = arr.reshape(-1)
    keep = np.argsort(np.abs(flat))[-k:]
    assert np.count_nonzero(rt) == k
    mask = np.zeros(arr.size, bool)
    mask[keep] = True
    # kept entries match to bf16 precision; everything else is zero
    np.testing.assert_allclose(rt.reshape(-1)[mask], flat[mask],
                               rtol=8e-3, atol=1e-6)
    np.testing.assert_array_equal(rt.reshape(-1)[~mask], 0.0)
    # ~density * bf16 payload: 6 bytes/entry vs 4 dense f32 bytes
    f32 = m.Tensor.from_array("g", arr).encode()
    assert len(t.encode()) < len(f32) * 0.2
    # degenerate cases: empty tensor and k rounding to >= 1
    empty = m.Tensor.from_array("e", np.zeros((0,), np.float32),
                                wire_dtype=m.WIRE_TOPK)
    assert m.Tensor.decode(empty.encode()).to_array().size == 0
    tiny = m.Tensor.from_array("t", np.ones(3, np.float32),
                               wire_dtype=m.WIRE_TOPK, topk_density=0.01)
    assert np.count_nonzero(
        m.Tensor.decode(tiny.encode()).to_array()) == 1
    # 0-d scalar: np.prod([]) == 1, so it round-trips as one element
    # (shape (1,) through packed encodings; .item() — float() on a
    # 1-element array is deprecated in NumPy 1.25+)
    s = m.Tensor.from_array("s", np.float32(3.5), wire_dtype=m.WIRE_TOPK)
    assert m.Tensor.decode(s.encode()).to_array().item() == 3.5
    # u32 index space: a >= 2**32-element tensor would wrap indices on
    # decode, so encode refuses loudly (zero-stride broadcast view: 4B
    # elements without the 16 GB allocation)
    big = np.broadcast_to(np.float32(1.0), (2**32,))
    with pytest.raises(ValueError, match="u32"):
        m.Tensor.from_array("g", big, wire_dtype=m.WIRE_TOPK)
    # density > 1 clamps k to the tensor size instead of corrupting
    over = m.Tensor.from_array("o", np.ones(10, np.float32),
                               wire_dtype=m.WIRE_TOPK, topk_density=2.0)
    np.testing.assert_array_equal(
        m.Tensor.decode(over.encode()).to_array(), np.ones(10, np.float32))
    # the density default has ONE owner shared by wire, config, and CLI
    from parameter_server_distributed_tpu.config import WorkerConfig
    assert WorkerConfig().topk_density == m.TOPK_DEFAULT_DENSITY


def test_float64_dtype_tag_roundtrip(rng):
    """The reference IDL declares dtype=1 float64 (proto:23) while carrying
    data as `repeated float`; from_array marks float64 inputs and to_array
    honors the tag by upcasting, so a dtype=1 tensor round-trips at the
    declared dtype instead of being silently retyped float32."""
    arr = rng.standard_normal((4, 3))  # float64
    t = m.Tensor.from_array("w", arr)
    assert t.dtype == m.DTYPE_FLOAT64
    rt = m.Tensor.decode(t.encode())
    out = rt.to_array()
    assert out.dtype == np.float64
    np.testing.assert_allclose(out, arr, rtol=1e-6)  # f32 wire precision
    # float32 input keeps dtype=0 and decodes float32
    t32 = m.Tensor.from_array("w", arr.astype(np.float32))
    assert t32.dtype == m.DTYPE_FLOAT32
    assert m.Tensor.decode(t32.encode()).to_array().dtype == np.float32


def test_raw_f32_decode_is_writable(rng):
    """Every decode path returns a writable array (frombuffer views are
    read-only; in-place aggregation must work on any encoding)."""
    arr = rng.standard_normal(32).astype(np.float32)
    for wd in (m.WIRE_F32, m.WIRE_RAW_F32, m.WIRE_BF16, m.WIRE_INT8,
               m.WIRE_TOPK):
        out = m.Tensor.decode(
            m.Tensor.from_array("w", arr, wire_dtype=wd).encode()).to_array()
        out += 1.0  # raises on read-only arrays


def test_lazy_array_payload_encodes_identically_to_eager_bytes(rng):
    """ArrayPayload (fused convert-into-buffer encode) must produce byte-
    identical messages to an eager astype+tobytes payload, and to_array on
    a locally built tensor must return the same quantized values a wire
    round-trip would."""
    from parameter_server_distributed_tpu.rpc.wire import ArrayPayload

    arr = rng.standard_normal((33, 17)).astype(np.float32)
    for wd, np_dtype in ((m.WIRE_BF16, None), (m.WIRE_RAW_F32, "<f4")):
        t = m.Tensor.from_array("w", arr, wire_dtype=wd)
        assert isinstance(t.packed, ArrayPayload)
        eager = m.Tensor(name="w", shape=list(arr.shape),
                         packed=t.packed.tobytes(), packed_dtype=wd)
        assert t.encode() == eager.encode()
        # local read-back equals the decoded wire value
        decoded = m.Tensor.decode(t.encode())
        np.testing.assert_array_equal(t.to_array(), decoded.to_array())


def test_writer_output_is_plain_bytes(rng):
    """encode() must hand gRPC a real `bytes` object (its cython layer
    rejects bytearray/memoryview), produced without a final whole-message
    copy (wire._Writer's uninitialized-bytes backing)."""
    t = m.Tensor.from_array("w", rng.standard_normal(257).astype(np.float32),
                            wire_dtype=m.WIRE_BF16)
    buf = m.GradientUpdate(worker_id=1, iteration=2, gradients=[t]).encode()
    assert type(buf) is bytes
    back = m.GradientUpdate.decode(buf)
    assert back.worker_id == 1 and back.gradients[0].name == "w"


# ------------------------------------------ borrowed decode (PR 41)
# A sink that folds at once is lent the frame's own views; everybody else
# still gets what ``Tensor.to_array`` has always given.

def _copied_bytes():
    from parameter_server_distributed_tpu.obs import stats as obs_stats
    return obs_stats.counter("rpc.server.decode.copied_bytes").value


@pytest.mark.parametrize("wire_dtype", [m.WIRE_F32, m.WIRE_RAW_F32,
                                        m.WIRE_BF16, m.WIRE_INT8,
                                        m.WIRE_TOPK],
                         ids=["f32", "raw_f32", "bf16", "int8", "topk"])
def test_borrow_array_is_to_array_without_the_last_copy(rng, wire_dtype):
    """Same values, shape and dtype as ``to_array`` on every wire; on the
    float32 wire the borrowed array is a read-only view of the frame and
    ``to_array`` beside it is still an owned, writable copy."""
    arr = rng.standard_normal((6, 5)).astype(np.float32)
    frame = m.Tensor.from_array("w", arr, wire_dtype=wire_dtype).encode()
    decoded = m.Tensor.decode(frame)
    owned, lent = decoded.to_array(), decoded.borrow_array()
    assert lent.shape == owned.shape == (6, 5)
    assert lent.dtype == owned.dtype == np.float32
    np.testing.assert_array_equal(lent, owned)
    assert owned.flags.writeable
    assert not np.shares_memory(owned, lent)
    if wire_dtype == m.WIRE_F32:
        assert not lent.flags.writeable
        assert np.shares_memory(lent, np.frombuffer(frame, np.uint8))
        with pytest.raises(ValueError):
            lent += 1.0
    owned += 1.0    # the contract every other caller relies on


def test_borrow_array_honours_the_float64_tag_and_a_scalar(rng):
    arr = rng.standard_normal((4, 3))   # float64
    decoded = m.Tensor.decode(m.Tensor.from_array("w", arr).encode())
    lent = decoded.borrow_array()
    assert lent.dtype == np.float64 and lent.shape == (4, 3)
    np.testing.assert_array_equal(lent, decoded.to_array())
    scalar = m.Tensor.decode(m.Tensor.from_array(
        "s", np.float32(2.5)).encode())
    assert scalar.borrow_array().shape == scalar.to_array().shape
    assert float(scalar.borrow_array().reshape(-1)[0]) == 2.5


@pytest.mark.parametrize("borrow", [False, True], ids=["owned", "borrowed"])
def test_decode_gradients_counts_what_it_copies_out_of_a_frame(rng, borrow):
    """Owned mode copies every float32-wire tensor out of its frame and
    counts the bytes (``rpc.server.decode.copied_bytes``); borrowed mode
    copies and counts nothing and hands out the frame's read-only views;
    a packed wire makes new arrays either way and counts nothing."""
    from parameter_server_distributed_tpu.rpc.data_plane import (
        decode_gradients)

    grads = {"a": rng.standard_normal((7, 3)).astype(np.float32),
             "b": rng.standard_normal(11).astype(np.float32)}
    frame = m.GradientUpdate(worker_id=1, iteration=2, gradients=[
        m.Tensor.from_array(k, v) for k, v in grads.items()]).encode()
    chunk = m.GradientUpdate.decode(frame)
    before = _copied_bytes()
    out = decode_gradients(chunk.gradients, borrow=borrow)
    assert _copied_bytes() - before == (0 if borrow else 4 * (21 + 11))
    for name, want in grads.items():
        np.testing.assert_array_equal(out[name], want)
        assert out[name].flags.writeable is (not borrow)
        assert np.shares_memory(
            out[name], np.frombuffer(frame, np.uint8)) is borrow
    packed = m.GradientUpdate.decode(m.GradientUpdate(
        worker_id=1, iteration=2, gradients=[m.Tensor.from_array(
            "a", grads["a"], wire_dtype=m.WIRE_BF16)]).encode())
    before = _copied_bytes()
    out = decode_gradients(packed.gradients, borrow=borrow)
    assert _copied_bytes() == before and out["a"].flags.writeable
