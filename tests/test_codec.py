"""Wire-codec byte-identity suite (ISSUE 6).

The native C++ codec (rpc/codec.py NativeCodec) is a pure speed
substitution for the numpy reference (PythonCodec): every packed payload
it emits must be BIT-IDENTICAL to the oracle's, and decodes must be
bit-identical in both cross directions (native-encoded -> Python-decoded
and vice versa).  The fuzz matrix covers every packed wire dtype, shapes
from empty through multi-MB, adversarial values (ties, specials,
denormals), chunk budgets, and group splits.
"""

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.core.tensor import from_wire, to_wire
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc.codec import (
    NativeCodec, PythonCodec, active_codec, payload_nbytes, topk_indices,
    topk_k)
from parameter_server_distributed_tpu.rpc.data_plane import (
    encode_parameter_records, split_tensors)

PACKED = ("raw", "bf16", "int8", "topk")

needs_native = pytest.mark.skipif(native.lib() is None,
                                  reason="native lib unavailable (no g++)")


def _cases(rng):
    """The fuzz corpus: (name, flat f32 array) pairs chosen to hit RNE
    ties, quantization clamp edges, top-k threshold ties, specials, and
    denormals — everywhere the two implementations could diverge."""
    return [
        ("empty", np.zeros(0, np.float32)),
        ("scalar", np.float32(1.5).reshape(())),
        ("ones", np.ones(257, np.float32)),
        ("ties", np.repeat(np.float32([3, -3, 1, 3, 2]), 100)),
        ("small", rng.standard_normal(33).astype(np.float32)),
        ("normal", (rng.standard_normal(10_007) * 5).astype(np.float32)),
        ("large", rng.standard_normal((128, 513)).astype(np.float32)),
        ("denormal", (rng.standard_normal(1_001) * 1e-40).astype(
            np.float32)),
        ("huge-vals", (rng.standard_normal(501) * 3e38).astype(np.float32)),
        ("specials", np.array([0.0, -0.0, np.inf, -np.inf, 1e-45, -1e-45,
                               3.4028235e38, 1.0000001, 0.99999994],
                              np.float32)),
        ("halves", (rng.integers(-255, 256, 2_001).astype(np.float32)
                    / 2.0)),
    ]


def _encode_with(codec_enabled: bool, arr, wire_dtype, density=0.1):
    native.set_enabled(codec_enabled)
    try:
        t = m.Tensor.from_array("x", arr, wire_dtype=wire_dtype,
                                topk_density=density)
        return t.encode()
    finally:
        native.set_enabled(True)


@needs_native
@pytest.mark.parametrize("wire_name", PACKED)
def test_fuzz_encode_byte_identity(rng, wire_name):
    """Native and Python encodes of the same tensor are byte-identical
    across the whole corpus — the codec contract."""
    wd = m.WIRE_DTYPE_NAMES[wire_name]
    for name, arr in _cases(rng):
        nat = _encode_with(True, arr, wd)
        py = _encode_with(False, arr, wd)
        assert nat == py, f"{wire_name}/{name}: native != python bytes"


@needs_native
@pytest.mark.parametrize("wire_name", PACKED)
def test_fuzz_cross_decode_bit_identity(rng, wire_name):
    """native-encoded -> Python-decoded and Python-encoded ->
    native-decoded produce bit-identical f32 arrays (NaN-free corpus:
    payload bit-identity already covers NaN payloads)."""
    wd = m.WIRE_DTYPE_NAMES[wire_name]
    for name, arr in _cases(rng):
        blob = _encode_with(True, arr, wd)
        native.set_enabled(False)
        try:
            via_python = m.Tensor.decode(blob).to_array()
        finally:
            native.set_enabled(True)
        via_native = m.Tensor.decode(_encode_with(False, arr, wd)).to_array()
        assert via_python.tobytes() == via_native.tobytes(), \
            f"{wire_name}/{name}: cross-decode mismatch"
        # 0-d scalars ride the wire as 1-element tensors (shape list is
        # empty — pre-existing wire semantics); all real shapes round-trip
        expect_shape = np.asarray(arr).shape or (1,)
        assert via_python.shape == expect_shape


@needs_native
def test_fuzz_record_groups_and_chunk_budgets(rng):
    """Whole-store encodes through the chunked record path — the exact
    bytes the serve cache and the streamed pulls put on the wire — are
    identical native vs Python for every (dtype, chunk budget, split)
    combination."""
    store = {f"t{i}": (rng.standard_normal(sz) * 3).astype(np.float32)
             for i, sz in enumerate((1, 33, 1024, 4097, 20_000))}
    for wire_name in PACKED:
        wd = m.WIRE_DTYPE_NAMES[wire_name]
        for budget in (256, 16 << 10, 32 << 20):
            bodies = {}
            for enabled in (True, False):
                native.set_enabled(enabled)
                try:
                    groups = list(split_tensors(
                        to_wire(store, wire_dtype=wd), budget))
                    bodies[enabled] = [
                        encode_parameter_records(
                            g, lambda size: memoryview(bytearray(size)))
                        for g in groups]
                finally:
                    native.set_enabled(True)
            assert bodies[True] == bodies[False], \
                f"{wire_name} budget={budget}"


def test_python_codec_is_default_oracle(each_codec, rng):
    """Round-trip through whichever codec the fixture selected: values
    decode to the documented precision and the packed layout prefix (k,
    scale) is well-formed.  Runs under BOTH fixture legs so the fallback
    path cannot rot."""
    arr = (rng.standard_normal(4_096) * 7).astype(np.float32)
    for wire_name in PACKED:
        wd = m.WIRE_DTYPE_NAMES[wire_name]
        t = m.Tensor.from_array("x", arr, wire_dtype=wd, topk_density=0.25)
        rt = m.Tensor.decode(t.encode()).to_array()
        assert rt.shape == arr.shape
        if wire_name == "raw":
            np.testing.assert_array_equal(rt, arr)
        elif wire_name == "bf16":
            np.testing.assert_allclose(rt, arr, rtol=1e-2)
        elif wire_name == "int8":
            assert np.max(np.abs(rt - arr)) <= float(
                np.max(np.abs(arr))) / 127.0 + 1e-6
        else:  # topk: kept entries bf16-exact, rest zero
            k = topk_k(arr.size, 0.25)
            assert np.count_nonzero(rt) <= k


def test_build_failure_is_retryable(monkeypatch):
    """The sticky-failure fix: a failed build must not latch forever —
    reset_for_retry() and set_enabled(True) both clear the tried flag
    when no library was bound, so the next lib() call rebuilds.  (Lives
    here, NOT in test_native.py, whose module-level skipif would skip it
    on exactly the no-g++ hosts it exercises.)"""
    native.reset_for_retry()
    monkeypatch.setattr(native, "_build", lambda: None)  # doomed build
    assert native.lib() is None
    assert native._tried is True
    monkeypatch.undo()
    # set_enabled(True) with no lib bound clears the latch...
    native.set_enabled(True)
    assert native._tried is False
    # ...so the next lib() genuinely retries (and succeeds where g++
    # exists; where it doesn't, it retries and records the failure again)
    rebuilt = native.lib()
    assert native._tried is True
    if rebuilt is not None:
        assert native.lib() is rebuilt


def test_reset_for_retry_drops_bound_lib():
    native.reset_for_retry()
    assert native._lib is None and native._tried is False
    first = native.lib()
    if first is None:
        pytest.skip("native lib unavailable (no g++)")
    native.reset_for_retry()
    again = native.lib()
    assert again is not None and again is not first  # fresh CDLL binding


def test_set_enabled_false_does_not_clear_latch(monkeypatch):
    """Disabling must not reset the tried flag (only re-enabling does):
    PSDT_NATIVE=0 A/B flips should not force rebuild probes."""
    native.reset_for_retry()
    monkeypatch.setattr(native, "_build", lambda: None)
    assert native.lib() is None
    native.set_enabled(False)
    assert native._tried is True
    assert native.lib() is None  # disabled: no probe at all
    native.set_enabled(True)  # re-enable clears it for the next test
    monkeypatch.undo()
    native.reset_for_retry()


def test_codec_selection_follows_native_toggle():
    """active_codec() resolves per call: native when the lib is bound and
    enabled, the Python oracle otherwise — and reports the choice via
    the rpc.codec.native gauge."""
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    native.set_enabled(False)
    try:
        assert isinstance(active_codec(), PythonCodec)
        assert not isinstance(active_codec(), NativeCodec)
        assert obs_stats.gauge("rpc.codec.native").value == 0.0
    finally:
        native.set_enabled(True)
    if native.lib() is not None:
        assert isinstance(active_codec(), NativeCodec)
        assert obs_stats.gauge("rpc.codec.native").value == 1.0


def test_topk_nan_gradients_still_encode_exactly_k(rng):
    """A diverging run's NaN gradients must not kill the topk push: NaNs
    sort as the largest values (numpy convention), the selection stays
    exactly k, and native/Python stay byte-identical."""
    for n_nan in (1, 5, 600):
        arr = rng.standard_normal(1_000).astype(np.float32)
        nan_at = rng.choice(arr.size, size=n_nan, replace=False)
        arr[nan_at] = np.nan
        k = 50
        idx = topk_indices(arr, k)
        assert idx.size == k
        assert np.all(np.diff(idx.astype(np.int64)) > 0)  # ascending
        py = _encode_with(False, arr, m.WIRE_TOPK, density=k / arr.size)
        if native.lib() is not None:
            nat = _encode_with(True, arr, m.WIRE_TOPK,
                               density=k / arr.size)
            assert nat == py, f"NaN topk bytes diverge (n_nan={n_nan})"
        # decodes on both paths without error
        out = m.Tensor.decode(py).to_array()
        assert out.shape == arr.shape


def test_topk_malformed_header_rejected(rng):
    """A hostile/corrupt payload whose k claims more entries than the
    payload carries must raise on decode (never read past the buffer —
    the native path declines and the Python path raises)."""
    bad = np.uint32(1000).tobytes() + b"\x00" * 16  # k=1000, 16 bytes
    t = m.Tensor(name="x", shape=[64], packed=bad,
                 packed_dtype=m.WIRE_TOPK)
    with pytest.raises(ValueError):
        t.to_array()
    if native.lib() is not None:
        out = np.zeros(64, np.float32)
        assert native.topk_unpack_native(bad, out) is False
        assert native.topk_unpack_native(b"\x01", out) is False


def test_topk_selection_deterministic_tiebreak():
    """The codec contract's tie-break: |v| strictly above the threshold
    always kept; threshold ties fill ascending by index."""
    flat = np.float32([2.0, -5.0, 2.0, 2.0, 7.0])
    idx = topk_indices(flat, 3)
    # |7| and |-5| above threshold 2; first tied index (0) fills slot 3
    assert idx.tolist() == [0, 1, 4]
    assert idx.dtype == np.dtype("<u4")
    # k >= n keeps everything
    assert topk_indices(flat, 5).tolist() == [0, 1, 2, 3, 4]


def test_payload_nbytes_matches_encodes(rng):
    arr = rng.standard_normal(1_000).astype(np.float32)
    for wire_name in PACKED:
        wd = m.WIRE_DTYPE_NAMES[wire_name]
        t = m.Tensor.from_array("x", arr, wire_dtype=wd, topk_density=0.05)
        k = topk_k(arr.size, 0.05) if wd == m.WIRE_TOPK else 0
        assert len(t.packed) == payload_nbytes(wd, arr.size, k)
        assert len(t.packed.tobytes()) == len(t.packed)


def test_lazy_payload_caches_single_quantize(rng):
    """to_array() before an encode (the error-feedback residual pattern)
    must not quantize twice: the materialized bytes are cached and the
    encode replays them."""
    arr = rng.standard_normal(512).astype(np.float32)
    t = m.Tensor.from_array("g", arr, wire_dtype=m.WIRE_INT8)
    first = t.to_array()
    cached = t.packed._cache
    assert cached is not None
    blob = t.encode()
    assert t.packed._cache is cached  # same object: no re-pack
    np.testing.assert_array_equal(m.Tensor.decode(blob).to_array(), first)


def test_from_wire_roundtrip_under_each_codec(each_codec, rng):
    """The worker/server store conversion path (to_wire/from_wire) works
    identically under both codec backends."""
    store = {"w": rng.standard_normal((17, 9)).astype(np.float32),
             "b": rng.standard_normal(23).astype(np.float32)}
    for wire_name in PACKED:
        wd = m.WIRE_DTYPE_NAMES[wire_name]
        rt = from_wire(m.ParameterUpdate.decode(m.ParameterUpdate(
            iteration=1, parameters=to_wire(store, wire_dtype=wd),
            ready=True).encode()).parameters)
        assert set(rt) == set(store)
        for name in store:
            assert rt[name].shape == store[name].shape
            assert rt[name].flags.writeable


@needs_native
def test_reference_shaped_unary_peer_interoperates(tmp_path, rng):
    """Acceptance: a reference-shaped peer (the 5 unary RPCs only, plain
    repeated-float tensors) pushes and pulls against a service running
    the NATIVE codec with results identical to the numpy path — the
    codec swap is invisible at the protocol level."""
    import grpc

    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)
    from parameter_server_distributed_tpu.core.ps_core import (
        ParameterServerCore)
    from parameter_server_distributed_tpu.rpc.service import (
        RpcClient, bind_service, make_server)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServerService)

    native.set_enabled(True)
    core = ParameterServerCore(total_workers=1)
    w0 = rng.standard_normal(64).astype(np.float32)
    core.initialize_parameters({"w": w0.copy()})
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
        with RpcClient(f"127.0.0.1:{port}", m.PARAMETER_SERVER_SERVICE,
                       m.PARAMETER_SERVER_METHODS) as ref:
            push = ref.call("ReceiveGradients", m.GradientUpdate(
                worker_id=0, iteration=1,
                gradients=[m.Tensor.from_array(
                    "w", np.full(64, 0.5, np.float32))]))
            assert push.success and push.aggregation_complete
            pulled = ref.call("ServeParameters",
                              m.PullRequest(worker_id=0, iteration=1))
            # reference encoding served: packed fields elided
            assert pulled.parameters[0].packed_dtype == m.WIRE_F32
            np.testing.assert_allclose(pulled.parameters[0].to_array(),
                                       w0 - 0.5, rtol=1e-5, atol=1e-6)
    finally:
        server.stop(0)
        service.shm_server.close()
