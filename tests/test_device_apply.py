"""Accelerator-resident sharded apply (ISSUE 11, core/device_apply.py +
async_sgd ShardedDeviceOptimizer): the f32 bit-exactness oracle against
the numpy path across optimizers x stripe counts x fold residences,
dequantize-on-device byte-compat with the codec oracle (and the native
C++ kernels when buildable), checkpoint round-trips of device slot state
across restore stripe counts and across the host/device optimizer
families, the make_optimizer failure matrix, the device_fold gate, and
a lockcheck-marked concurrent push/close/serve hammer."""

import os
import threading

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
    ShardedDeviceOptimizer)
from parameter_server_distributed_tpu.checkpoint.manager import (
    CheckpointManager)
from parameter_server_distributed_tpu.core import device_apply
from parameter_server_distributed_tpu.core.optimizer import (
    SGD, Adam, AdamW, Lion, Momentum, make_optimizer)
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import codec as codec_mod
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc.data_plane import decode_gradients
from parameter_server_distributed_tpu.core.tensor import to_wire


def _jnp():
    import jax.numpy as jnp

    return jnp


@pytest.fixture
def numpy_oracle():
    """Pin the pure-numpy host path (the bit-exactness oracle): the
    native fused adam differs from numpy in the v-slot rounding, so the
    oracle comparisons must not ride the C++ kernels."""
    native.set_enabled(False)
    try:
        yield
    finally:
        native.set_enabled(
            os.environ.get("PSDT_NATIVE", "1").lower()
            not in ("0", "false"))


def _shapes():
    # odd sizes + a matrix (exercises the adamw/lion decay mask lanes)
    return {"emb/w": (129, 33), "l0/w": (64, 65), "l0/b": (65,),
            "head/w": (33, 17), "odd": (513,)}


def _stores_equal(a, b) -> bool:
    if set(a) != set(b):
        return False
    return all(np.asarray(a[k], np.float32).tobytes()
               == np.asarray(b[k], np.float32).tobytes() for k in a)


# --------------------------------------------------------------- oracle
@pytest.mark.parametrize("rule", ShardedDeviceOptimizer.RULES)
def test_optimizer_oracle_bit_identical(rule, numpy_oracle, rng):
    """Raw apply_shard: device == numpy bit for bit over several steps,
    including pass-through names (a shard with no gradient for them)."""
    shapes = _shapes()
    host = make_optimizer(rule, 0.01)
    dev = ShardedDeviceOptimizer(rule, 0.01)
    params_h = {k: rng.standard_normal(s).astype(np.float32)
                for k, s in shapes.items()}
    params_d = {k: v.copy() for k, v in params_h.items()}
    for step in range(5):
        grads = {k: rng.standard_normal(s).astype(np.float32)
                 for k, s in shapes.items()}
        if step == 2:  # partial shard: 'odd' passes through untouched
            grads.pop("odd")
        host.tick()
        dev.tick()
        params_h = host.apply_shard(
            params_h, {k: g.copy() for k, g in grads.items()})
        params_d = dev.apply_shard(
            params_d, {k: g.copy() for k, g in grads.items()})
        assert _stores_equal(params_h, params_d), (rule, step)


@pytest.mark.parametrize("stripes", [1, 2, 4])
@pytest.mark.parametrize("rule", ["sgd", "momentum", "adam"])
@pytest.mark.parametrize("device_grads", [False, True])
def test_core_close_oracle_across_stripes(rule, stripes, device_grads,
                                          numpy_oracle, each_arena, rng):
    """Full barrier closes through ParameterServerCore: the device
    optimizer's store is byte-identical to the numpy optimizer's at
    every stripe count, with folds arriving as numpy arrays AND as
    device buffers (the decode-on-device residence) — and across
    PSDT_ARENA=0/1 (the flat mega-array layout must reproduce the same
    bytes; ISSUE 15)."""
    jnp = _jnp()
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(3)]

    def run(optimizer, device: bool):
        core = ParameterServerCore(total_workers=2, stripes=stripes,
                                   optimizer=optimizer)
        core.initialize_parameters(params)
        for it, grads in enumerate(grads_by_iter, start=1):
            for wid in range(2):
                payload = ({k: jnp.asarray(g) for k, g in grads.items()}
                           if device else
                           {k: g.copy() for k, g in grads.items()})
                r = core.receive_gradients(wid, it, payload)
            assert r.aggregation_complete, r.message
        store = core.get_parameters()
        return {k: np.asarray(v, np.float32) for k, v in store.items()}

    host_store = run(make_optimizer(rule, 0.02), device=False)
    dev_store = run(ShardedDeviceOptimizer(rule, 0.02),
                    device=device_grads)
    assert _stores_equal(host_store, dev_store)


# -------------------------------------------------------------- dequant
@pytest.mark.parametrize("wire", ["raw", "bf16", "int8", "topk"])
def test_device_unpack_matches_codec_oracle(wire, each_codec, rng):
    """device_unpack == Codec.unpack byte for byte, for every packed
    wire dtype, against both codec backends (the ``native`` leg proves
    byte-compat with psdt_native.cpp::psdt_dequant_int8 and friends)."""
    wire_dtype = codec_mod.WIRE_DTYPE_NAMES[wire]
    flat = rng.standard_normal(1023).astype(np.float32)
    size = flat.size
    k = codec_mod.topk_k(size, m.TOPK_DEFAULT_DENSITY)
    raw = bytearray(codec_mod.payload_nbytes(wire_dtype, size, k))
    codec_mod.active_codec().pack_into(wire_dtype, flat, raw, k=k)
    oracle = codec_mod.PythonCodec().unpack(wire_dtype, bytes(raw), size)
    got = np.asarray(device_apply.device_unpack(wire_dtype, bytes(raw),
                                                size))
    assert got.dtype == np.float32
    assert got.tobytes() == np.asarray(oracle, np.float32).tobytes()


@pytest.mark.parametrize("wire", ["raw", "bf16", "int8", "topk"])
def test_decode_gradients_device_matches_host(wire, rng):
    """rpc/data_plane.decode_gradients(device=True) lands jax buffers
    bit-identical to the host decode, for every packed wire dtype."""
    store = {"a": rng.standard_normal((31, 7)).astype(np.float32),
             "b": rng.standard_normal(257).astype(np.float32)}
    wire_dtype = codec_mod.WIRE_DTYPE_NAMES[wire]
    host = decode_gradients(to_wire(store, wire_dtype), device=False)
    dev = decode_gradients(to_wire(store, wire_dtype), device=True)
    for name in host:
        assert device_apply.is_device_array(dev[name])
        assert (np.asarray(dev[name], np.float32).tobytes()
                == np.asarray(host[name], np.float32).tobytes())
        assert dev[name].shape == host[name].shape


# ----------------------------------------------------------- checkpoint
@pytest.mark.parametrize("save_stripes,restore_stripes", [(1, 4), (2, 1),
                                                          (4, 2)])
def test_checkpoint_roundtrip_across_stripe_counts(save_stripes,
                                                   restore_stripes,
                                                   tmp_path, numpy_oracle,
                                                   rng):
    """Device slot state round-trips through the existing .ckpt layout
    bit-identically, across restore stripe counts AND across optimizer
    families (device state restores into the host adam and vice versa —
    the state_dict layouts are shared by construction)."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(4)]

    def closes(core, iters):
        for it in iters:
            for wid in range(2):
                r = core.receive_gradients(
                    wid, it, {k: g.copy()
                              for k, g in grads_by_iter[it - 1].items()})
            assert r.aggregation_complete

    core_a = ParameterServerCore(total_workers=2, stripes=save_stripes,
                                 optimizer=ShardedDeviceOptimizer(
                                     "adam", 0.02))
    core_a.initialize_parameters(params)
    closes(core_a, [1, 2])
    path = CheckpointManager(core_a, directory=str(tmp_path)).save(epoch=7)

    for opt in (ShardedDeviceOptimizer("adam", 0.02),
                make_optimizer("adam", 0.02)):
        core_b = ParameterServerCore(total_workers=2,
                                     stripes=restore_stripes,
                                     optimizer=opt)
        epoch, iteration = CheckpointManager(
            core_b, directory=str(tmp_path)).load(path)
        assert (epoch, iteration) == (7, 2)
        assert _stores_equal(core_b.get_parameters(),
                             core_a.get_parameters())
        # continue training on both: restored state must evolve
        # identically (slots round-tripped bit-exactly)
        closes(core_b, [3, 4])
        ref = ParameterServerCore(total_workers=2, stripes=save_stripes,
                                  optimizer=ShardedDeviceOptimizer(
                                      "adam", 0.02))
        ref.restore(7, 2, core_a.get_parameters(),
                    optimizer_state=core_a.optimizer_state())
        closes(ref, [3, 4])
        assert _stores_equal(core_b.get_parameters(),
                             ref.get_parameters())


def test_codec_dumps_device_store_bytes(tmp_path, rng):
    """checkpoint/codec.dumps of a device-resident store produces the
    exact bytes of the numpy store it mirrors (the async D2H prefetch is
    an overlap optimization, not a format change)."""
    from parameter_server_distributed_tpu.checkpoint import codec

    jnp = _jnp()
    store = {"w": rng.standard_normal((17, 5)).astype(np.float32),
             "b": rng.standard_normal(63).astype(np.float32)}
    dev_store = {k: jnp.asarray(v) for k, v in store.items()}
    assert (codec.dumps(3, 9, dev_store) == codec.dumps(3, 9, store))


# -------------------------------------------------- selection/downgrade
def test_make_optimizer_device_apply_resolves_sharded(monkeypatch):
    monkeypatch.setenv(device_apply.ENV_DEVICE_APPLY, "1")
    opt = make_optimizer("device_adam", 0.01)
    assert isinstance(opt, ShardedDeviceOptimizer)
    assert opt.rule == "adam"
    assert opt.supports_striping and opt.device_resident
    # flag off: the pre-existing whole-store optax family, unchanged
    monkeypatch.delenv(device_apply.ENV_DEVICE_APPLY)
    opt = make_optimizer("device_adam", 0.01)
    assert not isinstance(opt, ShardedDeviceOptimizer)
    assert not getattr(opt, "supports_striping", False)


def test_make_optimizer_sharded_names(monkeypatch):
    for rule, host_cls in (("sgd", SGD), ("momentum", Momentum),
                           ("adam", Adam), ("adamw", AdamW),
                           ("lion", Lion)):
        opt = make_optimizer(f"sharded_{rule}", 0.01)
        assert isinstance(opt, ShardedDeviceOptimizer), rule
        assert opt.rule == rule


def test_make_optimizer_raises_without_a_device(monkeypatch):
    """No accelerator => an error that names the cause and the host
    optimizer to ask for instead — never a silent host optimizer under
    a device name."""
    monkeypatch.setattr(device_apply, "_available", False)
    for name, rule in (("device_sgd", "sgd"),
                       ("sharded_momentum", "momentum"),
                       ("device_adam", "adam"), ("device_adamw", "adamw"),
                       ("pallas_adam", "adam"), ("sharded_lion", "lion")):
        with pytest.raises(RuntimeError, match=f"use '{rule}'"):
            make_optimizer(name, 0.01)
    monkeypatch.setattr(device_apply, "_available", True)
    # an unknown RULE raises too — a typo must never silently train
    # with a different update rule
    with pytest.raises(ValueError):
        make_optimizer("device_bogus", 0.01)
    with pytest.raises(ValueError):
        make_optimizer("sharded_adamw_bf16", 0.01)  # not a sharded rule


def test_make_optimizer_raises_on_constructor_error(monkeypatch):
    monkeypatch.setattr(device_apply, "_available", True)

    def boom(*a, **kw):
        raise RuntimeError("backend init failed")

    import parameter_server_distributed_tpu.core.optimizer as opt_mod
    monkeypatch.setattr(opt_mod, "_make_accelerator_optimizer", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        make_optimizer("device_adam", 0.01)


def test_available_raises_when_the_backend_does_not_come_up(monkeypatch):
    """PSDT_DEVICE_APPLY on a host whose backend fails is an error at PS
    start, not a silent False that takes the host path."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend")

    monkeypatch.setattr(device_apply, "_available", None)
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        device_apply.available()
    monkeypatch.undo()
    assert device_apply.available(refresh=True)


def test_make_optimizer_pallas_unimplemented_rule_raises(monkeypatch):
    """A pallas_<rule> the pallas family does not implement must RAISE
    on a healthy jax host (the pre-existing behavior), not degrade —
    degrading is only for accelerator UNAVAILABILITY."""
    monkeypatch.setattr(device_apply, "_available", True)
    with pytest.raises(ValueError):
        make_optimizer("pallas_adamw", 0.01)


def test_fold_add_rejects_wrong_shapes(rng):
    """fold_add reproduces np.add(acc, g, out=acc)'s shape contract:
    g may broadcast UP to the accumulator, but anything that would grow
    or change the result shape raises BEFORE the donation — jax's add
    would otherwise silently broadcast both ways."""
    jnp = _jnp()
    acc = device_apply.owned_copy(jnp.ones((2, 3), jnp.float32))
    with pytest.raises(ValueError):
        device_apply.fold_add(acc, jnp.ones((3, 1), jnp.float32))
    acc = device_apply.owned_copy(jnp.ones((3,), jnp.float32))
    with pytest.raises(ValueError):
        device_apply.fold_add(acc, jnp.ones((2, 3), jnp.float32))
    # broadcast-up matches numpy: acc (2,3) += g (3,)
    acc = device_apply.owned_copy(jnp.ones((2, 3), jnp.float32))
    out = device_apply.fold_add(acc, jnp.full((3,), 2.0, jnp.float32))
    ref = np.ones((2, 3), np.float32)
    np.add(ref, np.full((3,), 2.0, np.float32), out=ref)
    assert np.asarray(out).tobytes() == ref.tobytes()


@pytest.mark.parametrize("rule", ["momentum", "adam"])
def test_shape_change_raises_without_bricking_slots(rule, rng):
    """A per-name shape change (config skew / bad reshard) raises with
    the slot tables UNTOUCHED — the batched kernels donate slot buffers,
    so an unvalidated mismatch surfacing mid-chain would leave the
    optimizer holding deleted arrays and brick every later step."""
    opt = ShardedDeviceOptimizer(rule, 0.01)
    params = {"w": rng.standard_normal((4, 5)).astype(np.float32)}
    opt.tick()
    params = opt.apply_shard(
        params, {"w": rng.standard_normal((4, 5)).astype(np.float32)})
    opt.tick()
    with pytest.raises(ValueError):
        opt.apply_shard(
            params, {"w": rng.standard_normal((5,)).astype(np.float32)})
    # slots still alive: the original-shape step retries cleanly
    params = opt.apply_shard(
        params, {"w": rng.standard_normal((4, 5)).astype(np.float32)})
    assert np.asarray(params["w"]).shape == (4, 5)


# ----------------------------------------------------------- fold gate
def test_device_fold_gating(monkeypatch):
    core = ParameterServerCore(total_workers=1,
                               optimizer=ShardedDeviceOptimizer("sgd",
                                                                0.01))
    assert not core.device_fold  # env off => zero behavior change
    monkeypatch.setenv(device_apply.ENV_DEVICE_APPLY, "1")
    monkeypatch.setattr(device_apply, "_available", True)
    assert core.device_fold
    host = ParameterServerCore(total_workers=1,
                               optimizer=make_optimizer("sgd", 0.01))
    assert not host.device_fold  # host optimizer, no relay => host folds
    buffered = ParameterServerCore(total_workers=1,
                                   aggregation="buffered",
                                   optimizer=ShardedDeviceOptimizer(
                                       "sgd", 0.01))
    assert not buffered.device_fold  # buffered escape hatch stays host


def test_stripe_dispatch_policy(monkeypatch):
    small = {f"t{i}": np.zeros(1024, np.float32) for i in range(4)}
    assert device_apply.stripe_dispatch(small)
    big = {"t": np.zeros(8 << 20, np.float32)}  # 32MB mean
    assert not device_apply.stripe_dispatch(big)
    monkeypatch.setenv(device_apply.ENV_STRIPE_DISPATCH_MAX,
                       str(1 << 30))
    assert device_apply.stripe_dispatch(big)
    assert not device_apply.stripe_dispatch({})


def test_device_close_records_obs(numpy_oracle, rng):
    """A device-resident barrier close bumps ps.apply.device and the
    rollup renders the 'device apply' line."""
    from parameter_server_distributed_tpu.obs.export import (
        render_rollup, worker_rollup)

    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    core = ParameterServerCore(total_workers=1,
                               optimizer=ShardedDeviceOptimizer("sgd",
                                                                0.01))
    core.initialize_parameters(params)
    before = obs_stats.REGISTRY.snapshot().get("counters", {}).get(
        "ps.apply.device", 0)
    r = core.receive_gradients(0, 1, {
        k: rng.standard_normal(s).astype(np.float32)
        for k, s in shapes.items()})
    assert r.aggregation_complete
    device_apply.block_on_store(core.get_parameters())
    snap = obs_stats.REGISTRY.snapshot()
    assert snap["counters"]["ps.apply.device"] >= before + 1
    rolled = worker_rollup(snap)
    assert rolled["ps"]["device_apply"]["applies"] >= 1
    text = render_rollup({"cluster": {}, "per_worker": {0: rolled}})
    assert "device apply" in text


# ------------------------------------------------------- leaf relay
def test_leaf_relay_gets_host_sums_from_device_folds(monkeypatch, rng):
    """The PR-9 intra-host tier leftover: a leaf-aggregator core with
    device folds enabled accumulates member pushes as device reductions,
    and its barrier relay receives MATERIALIZED host numpy sums (the EF
    residual math and the native quantize kernels are numpy) that are
    bit-identical to a numpy-folded leaf's."""
    jnp = _jnp()
    monkeypatch.setenv(device_apply.ENV_DEVICE_APPLY, "1")
    monkeypatch.setattr(device_apply, "_available", True)
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads = [{k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()} for _ in range(2)]
    seen: dict = {}

    def relay(iteration, sums, counts):
        seen["types"] = {k: type(v).__name__ for k, v in sums.items()}
        seen["sums"] = {k: np.asarray(v).copy() for k, v in sums.items()}
        seen["counts"] = dict(counts)
        return dict(params)  # "fresh params from upstream"

    core = ParameterServerCore(total_workers=2,
                               optimizer=make_optimizer("sgd", 0.01))
    core.set_barrier_relay(relay)
    assert core.device_fold  # relay + env on => device member folds
    core.initialize_parameters(params)
    for wid in range(2):
        r = core.receive_gradients(
            wid, 1, {k: jnp.asarray(g) for k, g in grads[wid].items()})
    assert r.aggregation_complete
    assert all(t == "ndarray" for t in seen["types"].values()), (
        seen["types"])
    assert all(c == 2 for c in seen["counts"].values())
    for k in shapes:  # device adds == numpy adds, bit for bit
        expect = (np.array(grads[0][k], np.float32)
                  + grads[1][k].astype(np.float32))
        assert seen["sums"][k].tobytes() == expect.tobytes()


def test_relay_raise_puts_back_writeable_host_sums(monkeypatch, rng):
    """A relay raise must put back WRITEABLE host sums: np.asarray of a
    jax CPU array is a read-only view, and a read-only accumulator would
    crash every replayed member fold (np.add out=acc), wedging the
    barrier permanently."""
    jnp = _jnp()
    monkeypatch.setenv(device_apply.ENV_DEVICE_APPLY, "1")
    monkeypatch.setattr(device_apply, "_available", True)
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    calls = {"n": 0}

    def flaky_relay(iteration, sums, counts):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient upstream failure")
        return dict(params)

    core = ParameterServerCore(total_workers=2,
                               optimizer=make_optimizer("sgd", 0.01))
    core.set_barrier_relay(flaky_relay)
    core.initialize_parameters(params)
    for wid in range(2):
        try:
            core.receive_gradients(
                wid, 1, {k: jnp.asarray(g) for k, g in grads.items()})
        except RuntimeError:
            pass
    state = core._iteration_states[1]
    for name, acc in state.accum.items():
        assert isinstance(acc, np.ndarray) and acc.flags.writeable, name
    _, complete, _, _ = core.check_sync_status(1)  # retry closes cleanly
    assert complete and calls["n"] == 2


def test_make_optimizer_raises_when_device_family_unimportable(
        monkeypatch):
    """PSDT_DEVICE_APPLY=1 on a host where the device-optimizer module
    cannot import (no jax/optax) is an error at PS boot that names the
    import failure, not a host optimizer under a device name."""
    import sys

    monkeypatch.setenv(device_apply.ENV_DEVICE_APPLY, "1")
    monkeypatch.setattr(device_apply, "_available", True)
    monkeypatch.setitem(
        sys.modules,
        "parameter_server_distributed_tpu.async_sgd.device_optimizer",
        None)  # import of the module now raises ImportError
    with pytest.raises(RuntimeError, match="ModuleNotFoundError"):
        make_optimizer("device_adam", 0.01)


# ------------------------------------------------------------ put-back
def test_failed_device_apply_leaves_barrier_retryable(numpy_oracle, rng):
    """The put-back contract on the device path: an apply raise puts the
    accumulator back and the next push retries the close successfully
    (sums are never donated into the apply, so the retry reads live
    buffers)."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}

    class Flaky(ShardedDeviceOptimizer):
        fail = True

        def apply_shard(self, p, g):
            if Flaky.fail:
                Flaky.fail = False
                raise RuntimeError("injected apply failure")
            return super().apply_shard(p, g)

    # stripes=1: the injected raise precedes ANY mutation (a striped
    # apply commits per-stripe slot updates before the close raises —
    # the pre-existing partial-failure semantic shared with the host
    # optimizers — which would make retry-vs-clean comparison moot)
    core = ParameterServerCore(total_workers=1, stripes=1,
                               optimizer=Flaky("momentum", 0.02))
    core.initialize_parameters(params)
    grads = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    with pytest.raises(RuntimeError):
        core.receive_gradients(0, 1, {k: g.copy()
                                      for k, g in grads.items()})
    # the sync poll re-fires the close off the put-back accumulator
    # (the duplicate push dedups — first push wins)
    _, complete, _, _ = core.check_sync_status(1)
    assert complete
    # reference without the failure: momentum's tick is a no-op, and
    # the raise fired before any slot mutation, so the retried close
    # must be bit-identical to a clean run
    ref = ParameterServerCore(total_workers=1, stripes=1,
                              optimizer=ShardedDeviceOptimizer(
                                  "momentum", 0.02))
    ref.initialize_parameters(params)
    ref.receive_gradients(0, 1, {k: g.copy() for k, g in grads.items()})
    assert _stores_equal(core.get_parameters(), ref.get_parameters())


# --------------------------------------------------------------- hammer
@pytest.mark.lockcheck
def test_concurrent_push_close_serve_hammer(numpy_oracle, rng):
    """Concurrent pushes (device buffers), barrier closes, checkpoint
    snapshots, and serves against the device path, under the runtime
    lock-order checker; final store must equal the single-threaded
    oracle."""
    jnp = _jnp()
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(5)]
    n_workers = 3
    core = ParameterServerCore(total_workers=n_workers, stripes=2,
                               optimizer=ShardedDeviceOptimizer("adam",
                                                                0.02))
    core.initialize_parameters(params)
    stop = threading.Event()
    errors: list = []

    def server_noise():
        while not stop.is_set():
            try:
                core.serve_parameters()
                core.get_parameters()
                core.optimizer_state()
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return

    noise = threading.Thread(target=server_noise)
    noise.start()
    gate = threading.Barrier(n_workers)

    def worker(wid: int):
        try:
            for it, grads in enumerate(grads_by_iter, start=1):
                gate.wait(timeout=30)
                core.receive_gradients(
                    wid, it, {k: jnp.asarray(g)
                              for k, g in grads.items()})
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    noise.join(timeout=10)
    assert not errors, errors

    ref = ParameterServerCore(total_workers=n_workers,
                              optimizer=ShardedDeviceOptimizer("adam",
                                                               0.02))
    ref.initialize_parameters(params)
    for it, grads in enumerate(grads_by_iter, start=1):
        for wid in range(n_workers):
            ref.receive_gradients(wid, it, {k: g.copy()
                                            for k, g in grads.items()})
    assert _stores_equal(core.get_parameters(), ref.get_parameters())
