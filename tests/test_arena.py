"""Flat arena apply (ISSUE 15, core/arena.py): the bit-identity oracle
flat == per-tensor == numpy across 5 optimizers x stripe counts x fold
residences, the close dispatch-count bound (one kernel per stage per
stripe regardless of tensor count), packing-table stability/rebuild on
retire (tombstoned names vacate their slab, epoch fence), checkpoint
round-trips across PSDT_ARENA on/off and restore stripe counts, the
downgrade matrix (coverage / non-uniform counts / mixed momentum seeding
/ packing failure), serve-encode + delta-build byte identity, and a
lockcheck-marked concurrent push/close/serve hammer under the flag."""

import os
import threading

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
    ShardedDeviceOptimizer)
from parameter_server_distributed_tpu.checkpoint.manager import (
    CheckpointManager)
from parameter_server_distributed_tpu.core import arena, device_apply
from parameter_server_distributed_tpu.core.optimizer import make_optimizer
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.obs import stats as obs_stats


def _jnp():
    import jax.numpy as jnp

    return jnp


@pytest.fixture(autouse=True)
def _arena_on(monkeypatch):
    """Every test here runs under the flag (the off path is covered by
    the whole pre-existing suite plus the each_arena "0" legs)."""
    if not device_apply.available():
        pytest.skip("no jax backend/device")
    monkeypatch.setenv(arena.ENV_ARENA, "1")
    yield


@pytest.fixture
def numpy_oracle():
    native.set_enabled(False)
    try:
        yield
    finally:
        native.set_enabled(
            os.environ.get("PSDT_NATIVE", "1").lower()
            not in ("0", "false"))


def _shapes():
    # odd sizes + matrices (exercise the adamw/lion decay-mask lanes and
    # uneven stripe partitions)
    return {"emb/w": (129, 33), "l0/w": (64, 65), "l0/b": (65,),
            "head/w": (33, 17), "odd": (513,)}


def _stores_equal(a, b) -> bool:
    if set(a) != set(b):
        return False
    return all(np.asarray(a[k], np.float32).tobytes()
               == np.asarray(b[k], np.float32).tobytes() for k in a)


def _closes(core, grads_by_iter, workers=2, device=False):
    jnp = _jnp() if device else None
    for it, grads in enumerate(grads_by_iter, start=1):
        for wid in range(workers):
            payload = ({k: jnp.asarray(g) for k, g in grads.items()}
                       if device else
                       {k: g.copy() for k, g in grads.items()})
            r = core.receive_gradients(wid, it, payload)
        assert r.aggregation_complete, r.message
    return {k: np.asarray(v, np.float32)
            for k, v in core.get_parameters().items()}


def _arena_counters():
    c = obs_stats.REGISTRY.snapshot().get("counters", {})
    return c.get("ps.apply.arena", 0), c.get("ps.apply.arena_fallback", 0)


# --------------------------------------------------------------- oracle
@pytest.mark.parametrize("stripes", [1, 2, 4])
@pytest.mark.parametrize("rule", ShardedDeviceOptimizer.RULES)
@pytest.mark.parametrize("device_grads", [False, True])
def test_flat_close_bit_identical_to_numpy(rule, stripes, device_grads,
                                           numpy_oracle, rng):
    """The triangle: flat (PSDT_ARENA=1) == per-tensor numpy oracle,
    across all five rules x stripe counts x fold residences — and the
    closes really ran flat (counter-asserted, no silent fallback)."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(3)]

    host_core = ParameterServerCore(total_workers=2, stripes=stripes,
                                    optimizer=make_optimizer(rule, 0.02))
    host_core.initialize_parameters(params)
    host = _closes(host_core, grads_by_iter)

    before, fb_before = _arena_counters()
    core = ParameterServerCore(total_workers=2, stripes=stripes,
                               optimizer=ShardedDeviceOptimizer(rule,
                                                                0.02))
    assert core._arena is not None and core._arena.active
    core.initialize_parameters(params)
    flat = _closes(core, grads_by_iter, device=device_grads)
    after, fb_after = _arena_counters()
    assert _stores_equal(host, flat)
    assert after >= before + 3, "closes did not run flat"
    assert fb_after == fb_before, "unexpected arena fallback"
    # the published store is an ArenaStore of zero-copy slab views
    store = core.get_parameters()
    layout = core._params.layout
    some = next(iter(store))
    e = layout.entries[some]
    assert np.shares_memory(store[some], core._params.slabs[e.stripe])


def test_flat_equals_per_tensor_device(numpy_oracle, rng, monkeypatch):
    """flat == per-tensor DEVICE path bit for bit (the third corner of
    the triangle: PR 11's path is itself oracle-proven)."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(3)]

    def run():
        core = ParameterServerCore(
            total_workers=2, stripes=2,
            optimizer=ShardedDeviceOptimizer("adamw", 0.02))
        core.initialize_parameters(params)
        return _closes(core, grads_by_iter, device=True)

    flat = run()
    monkeypatch.setenv(arena.ENV_ARENA, "0")
    per_tensor = run()
    assert _stores_equal(flat, per_tensor)


# ------------------------------------------------------- dispatch bound
def _close_operands(n_tensors, flat, monkeypatch, rng, rule="adam",
                    stripes=2):
    """(stage kernel invocations, ARRAY operands they were handed) of one
    warm close over ``n_tensors`` small tensors, on the flat layout or on
    the per-tensor device path.  Counted via the kernel-library probe:
    fold lanes on the flat layout (slab_update/assemble) never route
    through k(), and the per-tensor path's scatter lanes are ingress
    (fold) work too and are left out."""
    import jax

    monkeypatch.setenv(arena.ENV_ARENA, "1" if flat else "0")
    shapes = {f"t{i:03d}": (64, 16) for i in range(n_tensors)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    core = ParameterServerCore(total_workers=2, stripes=stripes,
                               optimizer=ShardedDeviceOptimizer(rule,
                                                                0.02))
    core.initialize_parameters(params)
    for it in (1, 2):  # it=1 warms jit + seeds slots
        core.receive_gradients(0, it, {k: g.copy()
                                       for k, g in grads.items()})
        if it == 1:
            core.receive_gradients(1, it, {k: g.copy()
                                           for k, g in grads.items()})
    real_k = device_apply.k
    calls = {"n": 0, "operands": 0}

    def counting_k(name):
        fn = real_k(name)
        if name.startswith("a_scatter"):
            return fn

        def counted(*args, **kw):
            calls["n"] += 1
            calls["operands"] += sum(
                1 for leaf in jax.tree_util.tree_leaves(args)
                if getattr(leaf, "ndim", 0) > 0)
            return fn(*args, **kw)
        return counted

    with monkeypatch.context() as probe:
        probe.setattr(device_apply, "k", counting_k)
        r = core.receive_gradients(1, 2, {k: g.copy()
                                          for k, g in grads.items()})
    assert r.aggregation_complete
    return calls["n"], calls["operands"]


@pytest.mark.parametrize("rule", ShardedDeviceOptimizer.RULES)
def test_close_dispatch_bound(rule, numpy_oracle, rng, monkeypatch):
    """The acceptance bound: a flat close dispatches <= stages x stripes
    kernels REGARDLESS of tensor count (64 tensors here; the per-tensor
    path's operand count scales O(tensors): the next test)."""
    stripes = 2
    stage_calls, _ = _close_operands(64, True, monkeypatch, rng, rule=rule,
                                     stripes=stripes)
    budget = arena.close_dispatch_budget(rule, stripes)
    assert 0 < stage_calls <= budget, (stage_calls, budget)


def test_per_tensor_close_operands_grow_with_the_tensor_count(
        numpy_oracle, rng, monkeypatch):
    """The other half of the dispatch bound: the per-tensor path hands
    its stage kernels every tensor of the stripe (operands O(tensors)),
    the flat path one slab a role whatever the tensor count."""
    budget = arena.close_dispatch_budget("adam", 2)
    per_tensor = {n: _close_operands(n, False, monkeypatch, rng)
                  for n in (16, 64)}
    flat = {n: _close_operands(n, True, monkeypatch, rng) for n in (16, 64)}
    for n in (16, 64):
        assert per_tensor[n][1] >= n, per_tensor
        assert 0 < flat[n][0] <= budget, flat
    assert per_tensor[64][1] >= 4 * per_tensor[16][1] > 0, per_tensor
    assert flat[64] == flat[16] and flat[64][1] < 4 * budget, flat


@pytest.mark.parametrize("tensors,tensor_kb,gated", [(48, 4, False),
                                                     (4, 512, True)],
                         ids=["small", "big"])
def test_mean_tensor_size_decides_the_regime(tensors, tensor_kb, gated,
                                             numpy_oracle, rng,
                                             monkeypatch):
    """The regime gate (PSDT_ARENA_MAX_TENSOR_BYTES, here 64 KiB): many
    small tensors close on the flat layout; a store whose MEAN tensor is
    larger is bandwidth-bound and rides the per-tensor path, once
    recorded as a fallback, byte-identical to the host either way."""
    monkeypatch.setenv(arena.ENV_MAX_TENSOR, "65536")
    shapes = {f"t{i:03d}": (tensor_kb << 8,) for i in range(tensors)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [{k: rng.standard_normal(s).astype(np.float32)
                      for k, s in shapes.items()} for _ in range(2)]
    host_core = ParameterServerCore(total_workers=1, stripes=2,
                                    optimizer=make_optimizer("adam", 0.02))
    host_core.initialize_parameters(params)
    host = _closes(host_core, grads_by_iter, workers=1)
    arena_0, fallback_0 = _arena_counters()
    core = ParameterServerCore(total_workers=1, stripes=2,
                               optimizer=ShardedDeviceOptimizer("adam",
                                                                0.02))
    core.initialize_parameters(params)
    got = _closes(core, grads_by_iter, workers=1)
    arena_1, fallback_1 = _arena_counters()
    assert core._arena.gated == gated
    assert (arena_1 - arena_0, fallback_1 - fallback_0) == \
        ((0, 1) if gated else (2, 0))
    assert _stores_equal(host, got)


# ------------------------------------------------ packing table / epoch
def test_packing_table_stable_and_decay_prefix(rng):
    """Same store => identical offsets (process-stable, sorted
    decayed-first order); the decay mask is a per-stripe prefix; only a
    SHAPE change rebuilds (epoch fence) — value changes never do."""
    shapes = _shapes()
    store = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    t1 = arena.PackingTable(store, 2, epoch=1)
    t2 = arena.PackingTable(dict(reversed(list(store.items()))), 2,
                            epoch=1)
    assert {n: (e.stripe, e.offset, e.length, e.shape)
            for n, e in t1.entries.items()} == \
           {n: (e.stripe, e.offset, e.length, e.shape)
            for n, e in t2.entries.items()}
    for stripe in range(2):
        decayed = [t1.entries[n].decayed for n in t1.stripe_names[stripe]]
        assert decayed == sorted(decayed, reverse=True)  # prefix
    mgr = arena.ArenaManager(2)
    ta = mgr.ensure_table(store)
    changed_values = {k: v * 2 for k, v in store.items()}
    tb = mgr.ensure_table(changed_values)
    assert tb.epoch == ta.epoch  # same signature: no rebuild
    reshaped = dict(store)
    reshaped["odd"] = rng.standard_normal((3, 171)).astype(np.float32)
    tc = mgr.ensure_table(reshaped)
    assert tc.epoch == ta.epoch + 1  # shape change: epoch fence bumped


def test_alignment_pads_and_stays_exact(numpy_oracle, rng, monkeypatch):
    """PSDT_ARENA_ALIGN pads slab offsets; padding is reported by the
    gauge, never scattered into, and the closes stay bit-exact."""
    monkeypatch.setenv(arena.ENV_ALIGN, "32")
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(2)]
    host_core = ParameterServerCore(total_workers=1, stripes=2,
                                    optimizer=make_optimizer("adam",
                                                             0.02))
    host_core.initialize_parameters(params)
    host = _closes(host_core, grads_by_iter, workers=1)
    core = ParameterServerCore(total_workers=1, stripes=2,
                               optimizer=ShardedDeviceOptimizer("adam",
                                                                0.02))
    core.initialize_parameters(params)
    flat = _closes(core, grads_by_iter, workers=1)
    assert _stores_equal(host, flat)
    table = core._params.layout
    assert table.padding_elems > 0
    pad = obs_stats.REGISTRY.snapshot()["gauges"]["ps.apply.arena_pad"]
    assert pad > 0


def test_retire_vacates_slab_and_rebuilds(numpy_oracle, rng):
    """A reshard retire tombstones names: the in-flight iteration falls
    back per-tensor (popped names vacate coverage), the NEXT table epoch
    drops them from the slab, and the store tracks the host oracle
    through the whole sequence bit for bit."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    seq = [{k: rng.standard_normal(s).astype(np.float32)
            for k, s in shapes.items()} for _ in range(2)]
    rest = {k: s for k, s in shapes.items() if k != "odd"}
    seq_after = [{k: rng.standard_normal(s).astype(np.float32)
                  for k, s in rest.items()} for _ in range(2)]

    def run(opt):
        core = ParameterServerCore(total_workers=1, stripes=2,
                                   optimizer=opt)
        core.initialize_parameters(params)
        for it, grads in enumerate(seq, start=1):
            r = core.receive_gradients(0, it, {k: g.copy()
                                               for k, g in grads.items()})
            assert r.aggregation_complete
        core.retire_tensors(["odd"], map_epoch=9)
        for it, grads in enumerate(seq_after, start=3):
            r = core.receive_gradients(0, it, {k: g.copy()
                                               for k, g in grads.items()})
            assert r.aggregation_complete
        return core

    dev = run(ShardedDeviceOptimizer("momentum", 0.05))
    host = run(make_optimizer("momentum", 0.05))
    assert _stores_equal(dev.get_parameters(), host.get_parameters())
    table = dev._params.layout
    assert "odd" not in table.entries  # the tombstoned name vacated


# ----------------------------------------------------------- checkpoint
@pytest.mark.parametrize("save_stripes,restore_stripes", [(2, 1), (1, 4)])
def test_checkpoint_roundtrip_across_arena_flag(save_stripes,
                                                restore_stripes,
                                                tmp_path, numpy_oracle,
                                                rng, monkeypatch):
    """Slot state saved from arena slabs restores bit-identically into a
    PSDT_ARENA=0 core (and a host optimizer), across restore stripe
    counts — the .ckpt layout is the host optimizers', unchanged."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [
        {k: rng.standard_normal(s).astype(np.float32)
         for k, s in shapes.items()} for _ in range(4)]

    core_a = ParameterServerCore(total_workers=1, stripes=save_stripes,
                                 optimizer=ShardedDeviceOptimizer(
                                     "adam", 0.02))
    core_a.initialize_parameters(params)
    _closes(core_a, grads_by_iter[:2], workers=1)
    path = CheckpointManager(core_a, directory=str(tmp_path)).save(epoch=3)

    for flag, opt in (("0", ShardedDeviceOptimizer("adam", 0.02)),
                      ("1", ShardedDeviceOptimizer("adam", 0.02)),
                      ("1", make_optimizer("adam", 0.02))):
        monkeypatch.setenv(arena.ENV_ARENA, flag)
        core_b = ParameterServerCore(total_workers=1,
                                     stripes=restore_stripes,
                                     optimizer=opt)
        CheckpointManager(core_b, directory=str(tmp_path)).load(path)
        assert _stores_equal(core_b.get_parameters(),
                             core_a.get_parameters())
        _closes(core_b, grads_by_iter[2:], workers=1)
        ref = ParameterServerCore(total_workers=1, stripes=save_stripes,
                                  optimizer=make_optimizer("adam", 0.02))
        ref.restore(3, 2, core_a.get_parameters(),
                    optimizer_state=core_a.optimizer_state())
        _closes(ref, grads_by_iter[2:], workers=1)
        assert _stores_equal(core_b.get_parameters(),
                             ref.get_parameters()), (flag, type(opt))


# ------------------------------------------------------ downgrade rows
def test_partial_coverage_falls_back_per_tensor(numpy_oracle, rng):
    """A close whose gradients skip a name (pass-through) cannot run
    flat — it downgrades to the per-tensor path for THAT close (counter
    + flight), stays bit-exact, and the next full close runs flat
    again."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    seq = [{k: rng.standard_normal(s).astype(np.float32)
            for k, s in shapes.items()} for _ in range(3)]
    seq[1].pop("odd")  # iteration 2: partial shard

    def run(opt):
        core = ParameterServerCore(total_workers=1, stripes=2,
                                   optimizer=opt)
        core.initialize_parameters(params)
        return _closes(core, seq, workers=1), core

    before, fb_before = _arena_counters()
    flat, core = run(ShardedDeviceOptimizer("adam", 0.02))
    after, fb_after = _arena_counters()
    host, _ = run(make_optimizer("adam", 0.02))
    assert _stores_equal(host, flat)
    assert fb_after == fb_before + 1     # exactly the partial close
    assert after >= before + 2           # the full closes ran flat


def test_nonuniform_counts_fall_back(numpy_oracle, rng):
    """Disjoint-subset pushes (the sharded topology) give per-name
    counts that are not uniform: the flat scalar scale cannot represent
    them, so the close downgrades — and matches the host oracle."""
    shapes = {"a": (31,), "b": (17,)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    ga = {"a": rng.standard_normal((31,)).astype(np.float32)}
    gb = {"b": rng.standard_normal((17,)).astype(np.float32),
          "a": rng.standard_normal((31,)).astype(np.float32)}

    def run(opt):
        core = ParameterServerCore(total_workers=2, stripes=1,
                                   optimizer=opt)
        core.initialize_parameters(params)
        core.receive_gradients(0, 1, {k: g.copy() for k, g in ga.items()})
        r = core.receive_gradients(1, 1, {k: g.copy()
                                          for k, g in gb.items()})
        assert r.aggregation_complete
        return {k: np.asarray(v, np.float32)
                for k, v in core.get_parameters().items()}

    _, fb_before = _arena_counters()
    flat = run(ShardedDeviceOptimizer("sgd", 0.1))
    _, fb_after = _arena_counters()
    host = run(make_optimizer("sgd", 0.1))
    assert _stores_equal(host, flat)
    assert fb_after == fb_before + 1


def test_momentum_mixed_seed_falls_back(numpy_oracle, rng):
    """A velocity table covering only SOME names (reshard merge) cannot
    flatten (the copy-seed is per name): arena_ready refuses, the close
    runs per-tensor, and the result matches the host oracle.  The
    fallback SELF-HEALS: that close seeds every name's velocity, so the
    next close runs flat again."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    vel = {"velocity": {"odd": rng.standard_normal((513,)).astype(
        np.float32)}}
    grads = [{k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()} for _ in range(2)]

    def run(opt):
        opt.load_state_dict({"velocity": {
            k: v.copy() for k, v in vel["velocity"].items()}})
        core = ParameterServerCore(total_workers=1, stripes=2,
                                   optimizer=opt)
        core.initialize_parameters(params)
        return _closes(core, grads, workers=1)

    closes_before, fb_before = _arena_counters()
    flat = run(ShardedDeviceOptimizer("momentum", 0.05))
    closes_after, fb_after = _arena_counters()
    host = run(make_optimizer("momentum", 0.05))
    assert _stores_equal(host, flat)
    assert fb_after == fb_before + 1   # the mixed close refused flat
    assert closes_after >= closes_before + 1  # ... and then self-healed


def test_broadcast_fold_evicts_slab_sum_exactly(numpy_oracle, rng):
    """Review regression: the same name folding into the slab (exact
    shape, worker A) and then arriving broadcast-shaped (worker B — the
    host fold's legal broadcast-up) must converge in ONE accumulator:
    the slab-resident partial sum is EVICTED into overflow and the
    broadcast add lands on it, so the fallback close's mean covers both
    contributions — bit-identical to the host oracle."""
    shapes = {"w": (4, 31), "b": (17,)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    ga = {k: rng.standard_normal(s).astype(np.float32)
          for k, s in shapes.items()}
    gb = {"w": rng.standard_normal((31,)).astype(np.float32),  # (31,)
          "b": rng.standard_normal((17,)).astype(np.float32)}  # broadcasts

    def run(opt):
        core = ParameterServerCore(total_workers=2, stripes=1,
                                   optimizer=opt)
        core.initialize_parameters(params)
        core.receive_gradients(0, 1, {k: g.copy() for k, g in ga.items()})
        r = core.receive_gradients(1, 1, {k: g.copy()
                                          for k, g in gb.items()})
        assert r.aggregation_complete
        return {k: np.asarray(v, np.float32)
                for k, v in core.get_parameters().items()}

    flat = run(ShardedDeviceOptimizer("sgd", 0.1))
    host = run(make_optimizer("sgd", 0.1))
    assert _stores_equal(host, flat)


def test_momentum_store_growth_respects_copy_seed(numpy_oracle, rng):
    """Review regression: slot slabs packed for an OLD table epoch must
    not short-circuit arena_ready after the store grows — the new
    name's velocity is unseeded, so repacking it as zeros would replace
    the copy-seed with mu*0+g.  The grown close must fall back (then
    self-heal) and stay bit-identical to the host oracle."""
    shapes = {"a/w": (13, 7), "b": (29,)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grown = dict(shapes, new=(11,))

    def run(opt, seed=21):
        gen = np.random.default_rng(seed)
        core = ParameterServerCore(total_workers=1, stripes=1,
                                   optimizer=opt)
        core.initialize_parameters(params)
        g1 = {k: gen.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
        r = core.receive_gradients(0, 1, {k: v.copy()
                                          for k, v in g1.items()})
        assert r.aggregation_complete
        # the store grows a tensor (an install): table epoch bumps
        core.install_tensors(
            {"new": np.zeros((11,), np.float32)}, mark_aggregated=False)
        for it in (2, 3):
            g = {k: gen.standard_normal(s).astype(np.float32)
                 for k, s in grown.items()}
            # the copy-seed witness: a zeros-repacked velocity would
            # turn this element's seed into mu*0 + (-0.0) = +0.0
            g["new"][0] = np.float32(-0.0)
            r = core.receive_gradients(0, it, {k: v.copy()
                                               for k, v in g.items()})
            assert r.aggregation_complete
        return ({k: np.asarray(v, np.float32)
                 for k, v in core.get_parameters().items()},
                core.optimizer_state())

    _, fb_before = _arena_counters()
    flat, flat_opt = run(ShardedDeviceOptimizer("momentum", 0.05))
    _, fb_after = _arena_counters()
    host, host_opt = run(make_optimizer("momentum", 0.05))
    assert _stores_equal(host, flat)
    # slot bytes too: the -0.0 seed lives in the velocity slot
    assert _stores_equal(host_opt["velocity"], flat_opt["velocity"])
    assert fb_after >= fb_before + 1  # the grown close refused flat


def test_packing_failure_latches_off_never_fails(numpy_oracle, rng,
                                                 monkeypatch):
    """A packing EXCEPTION mid-close completes the close on the
    per-tensor path and latches the arena off — training continues,
    bit-exact, no boot/close failure."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads = [{k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()} for _ in range(2)]
    core = ParameterServerCore(total_workers=1, stripes=2,
                               optimizer=ShardedDeviceOptimizer("adam",
                                                                0.02))
    core.initialize_parameters(params)

    def boom(*a, **kw):
        raise RuntimeError("injected packing failure")

    monkeypatch.setattr(core._arena, "ensure_param_slabs", boom)
    flat = _closes(core, grads, workers=1)
    assert not core._arena.active  # latched off
    host_core = ParameterServerCore(total_workers=1, stripes=2,
                                    optimizer=make_optimizer("adam",
                                                             0.02))
    host_core.initialize_parameters(params)
    host = _closes(host_core, grads, workers=1)
    assert _stores_equal(host, flat)


def test_env_gate_off_means_no_manager(monkeypatch):
    monkeypatch.setenv(arena.ENV_ARENA, "0")
    core = ParameterServerCore(total_workers=1,
                               optimizer=ShardedDeviceOptimizer("sgd",
                                                                0.1))
    assert core._arena is None
    # buffered/async cores never arm the arena either
    monkeypatch.setenv(arena.ENV_ARENA, "1")
    buffered = ParameterServerCore(total_workers=1, aggregation="buffered",
                                   optimizer=ShardedDeviceOptimizer(
                                       "sgd", 0.1))
    assert buffered._arena is None
    host = ParameterServerCore(total_workers=1,
                               optimizer=make_optimizer("sgd", 0.1))
    assert host._arena is None  # host optimizers have no flat stages


# ------------------------------------------------- serve + delta bytes
def test_serve_and_delta_bytes_identical(numpy_oracle, rng):
    """Acceptance: serve-cache encode bodies and delta pairs under
    PSDT_ARENA=1 are byte-identical to the per-tensor path's (the slab
    views and the slab diff change WHERE bytes come from, never the
    bytes)."""
    from parameter_server_distributed_tpu.core.tensor import to_wire
    from parameter_server_distributed_tpu.delta.chain import DeltaChain
    from parameter_server_distributed_tpu.rpc.codec import WIRE_BF16
    from parameter_server_distributed_tpu.rpc.data_plane import (
        encode_parameter_record_groups, split_tensors)

    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads = [{k: (1e-4 * rng.standard_normal(s)).astype(np.float32)
              for k, s in shapes.items()} for _ in range(3)]

    def run(opt):
        core = ParameterServerCore(total_workers=1, stripes=2,
                                   optimizer=opt)
        chain = DeltaChain(depth=4, wire_dtype=WIRE_BF16, stripes=2)
        core.set_delta_sink(chain, seed=False)
        core.initialize_parameters(params)
        _closes(core, grads, workers=1)
        _, store, _, _ = core.serve_view()
        bodies = encode_parameter_record_groups(
            [g for g in split_tensors(to_wire(store), 1 << 20)],
            lambda i, size: memoryview(bytearray(size)), stripes=2)
        pairs = [(fv, p.to_version, p.crc, p.changed, p.entries)
                 for fv, p in chain._pairs.items()]
        return bodies, pairs

    flat_bodies, flat_pairs = run(ShardedDeviceOptimizer("adam", 0.02))
    host_bodies, host_pairs = run(make_optimizer("adam", 0.02))
    assert flat_bodies == host_bodies
    assert flat_pairs == host_pairs
    assert len(flat_pairs) >= 2  # slab-diffed pairs actually built


# --------------------------------------------------------------- hammer
@pytest.mark.lockcheck
def test_concurrent_push_close_serve_hammer(numpy_oracle, rng):
    """Concurrent pushes (device buffers), flat closes, checkpoint
    snapshots, and serves under the runtime lock-order checker; the
    final store must equal the single-threaded oracle."""
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
    assert core._arena is not None
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

    noise = threading.Thread(target=server_noise, name="arena-noise",
                             daemon=True)
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

    threads = [threading.Thread(target=worker, args=(w,),
                                name=f"arena-w{w}", daemon=True)
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


def test_failed_apply_leaves_barrier_retryable(numpy_oracle, rng):
    """A raise inside the flat apply puts the (scaled) accumulator back
    and the next poll retries the close — sums are never donated into
    the stages, so the retry reads live slabs; stripes=1 so the raise
    precedes any slot mutation and the retry is bit-exact vs clean."""
    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}

    class Flaky(ShardedDeviceOptimizer):
        fail = True

        def apply_arena(self, table, param_slabs, grad_slabs):
            if Flaky.fail:
                Flaky.fail = False
                raise RuntimeError("injected arena apply failure")
            return super().apply_arena(table, param_slabs, grad_slabs)

    core = ParameterServerCore(total_workers=1, stripes=1,
                               optimizer=Flaky("momentum", 0.02))
    core.initialize_parameters(params)
    grads = {k: rng.standard_normal(s).astype(np.float32)
             for k, s in shapes.items()}
    with pytest.raises(RuntimeError):
        core.receive_gradients(0, 1, {k: g.copy()
                                      for k, g in grads.items()})
    _, complete, _, _ = core.check_sync_status(1)
    assert complete
    ref = ParameterServerCore(total_workers=1, stripes=1,
                              optimizer=ShardedDeviceOptimizer(
                                  "momentum", 0.02))
    ref.initialize_parameters(params)
    ref.receive_gradients(0, 1, {k: g.copy() for k, g in grads.items()})
    assert _stores_equal(core.get_parameters(), ref.get_parameters())


def test_timeline_renders_arena_line():
    """pst-trace iteration timelines carry an 'arena:' line with the
    pack/dispatch/readback phases (and the fallback reason when a close
    downgraded)."""
    from parameter_server_distributed_tpu.obs import postmortem

    base = {"pid": 1, "tid": 1, "worker": -1, "a": 0, "b": 0,
            "note": "", "role": "ps"}
    events = [
        dict(base, ts=1.0, event="barrier.seal", iteration=7, a=2),
        dict(base, ts=1.001, event="apply.arena.pack", iteration=7,
             a=1200, b=2),
        dict(base, ts=1.01, event="apply.start", iteration=7),
        dict(base, ts=1.02, event="apply.end", iteration=7, a=9000),
        dict(base, ts=1.02, event="apply.arena", iteration=7, a=5000,
             b=2000),
        dict(base, ts=1.03, event="barrier.publish", iteration=7, a=2,
             b=2),
    ]
    tl = postmortem.iteration_timeline(events, 7)
    assert tl["arena"]["dispatch_s"] == pytest.approx(5e-3)
    assert tl["arena"]["readback_s"] == pytest.approx(2e-3)
    assert tl["arena"]["pack_s"] == pytest.approx(1.2e-3)
    report = postmortem.render_report({
        "directory": "/tmp/flight", "processes": [],
        "iterations": {"seen": [7], "published": [7]},
        "iteration": 7, "timeline": tl, "narrative": {}})
    assert "arena:" in report and "dispatch" in report

    fb = [dict(base, ts=1.0, event="apply.arena.fallback", iteration=3,
               note="coverage"),
          dict(base, ts=1.01, event="barrier.publish", iteration=3,
               a=1, b=1)]
    tl = postmortem.iteration_timeline(fb, 3)
    assert tl["arena_fallback"] == "coverage"


def test_rollup_renders_arena_line(numpy_oracle, rng):
    from parameter_server_distributed_tpu.obs.export import (
        render_rollup, worker_rollup)

    shapes = _shapes()
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    core = ParameterServerCore(total_workers=1, stripes=2,
                               optimizer=ShardedDeviceOptimizer("sgd",
                                                                0.05))
    core.initialize_parameters(params)
    r = core.receive_gradients(0, 1, {
        k: rng.standard_normal(s).astype(np.float32)
        for k, s in shapes.items()})
    assert r.aggregation_complete
    snap = obs_stats.REGISTRY.snapshot()
    rolled = worker_rollup(snap)
    assert rolled["ps"]["arena"]["applies"] >= 1
    text = render_rollup({"cluster": {}, "per_worker": {0: rolled}})
    assert "flat closes" in text
