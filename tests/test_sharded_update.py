"""Cross-replica sharded arena close (ISSUE 18): the primary and its
in-sync backups split every close's stripe slabs into owned slices,
each replica runs the fused arena stages only over its own slices, and
the fresh slabs all-gather back — raw exchange bit-identical to the
single-node arena close, quantized exchange bounded by error feedback,
any mid-exchange death degrading that close to the local full apply
with zero failed steps (replication/sharded_update.py)."""

import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.config import ParameterServerConfig
from parameter_server_distributed_tpu.core import device_apply
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
    ShardedDeviceOptimizer)
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.replication import sharded_update as su
from parameter_server_distributed_tpu.replication import messages as rmsg
from parameter_server_distributed_tpu.server.ps_service import ParameterServer

SIZE = 33  # deliberately prime-ish: slice boundaries land mid-tensor


def _counters():
    return dict(obs_stats.REGISTRY.snapshot().get("counters", {}))


def _replication_wire_bytes():
    """TRUE wire bytes of the three replication legs so far: what the
    client side sent and received, requests and responses."""
    counters = _counters()
    return sum(counters.get(f"rpc.client.{method}.{leg}", 0)
               for method in ("PushReplicaDelta", "ShardedApplySlices",
                              "InstallSlabSlices")
               for leg in ("request_bytes", "response_bytes"))


def _gauge(name):
    return obs_stats.REGISTRY.snapshot().get("gauges", {}).get(name, 0)


def make_ps(tmp_path, name, total_workers=1, **kw):
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=total_workers,
        checkpoint_dir=str(tmp_path / name), learning_rate=0.1,
        autosave_period_s=600.0, **kw))
    return ps, ps.start()


def rand_store(n=6, size=SIZE, seed=0):
    rng = np.random.default_rng(seed)
    return {f"layer{i}/w": rng.standard_normal(size).astype(np.float32)
            for i in range(n)}


def run_closes(primary, store, iterations, seed=1, worker=0, first=1):
    rng = np.random.default_rng(seed)
    for it in range(first, first + iterations):
        grads = {k: rng.standard_normal(len(v)).astype(np.float32)
                 for k, v in store.items()}
        r = primary.core.receive_gradients(worker, it, grads)
        assert r.aggregation_complete, r.message


def snapshot(ps):
    return {k: np.array(v, np.float32)
            for k, v in ps.core.get_parameters().items()}


@pytest.fixture
def arena_env(monkeypatch):
    """Every server-level sharded test runs the flat-arena close path
    (the sharded update only engages there)."""
    if not device_apply.available():
        pytest.skip("no jax backend/device for the arena close")
    monkeypatch.setenv("PSDT_ARENA", "1")


# ----------------------------------------------------------------- units

def test_slice_ranges_partition_exactly():
    for size in (0, 1, 2, 7, 33, 1024):
        for replicas in (1, 2, 3, 4, 7):
            ranges = su.slice_ranges(size, replicas)
            assert len(ranges) == replicas
            assert ranges[0][0] == 0 and ranges[-1][1] == size
            assert all(ranges[i][1] == ranges[i + 1][0]
                       for i in range(replicas - 1))
            assert su._full_cover(ranges, size)
    # R > size: some replicas own empty ranges, coverage still exact
    assert su.slice_ranges(2, 4) == [(0, 0), (0, 1), (1, 1), (1, 2)]


def test_exchange_dtype_options():
    from parameter_server_distributed_tpu.rpc import messages as m

    assert su.exchange_wire_dtype("raw") == m.WIRE_RAW_F32
    assert su.exchange_wire_dtype("bf16") == m.WIRE_BF16
    assert su.exchange_wire_dtype("int8") == m.WIRE_INT8
    with pytest.raises(ValueError):
        su.exchange_wire_dtype("fp4")


# -------------------------------------------------- raw bit identity

@pytest.mark.parametrize("backups", [1, 3])
def test_sharded_close_bit_identical_to_single_node(tmp_path, arena_env,
                                                    backups):
    """THE acceptance: the raw sharded close at R=2 and R=4 produces
    byte-identical params to the single-node arena close, every backup
    ends byte-identical to the primary, and the closes really ran
    sharded (counter-asserted, no silent full-apply)."""
    store = rand_store()
    base, _ = make_ps(tmp_path, "base", optimizer="sharded_momentum")
    bks = [make_ps(tmp_path, f"bk{i}", optimizer="sharded_momentum")
           for i in range(backups)]
    primary, _ = make_ps(
        tmp_path, "pr", optimizer="sharded_momentum",
        backup_address=",".join(f"127.0.0.1:{port}" for _, port in bks),
        replication="sync", sharded_update="1")
    try:
        assert primary.sharded_updater is not None
        before = _counters()
        base.core.initialize_parameters(rand_store())
        run_closes(base, store, 5)
        primary.core.initialize_parameters(rand_store())
        run_closes(primary, store, 5)
        after = _counters()
        # the FIRST close may run local (the backups learn the init
        # version through its flat ship); every later close shards
        sharded = (after.get("ps.apply.sharded", 0)
                   - before.get("ps.apply.sharded", 0))
        assert sharded >= 4, f"only {sharded} of 5 closes ran sharded"
        assert (after.get("ps.replica.sharded_bytes", 0)
                > before.get("ps.replica.sharded_bytes", 0))
        assert (after.get("ps.replica.sharded_applies", 0)
                - before.get("ps.replica.sharded_applies", 0)
                >= sharded * backups)
        expected = snapshot(base)
        got = snapshot(primary)
        assert set(expected) == set(got)
        for name in expected:
            assert np.array_equal(expected[name], got[name]), name
        # every backup holds the identical raw bits and the iteration
        for bk, _port in bks:
            bp = snapshot(bk)
            for name in expected:
                assert np.array_equal(expected[name], bp[name]), name
            assert bk.core.current_iteration == 5
        # the backups COMPUTED this close: not idle flat-ship replicas
        assert _gauge("ps.replica.idle_accelerator") == 0
    finally:
        primary.stop(0)
        for bk, _port in bks:
            bk.stop(0)
        base.stop(0)


def test_flat_ship_replica_flags_idle_accelerator(tmp_path, arena_env):
    """The satellite gauge: a backup replicating by flat SHIPPING only
    (sharded update off) surfaces its idle accelerator as
    ps.replica.idle_accelerator=1."""
    gauge = obs_stats.gauge("ps.replica.idle_accelerator")
    gauge.set(0)
    backup, bport = make_ps(tmp_path, "idle-bk",
                            optimizer="sharded_momentum")
    primary, _ = make_ps(tmp_path, "idle-pr", optimizer="sharded_momentum",
                         backup_address=f"127.0.0.1:{bport}",
                         replication="sync")
    try:
        assert primary.sharded_updater is None  # not requested
        store = rand_store()
        primary.core.initialize_parameters(store)
        run_closes(primary, store, 2)
        assert gauge.value == 1, "flat-ship replica did not flag idle"
        bp, pp = snapshot(backup), snapshot(primary)
        for name in pp:
            assert np.array_equal(pp[name], bp[name]), name
    finally:
        gauge.set(0)
        primary.stop(0)
        backup.stop(0)


def test_single_replica_declines_to_local_apply(tmp_path, arena_env):
    """sharded_update=1 with NO backup configured: the updater stays
    disarmed and every close runs the ordinary local arena apply."""
    before, wire_before = _counters(), _replication_wire_bytes()
    solo, _ = make_ps(tmp_path, "solo", optimizer="sharded_momentum",
                      sharded_update="1")
    try:
        assert solo.sharded_updater is None
        store = rand_store()
        solo.core.initialize_parameters(store)
        run_closes(solo, store, 3)
        after = _counters()
        assert (after.get("ps.apply.sharded", 0)
                == before.get("ps.apply.sharded", 0))
        assert solo.core.current_iteration == 3
        # one replica: no replication byte moves at all
        assert _replication_wire_bytes() == wire_before
    finally:
        solo.stop(0)


# ------------------------------------------------------- wire bytes

@pytest.mark.parametrize("replicas,raw_share,int8_share",
                         [(2, 0.85, 0.47), (4, 0.60, 0.29)],
                         ids=["2_replicas", "4_replicas"])
def test_sharded_exchange_moves_fewer_bytes_than_the_flat_ship(
        tmp_path, arena_env, replicas, raw_share, int8_share):
    """ISSUE 18's count: after the first close (a flat ship, by which the
    backups learn the base version) EVERY close of a sharded arm runs
    sharded with no fallback, a flat-ship arm runs none, and an
    iteration's replication wire bytes are, against the flat ship's,
    0.83x (raw exchange) and 0.46x (int8) at 2 replicas, 0.58x and 0.27x
    at 4: counts that follow from the shapes.  32 tensors of 3,125 (1e5
    parameters): the exchange's per-slice framing must not eat them."""
    closes = 3
    store = rand_store(n=32, size=3125)
    per_iteration = {}
    for arm, kw in (("flat", {}),
                    ("raw", {"sharded_update": "1"}),
                    ("int8", {"sharded_update": "1",
                              "sharded_update_dtype": "int8"})):
        bks = [make_ps(tmp_path, f"{arm}-bk{i}", optimizer="sharded_adam")
               for i in range(replicas - 1)]
        primary, _ = make_ps(
            tmp_path, f"{arm}-pr", optimizer="sharded_adam",
            backup_address=",".join(f"127.0.0.1:{port}" for _, port in bks),
            replication="sync", **kw)
        try:
            primary.core.initialize_parameters(store)
            run_closes(primary, store, 1)  # the flat ship of the base
            before, wire_before = _counters(), _replication_wire_bytes()
            run_closes(primary, store, closes, seed=2, first=2)
            after = _counters()
            per_iteration[arm] = (
                _replication_wire_bytes() - wire_before) / closes
        finally:
            primary.stop(0)
            for bk, _port in bks:
                bk.stop(0)
        sharded, fallbacks = (
            after.get(name, 0) - before.get(name, 0)
            for name in ("ps.apply.sharded", "ps.apply.sharded_fallback"))
        assert (sharded, fallbacks) == (closes if kw else 0, 0), arm
    flat = per_iteration["flat"]
    assert 0 < per_iteration["int8"] <= int8_share * flat, per_iteration
    assert per_iteration["int8"] < per_iteration["raw"] <= raw_share * flat, \
        per_iteration


# ----------------------------------------------- quantized exchange

@pytest.mark.parametrize("dtype,tol", [("bf16", 0.02), ("int8", 0.05)])
def test_quantized_exchange_bounded_error(tmp_path, arena_env, dtype, tol):
    """EQuARX-style lossy exchange + PR-9 error feedback: the sharded
    close under bf16/int8 sums tracks the exact run within a bounded
    envelope instead of compounding, and the closes really sharded."""
    store = rand_store()
    base, _ = make_ps(tmp_path, f"{dtype}-base", optimizer="sharded_adam")
    backup, bport = make_ps(tmp_path, f"{dtype}-bk",
                            optimizer="sharded_adam")
    primary, _ = make_ps(tmp_path, f"{dtype}-pr", optimizer="sharded_adam",
                         backup_address=f"127.0.0.1:{bport}",
                         replication="sync", sharded_update="1",
                         sharded_update_dtype=dtype)
    try:
        before = _counters()
        base.core.initialize_parameters(rand_store())
        run_closes(base, store, 6)
        primary.core.initialize_parameters(rand_store())
        run_closes(primary, store, 6)
        after = _counters()
        assert (after.get("ps.apply.sharded", 0)
                - before.get("ps.apply.sharded", 0)) >= 5
        expected, got = snapshot(base), snapshot(primary)
        scale = max(float(np.max(np.abs(v))) for v in expected.values())
        for name in expected:
            err = float(np.max(np.abs(expected[name] - got[name])))
            assert err <= tol * max(scale, 1.0), (name, err)
        # the backup's params: own slices exact, foreign slices arrive
        # through the quantized install leg — same bounded envelope
        bp = snapshot(backup)
        for name in expected:
            err = float(np.max(np.abs(got[name] - bp[name])))
            assert err <= tol * max(scale, 1.0), (name, err)
    finally:
        primary.stop(0)
        backup.stop(0)
        base.stop(0)


# ------------------------------------------------------------- chaos

def test_kill_backup_mid_run_zero_failed_steps(tmp_path, arena_env):
    """THE chaos acceptance: hard-kill the backup while closes stream
    through the sharded exchange — every step still succeeds (the
    degraded closes run the local full apply, which is bit-identical),
    the fallback counter surfaces the degrade, and the final params
    match the no-replication run exactly."""
    store = rand_store()
    base, _ = make_ps(tmp_path, "chaos-base", optimizer="sharded_momentum")
    backup, bport = make_ps(tmp_path, "chaos-bk",
                            optimizer="sharded_momentum")
    primary, _ = make_ps(tmp_path, "chaos-pr", optimizer="sharded_momentum",
                         backup_address=f"127.0.0.1:{bport}",
                         replication="sync", sharded_update="1")
    iterations = 8
    errors: list[BaseException] = []
    try:
        base.core.initialize_parameters(rand_store())
        run_closes(base, store, iterations)
        primary.core.initialize_parameters(rand_store())
        before = _counters()

        def pusher():
            try:
                run_closes(primary, store, iterations)
            except BaseException as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        t = threading.Thread(target=pusher, daemon=True,
                             name="sharded-chaos-pusher")
        t.start()
        deadline = time.monotonic() + 60
        while (primary.core.current_iteration < 3
               and time.monotonic() < deadline):
            time.sleep(0.001)
        backup._server.stop(None)  # hard kill, streams die mid-flight
        t.join(timeout=120)
        assert not t.is_alive(), "pusher wedged after the backup died"
        assert not errors, errors
        assert primary.core.current_iteration == iterations
        after = _counters()
        assert (after.get("ps.apply.sharded_fallback", 0)
                > before.get("ps.apply.sharded_fallback", 0)), \
            "the kill never surfaced a sharded fallback"
        # zero drift: the degraded closes applied the same arithmetic
        expected, got = snapshot(base), snapshot(primary)
        for name in expected:
            assert np.array_equal(expected[name], got[name]), name
    finally:
        primary.stop(0)
        backup.stop(0)
        base.stop(0)


def test_sink_refuses_version_skew_and_empty_streams(tmp_path, arena_env):
    """Backup-side refusal paths answer in-band (error chunk / failed
    ack), never raise through the RPC plumbing."""
    backup, _bport = make_ps(tmp_path, "ref-bk",
                             optimizer="sharded_momentum")
    try:
        sink = backup.service.sharded_sink
        out = list(sink.apply_slices(iter([])))
        assert out and out[-1].error and out[-1].last
        ack = sink.install_slices(iter([]))
        assert not ack.success
        # a version the replica does not hold: refused before any apply
        chunk = rmsg.ShardedSliceChunk(plan_epoch=0, epoch=0, iteration=9,
                                       base_version=7, new_version=8,
                                       kind=rmsg.SLICE_SUMS, last=True,
                                       replicas=2, stripes=1)
        out = list(sink.apply_slices(iter([chunk])))
        assert out and out[-1].error
        assert "version" in out[-1].error or "empty" in out[-1].error
        # install with no pending apply: failed ack
        ack = sink.install_slices(iter([rmsg.ShardedSliceChunk(
            plan_epoch=0, epoch=0, iteration=9, base_version=7,
            new_version=8, kind=rmsg.SLICE_PARAMS, last=True,
            replicas=2, stripes=1)]))
        assert not ack.success and "pending" in ack.message
    finally:
        backup.stop(0)


# ---------------------------------------------------------- lockcheck

@pytest.mark.lockcheck
def test_lockcheck_sharded_close_hammer(tmp_path, arena_env):
    """Concurrent pushes through sharded closes + garbage sink streams
    + obs snapshots, all with PSDT_LOCK_CHECK=1: any ordering violation
    in the ShardedUpdater/ShardedUpdateSink/core chains raises
    LockOrderError instead of deadlocking."""
    backup, bport = make_ps(tmp_path, "hammer-bk",
                            optimizer="sharded_momentum")
    primary, _ = make_ps(tmp_path, "hammer-pr", total_workers=4,
                         optimizer="sharded_momentum",
                         backup_address=f"127.0.0.1:{bport}",
                         replication="sync", sharded_update="1")
    errors: list[BaseException] = []
    try:
        assert primary.sharded_updater is not None
        store = rand_store(n=8)
        primary.core.initialize_parameters(store)
        stop = threading.Event()

        def pusher(wid):
            try:
                rng = np.random.default_rng(wid)
                for it in range(1, 9):
                    grads = {k: rng.standard_normal(SIZE).astype(np.float32)
                             for k in store}
                    primary.core.receive_gradients(wid, it, grads)
            except BaseException as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        def churner():
            try:
                sink = backup.service.sharded_sink
                while not stop.is_set():
                    list(sink.apply_slices(iter([])))
                    sink.install_slices(iter([]))
                    obs_stats.REGISTRY.snapshot()
                    time.sleep(0.001)
            except BaseException as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        threads = [threading.Thread(target=pusher, args=(wid,), daemon=True,
                                    name=f"shard-hammer-{wid}")
                   for wid in range(4)]
        churn = threading.Thread(target=churner, daemon=True,
                                 name="shard-hammer-churn")
        for t in threads:
            t.start()
        churn.start()
        for t in threads:
            t.join(timeout=180)
            assert not t.is_alive()
        stop.set()
        churn.join(timeout=10)
        assert not errors, errors
        assert primary.core.current_iteration == 8
        # the sharded path genuinely ran under the hammer
        pp, bp = snapshot(primary), snapshot(backup)
        for name in pp:
            assert np.array_equal(pp[name], bp[name]), name
    finally:
        primary.stop(0)
        backup.stop(0)


# -------------------------------------- sub-chunked stage programs

@pytest.mark.parametrize("rule", ["momentum", "adam", "adamw", "lion"])
def test_stage_chunk_bit_identical(rule, monkeypatch, rng):
    """ISSUE 18 satellite (ISSUE 15 leftover): PSDT_DEVICE_STAGE_CHUNK
    splits every whole-stripe stage program into per-range programs over
    the SAME pure range kernels the sharded exchange uses — params and
    slot slabs stay bit-identical to the unchunked close, and the
    chunked run really took the range path (call-counted)."""
    if not device_apply.available():
        pytest.skip("no jax backend/device for the arena close")
    monkeypatch.setenv("PSDT_ARENA", "1")
    shapes = {f"t{i}": (4, 13) for i in range(6)}
    params = {k: rng.standard_normal(s).astype(np.float32)
              for k, s in shapes.items()}
    grads_by_iter = [{k: rng.standard_normal(s).astype(np.float32)
                      for k, s in shapes.items()} for _ in range(3)]

    def closes(chunk):
        if chunk:
            monkeypatch.setenv(device_apply.ENV_STAGE_CHUNK, str(chunk))
        else:
            monkeypatch.delenv(device_apply.ENV_STAGE_CHUNK,
                               raising=False)
        core = ParameterServerCore(
            total_workers=1, stripes=2,
            optimizer=ShardedDeviceOptimizer(rule, 0.02))
        core.initialize_parameters(params)
        for it, grads in enumerate(grads_by_iter, start=1):
            r = core.receive_gradients(0, it, {k: g.copy()
                                               for k, g in grads.items()})
            assert r.aggregation_complete, r.message
        store = {k: np.array(v, np.float32)
                 for k, v in core.get_parameters().items()}
        slots = core._optimizer.state_dict()
        return store, slots

    calls = {"n": 0}
    real = ShardedDeviceOptimizer.apply_arena_range

    def counting(self, *a, **kw):
        calls["n"] += 1
        return real(self, *a, **kw)

    whole_store, whole_slots = closes(0)
    monkeypatch.setattr(ShardedDeviceOptimizer, "apply_arena_range",
                        counting)
    chunk_store, chunk_slots = closes(17)  # mid-tensor range boundaries
    assert calls["n"] >= 6, "chunked close never took the range path"
    assert set(whole_store) == set(chunk_store)
    for name in whole_store:
        assert np.array_equal(whole_store[name], chunk_store[name]), name

    def flat(d, prefix=""):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out.update(flat(v, f"{prefix}{k}/"))
            elif not np.isscalar(v):
                out[f"{prefix}{k}"] = np.asarray(v, np.float32)
        return out

    ws, cs = flat(whole_slots), flat(chunk_slots)
    assert set(ws) == set(cs)
    for name in ws:
        assert np.array_equal(ws[name], cs[name]), name


# ------------------------------------------------------------- rollup

def test_sharded_metrics_surface_in_rollup():
    from parameter_server_distributed_tpu.obs.export import (render_rollup,
                                                             worker_rollup)

    snap = {"counters": {"ps.apply.sharded": 12,
                         "ps.apply.sharded_fallback": 2,
                         "ps.replica.sharded_bytes": 65536,
                         "ps.replica.sharded_applies": 24},
            "gauges": {"ps.replica.idle_accelerator": 1},
            "histograms": {}, "t": 0.0}
    rolled = worker_rollup(snap)
    replica = rolled["ps"]["replica"]
    assert replica["sharded_closes"] == 12
    assert replica["sharded_fallbacks"] == 2
    assert replica["sharded_bytes"] == 65536
    assert replica["sharded_applies"] == 24
    assert replica["idle_accelerator"] is True
    text = render_rollup({"per_worker": {0: rolled}, "cluster": {}})
    assert "12 sharded closes" in text
    assert "2 sharded fallbacks" in text
    assert "idle accelerator" in text
