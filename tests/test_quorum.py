"""K-of-N quorum barriers + bounded-staleness straggler folding
(elastic/, ISSUE 13).

Core-level units of the quorum close (grace window, elastic threshold,
contributor-mean math), the forward stale fold (staleness-1 landing,
per-(worker, tensor) dedup, learning-rate damping against hand-computed
sequences), the shared damping policy (async_sgd/damping.py), a
lockcheck-marked concurrent push/seal/drain hammer, and the gRPC
scenario acceptance: a 4-worker run with one netsim-delayed straggler
under PSDT_QUORUM=0.75 closes every barrier within grace (pst-trace
postmortem: zero stalled iterations) while its loss curve tracks the
fixed-membership f32 run.
"""

import os
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.async_sgd.damping import (
    DEFAULT_BETA, StalenessDamping, async_damping)
from parameter_server_distributed_tpu.core.optimizer import SGD
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.elastic import quorum as equorum
from parameter_server_distributed_tpu.obs import stats as obs_stats


def _core(total=3, quorum=0.5, grace_ms=0.0, **kw):
    core = ParameterServerCore(total_workers=total, optimizer=SGD(1.0),
                               quorum=quorum, quorum_grace_ms=grace_ms,
                               **kw)
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    return core


def _grad(value):
    return {"w": np.full(4, float(value), np.float32)}


def _barrier_counts():
    """(quorum closes, stale folds) recorded so far in this process."""
    counters = obs_stats.REGISTRY.snapshot()["counters"]
    return (counters.get("ps.barrier.quorum_closes", 0),
            counters.get("ps.stale.folds", 0))


# ------------------------------------------------------------------ policy

def test_quorum_threshold_math():
    assert equorum.threshold(0.75, 4) == 3
    assert equorum.threshold(0.5, 4) == 2
    assert equorum.threshold(0.5, 3) == 2  # ceil(1.5)
    assert equorum.threshold(0.75, 1) == 1
    assert equorum.threshold(0.1, 2) == 1
    assert equorum.threshold(0.99, 4) == 4
    assert equorum.threshold(0.5, 0) == 1  # degenerate width


def test_draining_preshrinks_threshold_hand_computed():
    """ISSUE 14 satellite (the PR 13 leftover): DRAINING caps K at
    width - draining, hand-computed before/after the drain."""
    # before any drain: ceil(q * width) as ever
    assert equorum.threshold(0.9, 4) == 4
    assert equorum.threshold(0.75, 8) == 6
    # one drain announced: K = min(ceil(0.9*4)=4, 4-1=3) = 3
    assert equorum.threshold(0.9, 4, draining=1) == 3
    # three drains: K = min(ceil(0.75*8)=6, 8-3=5) = 5
    assert equorum.threshold(0.75, 8, draining=3) == 5
    # the cap only ever SHRINKS K: ceil(0.5*4)=2 < 4-1=3 stays 2
    assert equorum.threshold(0.5, 4, draining=1) == 2
    # floor: a fully-draining barrier still needs one contributor
    assert equorum.threshold(0.9, 2, draining=2) == 1
    assert equorum.threshold(0.9, 4, draining=9) == 1


def test_graceful_drain_costs_zero_grace_windows():
    """With one member DRAINING, the close fires the moment every
    NON-draining member has committed — no grace window, even one set
    to 60 s (the pre-shrink satellite's end-to-end contract)."""
    class Reg:
        live = 4
        drain = ()

        def __call__(self):
            return self.live

        def draining(self):
            return self.drain

    reg = Reg()
    core = ParameterServerCore(total_workers=99, optimizer=SGD(1.0),
                               live_workers_fn=reg,
                               live_workers_ttl_s=0.0,
                               quorum=0.75, quorum_grace_ms=60_000.0)
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    for worker in range(3):
        core.receive_gradients(worker, 1, _grad(1))
    # K = ceil(0.75*4) = 3 reached, but the 60 s grace gates the close
    _, ready, _, _ = core.check_sync_status(1)
    assert not ready
    # worker 3 announces its drain: the same three commits now close
    # IMMEDIATELY (every non-draining member is in), zero grace paid
    reg.drain = (3,)
    _, ready, received, total = core.check_sync_status(1)
    assert ready and received == 3 and total == 4


def test_drain_skip_never_cuts_off_a_healthy_worker():
    """The skip-the-grace close counts only NON-draining commits: a
    DRAINING worker finishing its last in-flight iteration must not
    let the close fire while a healthy worker is the absentee — the
    grace window exists for exactly that worker."""
    class Reg:
        live = 4
        drain = (3,)

        def __call__(self):
            return self.live

        def draining(self):
            return self.drain

    core = ParameterServerCore(total_workers=99, optimizer=SGD(1.0),
                               live_workers_fn=Reg(),
                               live_workers_ttl_s=0.0,
                               quorum=0.75, quorum_grace_ms=60_000.0)
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    # the DRAINING worker (3) commits its last iteration + two healthy
    # peers: received = 3 = K, but only 2 of the 3 NON-draining members
    # are in — the grace must still gate the close
    for worker in (0, 1, 3):
        core.receive_gradients(worker, 1, _grad(1))
    _, ready, _, _ = core.check_sync_status(1)
    assert not ready
    # the last healthy worker lands: full barrier, immediate close
    core.receive_gradients(2, 1, _grad(1))
    _, ready, received, total = core.check_sync_status(1)
    assert ready and received == 4 and total == 4


def test_quorum_fraction_parsing(monkeypatch):
    monkeypatch.delenv(equorum.ENV_QUORUM, raising=False)
    assert equorum.quorum_fraction() == 0.0          # default off
    assert equorum.quorum_fraction(0.75) == 0.75     # config override
    assert equorum.quorum_fraction(1.0) == 0.0       # 1.0 == all-of-N
    monkeypatch.setenv(equorum.ENV_QUORUM, "0.6")
    assert equorum.quorum_fraction() == 0.6
    monkeypatch.setenv(equorum.ENV_QUORUM, "1.5")
    with pytest.raises(ValueError):
        equorum.quorum_fraction()


def test_damping_policy_units(monkeypatch):
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    d = StalenessDamping()
    assert d.beta == DEFAULT_BETA
    assert d.scale(0) == 1.0
    assert d.scale(1) == DEFAULT_BETA
    assert d.scale(3) == pytest.approx(DEFAULT_BETA ** 3)
    src = {"w": np.full(2, 8.0, np.float32)}
    out = d.damp(src, 1)
    np.testing.assert_allclose(out["w"], 4.0)
    np.testing.assert_allclose(src["w"], 8.0)  # never mutates the input
    # async-mode damping arms ONLY on an explicit env beta
    assert async_damping() is None
    monkeypatch.setenv("PSDT_STALENESS_BETA", "0.25")
    armed = async_damping()
    assert armed is not None and armed.scale(2) == pytest.approx(0.0625)
    monkeypatch.setenv("PSDT_STALENESS_BETA", "1.5")
    with pytest.raises(ValueError):
        StalenessDamping()


# ------------------------------------------------------------- quorum close

def test_quorum_off_by_default_is_all_of_n(monkeypatch):
    monkeypatch.delenv(equorum.ENV_QUORUM, raising=False)
    core = ParameterServerCore(total_workers=3, optimizer=SGD(1.0))
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    assert core.quorum == 0.0
    core.receive_gradients(0, 1, _grad(1))
    core.receive_gradients(1, 1, _grad(1))
    time.sleep(0.02)
    _, ready, received, total = core.check_sync_status(1)
    assert not ready and received == 2 and total == 3  # parks forever


def test_quorum_close_waits_for_grace_then_fires():
    core = _core(total=3, quorum=0.5, grace_ms=60.0)
    core.receive_gradients(0, 1, _grad(2))
    r = core.receive_gradients(1, 1, _grad(2))
    # K=2 reached, but the grace window is still running
    assert not r.aggregation_complete
    _, ready, _, _ = core.check_sync_status(1)
    assert not ready
    time.sleep(0.08)
    _, ready, received, total = core.check_sync_status(1)
    assert ready and received == 2 and total == 3
    # contributor mean over the 2 contributors: 4 - mean(2, 2) = 2
    np.testing.assert_allclose(core.get_parameters()["w"], 2.0)


def test_quorum_full_width_still_closes_immediately():
    core = _core(total=2, quorum=0.5, grace_ms=10_000.0)
    core.receive_gradients(0, 1, _grad(1))
    r = core.receive_gradients(1, 1, _grad(3))
    # all of N present: the close never waits out the grace window
    assert r.aggregation_complete and r.workers_received == 2
    np.testing.assert_allclose(core.get_parameters()["w"], 2.0)


def test_quorum_threshold_follows_elastic_width():
    class Reg:
        live = 4

        def __call__(self):
            return self.live

    reg = Reg()
    core = ParameterServerCore(total_workers=99, optimizer=SGD(1.0),
                               live_workers_fn=reg, live_workers_ttl_s=0.0,
                               quorum=0.75, quorum_grace_ms=0.0)
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    core.receive_gradients(0, 1, _grad(1))
    core.receive_gradients(1, 1, _grad(1))
    _, ready, _, _ = core.check_sync_status(1)
    assert not ready  # K = ceil(0.75 * 4) = 3 > 2
    reg.live = 2      # shrink: K = ceil(0.75 * 2) = 2 — already there
    _, ready, received, total = core.check_sync_status(1)
    assert ready and received == 2 and total == 2


def test_quorum_streaming_sync_only():
    # buffered mode keeps the classic all-of-N close even with a quorum
    core = ParameterServerCore(total_workers=3, optimizer=SGD(1.0),
                               aggregation="buffered", quorum=0.5,
                               quorum_grace_ms=0.0)
    core.initialize_parameters({"w": np.full(4, 4.0, np.float32)})
    core.receive_gradients(0, 1, _grad(1))
    core.receive_gradients(1, 1, _grad(1))
    time.sleep(0.01)
    _, ready, _, _ = core.check_sync_status(1)
    assert not ready


# -------------------------------------------------------- straggler folding

def test_straggler_folds_forward_at_staleness_one(monkeypatch):
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    core = _core(total=3, quorum=0.5, grace_ms=0.0)
    core.receive_gradients(0, 1, _grad(2))
    core.receive_gradients(1, 1, _grad(2))
    _, ready, _, _ = core.check_sync_status(1)
    assert ready  # quorum close without worker 2
    np.testing.assert_allclose(core.get_parameters()["w"], 2.0)

    # worker 2's push for the SEALED iteration 1: folded into iteration
    # 2 at staleness 1, lr-damped — not rejected
    r = core.receive_gradients(2, 1, _grad(8))
    assert r.success and r.aggregation_complete
    assert "staleness 1" in r.message and "folded into iteration 2" in r.message

    # workers 0+1 run iteration 2; the straggler's damped carry
    # (0.5 * 8 = 4) is already a contribution there
    core.receive_gradients(0, 2, _grad(1))
    _, ready, received, _ = core.check_sync_status(2)
    # contributors: {2 (stale), 0} = K; grace 0 => closes on this poll
    assert ready and received == 2
    # mean(damped 4, fresh 1) = 2.5; params 2 - 2.5 = -0.5
    np.testing.assert_allclose(core.get_parameters()["w"], -0.5)


def test_stale_fold_dedup_absorbs_the_real_push(monkeypatch):
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    core = _core(total=3, quorum=0.6, grace_ms=0.0)  # K = 2
    core.receive_gradients(0, 1, _grad(2))
    core.receive_gradients(1, 1, _grad(2))
    _, ready, _, _ = core.check_sync_status(1)
    assert ready  # quorum close without worker 2; params 4 - 2 = 2
    r = core.receive_gradients(2, 1, _grad(8))  # stale fold -> iteration 2
    assert "folded into iteration 2" in r.message
    # the straggler's REAL push for iteration 2 dedups per (worker,
    # tensor): first-push-wins, no double count — and iteration 2 is
    # still open (1 of K=2 contributors)
    r2 = core.receive_gradients(2, 2, _grad(100))
    assert r2.success and "duplicate" in r2.message
    core.receive_gradients(0, 2, _grad(2))
    time.sleep(0.01)
    _, ready, _, _ = core.check_sync_status(2)
    assert ready
    # iteration-2 mean = mean(damped 4, fresh 2) = 3; params 2 - 3 = -1
    # (the 100-valued duplicate must be invisible)
    np.testing.assert_allclose(core.get_parameters()["w"], -1.0)


def test_stale_fold_is_idempotent_on_retry(monkeypatch):
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    core = _core(total=3, quorum=0.6, grace_ms=0.0)  # K = 2
    core.receive_gradients(0, 1, _grad(2))
    core.receive_gradients(1, 1, _grad(2))
    core.check_sync_status(1)
    r1 = core.receive_gradients(2, 1, _grad(8))
    r2 = core.receive_gradients(2, 1, _grad(8))  # RPC retry, same payload
    assert "folded into iteration 2" in r1.message
    assert r2.success  # absorbed, not double-folded
    core.receive_gradients(0, 2, _grad(2))
    time.sleep(0.01)
    _, ready, _, _ = core.check_sync_status(2)
    assert ready
    np.testing.assert_allclose(core.get_parameters()["w"], -1.0)


@pytest.mark.parametrize("quorum,recorded", [(0.75, (1, 1)), (0.0, (0, 0))],
                         ids=["k_of_n", "all_of_n"])
def test_barrier_counters_tell_the_arms_apart(monkeypatch, quorum, recorded):
    """What a run's counters say of its barriers: with 4 workers of which
    one pushes after the others, K=3-of-4 records a quorum close at the
    third push and a stale fold for the late one, and all-of-N records
    neither (its close is the fourth push)."""
    monkeypatch.delenv(equorum.ENV_QUORUM, raising=False)
    core = _core(total=4, quorum=quorum, grace_ms=0.0)
    closes_0, folds_0 = _barrier_counts()
    for wid in (0, 1, 2):
        core.receive_gradients(wid, 1, _grad(1))
    _, ready, received, _ = core.check_sync_status(1)
    assert ready == bool(quorum) and received == 3
    r = core.receive_gradients(3, 1, _grad(1))  # the straggler
    assert r.success and r.aggregation_complete
    closes_1, folds_1 = _barrier_counts()
    assert (closes_1 - closes_0, folds_1 - folds_0) == recorded


def test_stale_fold_respects_staleness_bound():
    core = _core(total=2, quorum=0.5, grace_ms=0.0)
    before = _barrier_counts()[1]
    # close iterations 1 AND 2 with worker 0 alone
    for it in (1, 2):
        core.receive_gradients(0, it, _grad(1))
        time.sleep(0.005)
        _, ready, _, _ = core.check_sync_status(it)
        assert ready
    # worker 1's push for iteration 1 is 2 behind the next open
    # iteration (3) — past max(1, staleness_bound): plain late push
    r = core.receive_gradients(1, 1, _grad(8))
    assert r.success and r.aggregation_complete
    assert "already aggregated" in r.message
    assert _barrier_counts()[1] == before


def test_stale_fold_via_chunk_streamed_sink(monkeypatch):
    """The fused data plane path: a PushSink whose chunks land after the
    quorum seal redirects per chunk and commits the stale contribution."""
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    core = _core(total=3, quorum=0.5, grace_ms=0.0)
    core.receive_gradients(0, 1, _grad(2))
    core.receive_gradients(1, 1, _grad(2))
    core.check_sync_status(1)  # quorum close
    sink = core.begin_push(2, 1)
    sink.fold(_grad(8))
    r = sink.commit()
    assert r.success and "staleness 1" in r.message
    core.receive_gradients(0, 2, _grad(1))
    time.sleep(0.01)
    _, ready, _, _ = core.check_sync_status(2)
    assert ready
    np.testing.assert_allclose(core.get_parameters()["w"], -0.5)


def test_async_mode_damping_armed_by_env(monkeypatch):
    monkeypatch.setenv("PSDT_STALENESS_BETA", "0.5")
    core = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                               staleness_bound=2)
    # bootstrap, then advance the PS to iteration 3
    core.receive_gradients(0, 1, {"w": np.full(4, 4.0, np.float32)})
    core.receive_gradients(0, 3, _grad(1))      # fresh: 4 - 1 = 3
    r = core.receive_gradients(1, 2, _grad(2))  # staleness 1: - 0.5*2
    assert r.success
    np.testing.assert_allclose(core.get_parameters()["w"], 2.0)


def test_async_mode_undamped_without_env(monkeypatch):
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    core = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                               staleness_bound=2)
    core.receive_gradients(0, 1, {"w": np.full(4, 4.0, np.float32)})
    core.receive_gradients(0, 3, _grad(1))
    core.receive_gradients(1, 2, _grad(2))  # staleness 1, full strength
    np.testing.assert_allclose(core.get_parameters()["w"], 1.0)


# ----------------------------------------------------------------- scenario

def _run_quorum_cluster(tmp_path, tag, iterations, workers_n=4,
                        quorum=0.0, grace_ms=120.0,
                        straggler_delay_ms=None, flight_dir=None):
    """4-worker gRPC cluster; optionally one worker rides a netsim
    relay (the straggler) and the PS closes at a quorum.  Returns the
    per-worker loss lists."""
    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.config import (
        CoordinatorConfig, ParameterServerConfig, WorkerConfig)
    from parameter_server_distributed_tpu.obs import flight
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)
    from parameter_server_distributed_tpu.utils.netsim import ThrottledRelay

    if flight_dir:
        flight.enable(flight_dir, role=f"cluster-{tag}", records=65536)
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0,
        ps_address="127.0.0.1", ps_port=1, reap_period_s=600.0))
    coord_port = coordinator.start()
    ps = ParameterServer(
        ParameterServerConfig(
            bind_address="127.0.0.1", port=0, total_workers=workers_n,
            checkpoint_interval=10**6, checkpoint_dir=str(tmp_path / tag),
            learning_rate=0.05, elastic=True, live_workers_ttl_s=0.0,
            autosave_period_s=600.0, quorum=quorum,
            quorum_grace_ms=grace_ms),
        live_workers_fn=coordinator.core.width_provider())
    ps_port = ps.start()
    coordinator.core.set_parameter_server_address("127.0.0.1", ps_port)
    relay = None
    workers = []
    try:
        for wid in range(workers_n):
            w = build_worker(WorkerConfig(
                coordinator_address=f"127.0.0.1:{coord_port}",
                worker_id=wid, address="127.0.0.1", port=50400 + wid,
                batch_size=16, heartbeat_period_s=600.0))
            w.initialize()
            workers.append(w)
        if straggler_delay_ms:
            # the LAST worker's PS leg rides a netsim relay: its pushes
            # arrive ~delay late, landing after the quorum seal
            relay = ThrottledRelay(ps_port,
                                   delay_ms=straggler_delay_ms / 2.0)
            relay_port = relay.start()
            straggler = workers[-1]
            straggler._ps.close()
            straggler._ps = PSClient(f"127.0.0.1:{relay_port}")
            straggler._reset_wire_negotiation()
            straggler._next_params = None

        losses: dict[int, list[float]] = {w.config.worker_id: []
                                          for w in workers}
        errors: list = []

        def loop(w):
            try:
                for it in range(iterations):
                    losses[w.config.worker_id].append(w.run_iteration(it))
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append((w.config.worker_id, exc))

        threads = [threading.Thread(target=loop, args=(w,),
                                    name=f"{tag}-w{w.config.worker_id}")
                   for w in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert errors == [], errors
        assert all(len(ls) == iterations for ls in losses.values())
        return losses
    finally:
        for w in workers:
            w.shutdown()
        if relay is not None:
            relay.stop()
        coordinator.stop()
        ps.stop()
        if flight_dir:
            flight.disable()


def test_quorum_netsim_straggler_zero_stalled_iterations(tmp_path,
                                                         monkeypatch):
    """ISSUE 13 acceptance: K=3-of-4 under one netsim-delayed straggler
    closes every barrier within grace — the pst-trace postmortem shows
    ZERO stalled iterations — and the loss curve tracks the
    fixed-membership f32 run (loose allclose: the straggler's damped
    forward folds perturb, they must not derail)."""
    from parameter_server_distributed_tpu.cli.trace_main import (
        main as trace_main)
    from parameter_server_distributed_tpu.obs import postmortem

    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    monkeypatch.delenv("PSDT_QUORUM", raising=False)
    # the straggler's delay is injected at the TCP layer; the same-host
    # shm rings would negotiate past the relay and erase it
    monkeypatch.setenv("PSDT_SHM", "0")
    iterations = 5
    counts_0 = _barrier_counts()
    clean = _run_quorum_cluster(tmp_path, "clean", iterations)
    counts_clean = _barrier_counts()
    flight_dir = str(tmp_path / "flight")
    chaos = _run_quorum_cluster(
        tmp_path, "quorum", iterations, quorum=0.75, grace_ms=120.0,
        straggler_delay_ms=600.0, flight_dir=flight_dir)
    counts_chaos = _barrier_counts()
    # the counters name the arm: all-of-N records no quorum close and no
    # stale fold, K-of-N under a straggler records both
    assert counts_clean == counts_0
    assert counts_chaos[0] > counts_clean[0]
    assert counts_chaos[1] > counts_clean[1]

    events = postmortem.merge_events(postmortem.load_rings(flight_dir))
    # the quorum actually fired (the straggler missed grace at least once)
    seals = [e for e in events if e["event"] == "quorum.seal"]
    assert seals, "no quorum close recorded — straggler never sealed out?"
    folds = [e for e in events if e["event"] == "stale.fold"]
    assert folds and all(e["worker"] == 3 for e in folds)
    # ZERO stalled iterations: no barrier waited on the straggler past
    # grace (generous scheduling slack; a stall would be the 60 s fused
    # barrier timeout)
    assert postmortem.stalled_iterations(events, stall_s=2.0) == []
    assert trace_main([flight_dir, "--stalled=2.0"]) == 0
    # the timeline of a quorum-closed iteration names the worker left
    # outside the close
    quorum_iterations = sorted({e["iteration"] for e in seals})
    tl = postmortem.iteration_timeline(events, quorum_iterations[0])
    assert tl.get("quorum", {}).get("outside") == [3]

    # loss curves: the three healthy workers track the fixed-membership
    # run within a loose band (damped stale folds perturb the
    # trajectory; they must not derail it), and every loss is finite
    for wid in range(3):
        # index 0 is the bootstrap seed (loss NaN by contract)
        c, q = np.asarray(clean[wid])[1:], np.asarray(chaos[wid])[1:]
        assert np.isfinite(c).all() and np.isfinite(q).all()
        np.testing.assert_allclose(q, c, rtol=0.5, atol=0.3,
                                   err_msg=f"worker {wid} loss diverged")


def test_preemption_chaos_drive_zero_stalled_iterations(tmp_path,
                                                        monkeypatch):
    """Preemption chaos under 4 workers with the quorum armed: one
    worker DIES mid-run (no leave announce — the reap evicts it), a
    second is drained via the pst-ctl path mid-run, the remaining two
    finish — and the pst-trace postmortem shows ZERO stalled
    iterations: no barrier ever waited past grace on the gone worker
    (quorum close), and the eviction/drain narrowed the width for the
    rest."""
    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.config import (
        CoordinatorConfig, ParameterServerConfig, WorkerConfig)
    from parameter_server_distributed_tpu.elastic.membership import (
        MembershipClient)
    from parameter_server_distributed_tpu.obs import flight, postmortem
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    iterations = 8
    flight_dir = str(tmp_path / "flight")
    flight.enable(flight_dir, role="chaos", records=65536)
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0,
        ps_address="127.0.0.1", ps_port=1, reap_period_s=600.0))
    coord_port = coordinator.start()
    ps = ParameterServer(
        ParameterServerConfig(
            bind_address="127.0.0.1", port=0, total_workers=4,
            checkpoint_interval=10**6, checkpoint_dir=str(tmp_path / "ck"),
            learning_rate=0.05, elastic=True, live_workers_ttl_s=0.0,
            autosave_period_s=600.0, quorum=0.75, quorum_grace_ms=100.0),
        live_workers_fn=coordinator.core.width_provider())
    ps_port = ps.start()
    coordinator.core.set_parameter_server_address("127.0.0.1", ps_port)
    workers = []
    try:
        for wid in range(4):
            w = build_worker(WorkerConfig(
                coordinator_address=f"127.0.0.1:{coord_port}",
                worker_id=wid, address="127.0.0.1", port=50500 + wid,
                batch_size=16, heartbeat_period_s=600.0))
            w.initialize()
            workers.append(w)

        done: dict[int, int] = {wid: -1 for wid in range(4)}
        dead = threading.Event()
        errors: list = []

        def loop(w, last_it):
            try:
                for it in range(iterations):
                    if w.config.worker_id == 2 and w.drain_requested:
                        break  # the run()-loop drain contract
                    w.run_iteration(it)
                    done[w.config.worker_id] = it
                    if last_it is not None and it >= last_it:
                        dead.set()  # worker 3 "kill -9": just stops
                        return
            except Exception as exc:  # noqa: BLE001 — surfaced below
                errors.append((w.config.worker_id, exc))

        threads = [threading.Thread(
            target=loop, args=(w, 2 if w.config.worker_id == 3 else None),
            name=f"chaos-w{w.config.worker_id}") for w in workers]
        for t in threads:
            t.start()

        # the "killed" worker went silent after iteration 2: age its
        # heartbeat and reap — membership marks it GONE, the generation
        # bump narrows the barrier at the PS's next width read
        assert dead.wait(timeout=120)
        coordinator.core._workers[3].last_heartbeat = -1e9
        evicted = coordinator.core.remove_stale_workers(timeout_s=30.0)
        assert evicted == [3]

        # mid-run ctl drain of worker 2 (DRAINING at the coordinator;
        # the worker's heartbeat-cadence poll latches it — heartbeats
        # are parked in this test, so tick the poll directly)
        while done[2] < 4 and not errors:
            time.sleep(0.02)
        ctl = MembershipClient(f"127.0.0.1:{coord_port}")
        try:
            resp = ctl.drain(2)
            assert resp is not None and resp.success
        finally:
            ctl.close()
        workers[2]._poll_drain()
        assert workers[2].drain_requested
        # its loop stops between iterations; the leave announce at
        # shutdown narrows the width for the survivors
        threads[2].join(timeout=120)
        workers[2].shutdown()

        for t in threads:
            t.join(timeout=120)
        assert errors == [], errors
        assert done[0] == iterations - 1 and done[1] == iterations - 1
        assert done[3] == 2  # died on schedule
    finally:
        for w in workers:
            w.shutdown()
        coordinator.stop()
        ps.stop()
        flight.disable()

    events = postmortem.merge_events(postmortem.load_rings(flight_dir))
    # the acceptance: ZERO stalled iterations — no barrier waited past
    # grace on the gone worker (generous slack over the 100 ms grace;
    # a real stall would be the 60 s fused-barrier timeout)
    assert postmortem.stalled_iterations(events, stall_s=5.0) == []
    evicts = [e for e in events if e["event"] == "elastic.evict"]
    assert [e["worker"] for e in evicts] == [3]
    drains = [e for e in events if e["event"] == "elastic.drain"]
    assert any(e["worker"] == 2 for e in drains)
    # the narrative names the membership churn
    narrative = postmortem.failure_narrative(
        postmortem.load_rings(flight_dir), events)
    assert narrative["membership"]["evictions"] == [{"worker": 3}]


# ------------------------------------------------------------------- hammer

@pytest.mark.lockcheck
def test_quorum_concurrent_push_seal_drain_hammer(monkeypatch):
    """Concurrent pushes, quorum polls, and an elastic width flapping
    under a generation-aware provider — the push/seal/drain interleaving
    hammer, run under PSDT_LOCK_CHECK=1 (conftest lockcheck marker)."""
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)

    class Reg:
        def __init__(self):
            self.live = 4
            self.gen = 0

        def __call__(self):
            return self.live

        def generation(self):
            return self.gen

    reg = Reg()
    core = ParameterServerCore(total_workers=99, optimizer=SGD(0.001),
                               live_workers_fn=reg, live_workers_ttl_s=60.0,
                               quorum=0.75, quorum_grace_ms=0.0, stripes=2)
    core.initialize_parameters(
        {f"w{i}": np.ones(64, np.float32) for i in range(8)})
    iterations = 12
    errors: list = []
    stop = threading.Event()

    def worker_loop(wid: int):
        try:
            rng = np.random.default_rng(wid)
            for it in range(1, iterations + 1):
                grads = {f"w{i}": rng.standard_normal(64).astype(np.float32)
                         for i in range(8)}
                sink = core.begin_push(wid, it)
                for i in range(8):  # chunked
                    sink.fold({f"w{i}": grads[f"w{i}"]})
                sink.commit()
                core.wait_for_aggregation(it, timeout=10.0)
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append((wid, exc))

    def drain_loop():
        while not stop.is_set():
            reg.live = 3
            reg.gen += 1
            time.sleep(0.003)
            reg.live = 4
            reg.gen += 1
            time.sleep(0.003)

    threads = [threading.Thread(target=worker_loop, args=(wid,),
                                name=f"hammer-w{wid}", daemon=True)
               for wid in range(4)]
    drain = threading.Thread(target=drain_loop, name="hammer-drain",
                             daemon=True)
    for t in threads:
        t.start()
    drain.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    drain.join(timeout=5)
    assert errors == []
    assert core.current_iteration == iterations
    # every iteration the workers pushed eventually published a barrier
    ready, _, _ = core.wait_for_aggregation(iterations, timeout=10.0)
    assert ready
