"""Free-running barrier-free training (freerun/, ISSUE 16).

Covers the apply-on-arrival engine (version-vector dedup idempotence
under RPC retry replay, staleness damping with hand-computed sequences,
bootstrap, the downgrade matrix), the adaptive EWMA-normalized schedule
(fixed-beta oracle equivalence when the EWMA is flat), the damp floor
(clamp + flight event), coalesced publication (serve-version stability
and the encode-once serve-cache regression), N-worker convergence
against the synchronous baseline, the 50%-churn chaos row with zero
failed steps, and the lockcheck-marked concurrent push/apply/serve
hammer."""

import tempfile
import threading

import numpy as np
import pytest

from parameter_server_distributed_tpu.async_sgd.adaptive import AdaptiveDamping
from parameter_server_distributed_tpu.async_sgd.damping import (
    MAX_STALENESS, StalenessDamping, clamp_staleness)
from parameter_server_distributed_tpu.core.optimizer import SGD
from parameter_server_distributed_tpu.core.ps_core import (
    TIER_AGGREGATE_ID_BASE, ParameterServerCore)
from parameter_server_distributed_tpu.delta.chain import (
    publish_max_lag_s, publish_min_versions)
from parameter_server_distributed_tpu.obs import flight, postmortem
from parameter_server_distributed_tpu.obs import stats as obs_stats


def store(**kw):
    return {k: np.asarray(v, np.float32) for k, v in kw.items()}


def make_core(total_workers=2, lr=1.0, **kw):
    return ParameterServerCore(total_workers=total_workers,
                               optimizer=SGD(lr), freerun=True, **kw)


def counters():
    return obs_stats.REGISTRY.snapshot()["counters"]


# ------------------------------------------------------------------ damping

def test_fixed_damping_hand_computed_sequence():
    """beta^staleness against a hand-computed table, with the defensive
    clamps: negative staleness damps like fresh (1.0), and an
    overflow-sized staleness clamps to MAX_STALENESS instead of raising
    (beta**2^20 underflows cleanly to 0.0)."""
    d = StalenessDamping(beta=0.5)
    assert d.scale(0) == 1.0
    assert d.scale(1) == 0.5
    assert d.scale(3) == pytest.approx(0.125)
    # clamps (satellite: negative/overflow staleness must be defensive)
    assert clamp_staleness(-7) == 0
    assert clamp_staleness(2**40) == MAX_STALENESS
    assert d.scale(-7) == 1.0
    assert d.scale(2**40) == 0.0  # underflow, not OverflowError


def test_adaptive_matches_fixed_oracle_when_ewma_flat():
    """The fixed-beta path is the ORACLE: with the EWMA at <= 1 (a fleet
    whose pushes are at most one step stale) the adaptive schedule is
    beta**s exactly."""
    fixed = StalenessDamping(beta=0.7)
    adaptive = AdaptiveDamping(beta=0.7)  # ewma starts 0.0 (flat)
    for s in (0, 1, 2, 5, 11):
        assert adaptive.scale(s) == pytest.approx(fixed.scale(s))
    # a fleet operating at staleness <= 1 keeps the EWMA <= 1, so the
    # equivalence survives live observations too
    for _ in range(50):
        adaptive.observe(1)
    assert adaptive.ewma <= 1.0
    for s in (0, 2, 7):
        assert adaptive.scale(s) == pytest.approx(fixed.scale(s))


def test_adaptive_ewma_and_normalized_scale_hand_computed():
    """EWMA arithmetic and the normalized exponent against hand-computed
    values: after observing staleness 8 with alpha 0.5 twice from 0,
    ewma = 0.5*8 + 0.5*(0.5*8) = 6; scale(6) = beta^(6/6) = beta and
    scale(12) = beta^2."""
    a = AdaptiveDamping(beta=0.5, alpha=0.5)
    a.observe(8)
    assert a.ewma == pytest.approx(4.0)
    a.observe(8)
    assert a.ewma == pytest.approx(6.0)
    assert a.scale(6) == pytest.approx(0.5)
    assert a.scale(12) == pytest.approx(0.25)
    assert a.effective_beta == pytest.approx(0.5 ** (1 / 6))
    # seeding (pst-trace commit-spread) starts at the fleet's known
    # operating point instead of re-learning it
    seeded = AdaptiveDamping(beta=0.5, seed=4.0)
    assert seeded.scale(4) == pytest.approx(0.5)


def test_adaptive_validation():
    with pytest.raises(ValueError):
        AdaptiveDamping(beta=0.0)
    with pytest.raises(ValueError):
        AdaptiveDamping(beta=0.5, alpha=0.0)
    with pytest.raises(ValueError):
        AdaptiveDamping(beta=0.5, seed=-1.0)


def test_damp_floor_validation_and_flight_event(tmp_path):
    """A scale below PSDT_DAMP_FLOOR is an effectively-dropped
    contribution: floored() says so and records the damp.floor flight
    event (satellite 2)."""
    with pytest.raises(ValueError):
        StalenessDamping(beta=0.5, floor=1.5)
    d = StalenessDamping(beta=0.5, floor=0.1)
    ring_dir = str(tmp_path / "flight")
    flight.enable(ring_dir, role="test:floor", records=64)
    try:
        assert not d.floored(0.5, worker=1, iteration=3, staleness=1)
        assert d.floored(0.01, worker=1, iteration=9, staleness=7)
    finally:
        flight.disable()
    events = [e for ring in postmortem.load_rings(ring_dir)
              for e in ring["events"] if e["event"] == "damp.floor"]
    assert len(events) == 1
    assert events[0]["worker"] == 1
    assert events[0]["iteration"] == 9
    assert events[0]["a"] == 7  # staleness
    assert events[0]["b"] == int(0.01 * 1e9)  # scale in ppb
    # scale() runs the floor check itself on the fixed path
    off = StalenessDamping(beta=0.5)  # floor off by default
    assert not off.floored(0.0)


# ------------------------------------------------------------ engine: dedup

def test_version_vector_dedup_is_idempotent_under_retry_replay():
    """An RPC retry replays an IDENTICAL payload for the same
    (worker, worker_step): exactly one apply must land, and the retry
    must answer success (the worker's contribution DID land)."""
    core = make_core(total_workers=2)
    core.initialize_parameters(store(w=[10.0, 10.0]))
    before_dups = counters().get("ps.freerun.duplicates", 0)

    r1 = core.receive_gradients(0, 1, store(w=[1.0, 1.0]))
    assert r1.success and r1.aggregation_complete
    np.testing.assert_allclose(core.get_parameters()["w"], [9.0, 9.0])

    # the retry replay: same worker, same step, same payload
    r2 = core.receive_gradients(0, 1, store(w=[1.0, 1.0]))
    assert r2.success  # success-without-apply: the worker moves on
    assert "duplicate" in r2.message
    np.testing.assert_allclose(core.get_parameters()["w"], [9.0, 9.0])
    # an OLDER step replayed late dedups too (vector keeps the highest)
    core.receive_gradients(0, 5, store(w=[1.0, 1.0]))
    r3 = core.receive_gradients(0, 3, store(w=[1.0, 1.0]))
    assert r3.success and "duplicate" in r3.message
    assert counters().get("ps.freerun.duplicates", 0) - before_dups == 2
    # a DIFFERENT worker at the same step is a fresh contribution
    r4 = core.receive_gradients(1, 1, store(w=[1.0, 1.0]))
    assert r4.success and "applied" in r4.message


def test_freerun_bootstrap_and_stale_damping():
    """First push bootstraps (payload becomes the parameters — the
    reference quirk every mode preserves); a late worker's push applies
    damped by beta^staleness instead of being rejected."""
    import os
    os.environ.pop("PSDT_STALENESS_BETA", None)
    core = make_core(total_workers=2)
    boot = core.receive_gradients(0, 0, store(w=[4.0]))
    assert boot.success and "bootstrap" in boot.message
    np.testing.assert_allclose(core.get_parameters()["w"], [4.0])
    # bootstrap-duplicate (another worker racing the same init): dropped
    dup = core.receive_gradients(1, 0, store(w=[4.0]))
    assert dup.success and "bootstrap duplicate" in dup.message

    for it in range(1, 4):
        core.receive_gradients(0, it, store(w=[1.0]))
    np.testing.assert_allclose(core.get_parameters()["w"], [1.0])
    # worker 1 pushes step 1 while the clock sits at 3: staleness 2
    beta = core._freerun._damping.beta
    r = core.receive_gradients(1, 1, store(w=[1.0]))
    assert r.success and "staleness 2" in r.message
    np.testing.assert_allclose(core.get_parameters()["w"],
                               [1.0 - beta ** 2], rtol=1e-6)


def test_freerun_rejects_tier_aggregates_retryably():
    core = make_core()
    core.initialize_parameters(store(w=[1.0]))
    r = core.receive_gradients(TIER_AGGREGATE_ID_BASE + 3, 1,
                               store(w=[1.0]))
    assert not r.success and "replay flat" in r.message


def test_freerun_no_barrier_state():
    """check_sync_status answers ready immediately and creates no
    per-iteration state; wait_for_aggregation never blocks."""
    core = make_core()
    core.initialize_parameters(store(w=[1.0]))
    for it in (0, 1, 99):
        _, ready, received, _ = core.check_sync_status(it)
        assert ready and received == 1
    assert core.wait_for_aggregation(7, 0.01)[0]
    assert not core._iteration_states  # nothing materialized


# -------------------------------------------------------- downgrade matrix

def test_downgrade_matrix():
    """Buffered aggregation and bounded-staleness async win over a
    freerun request (warn + disable); a quorum is force-disabled UNDER
    freerun (no barrier to close)."""
    buffered = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                                   freerun=True, aggregation="buffered")
    assert buffered._freerun is None
    bounded = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                                  freerun=True, staleness_bound=4)
    assert bounded._freerun is None
    quorumed = ParameterServerCore(total_workers=4, optimizer=SGD(1.0),
                                   freerun=True, quorum=0.75)
    assert quorumed._freerun is not None
    assert quorumed._quorum == 0.0
    # default-off: no env, no flag -> no engine, byte-identical paths
    plain = ParameterServerCore(total_workers=2, optimizer=SGD(1.0))
    assert plain._freerun is None


# --------------------------------------------------- coalesced publication

def test_publication_coalescing_serve_version_stable(monkeypatch):
    """With PSDT_PUBLISH_MIN_VERSIONS=4 the served version advances at
    most once per 4 applies even though the raw store version bumps per
    push (satellite 1)."""
    monkeypatch.setenv("PSDT_PUBLISH_MIN_VERSIONS", "4")
    monkeypatch.setenv("PSDT_PUBLISH_MAX_LAG_MS", "60000")
    core = make_core(total_workers=2)
    core.initialize_parameters(store(w=np.zeros(8)))
    core.receive_gradients(0, 1, store(w=np.ones(8)))
    v0 = core.serve_version()
    versions = {v0}
    for it in range(2, 5):  # applies 2..4 within the window
        core.receive_gradients(0, it, store(w=np.ones(8)))
        versions.add(core.serve_version())
    assert len(versions) <= 2  # at most one publication boundary crossed
    for it in range(5, 9):
        core.receive_gradients(0, it, store(w=np.ones(8)))
    v_late = core.serve_version()
    assert v_late > v0  # the window DID roll over eventually
    # served values are the published snapshot, not the live store
    _, served, ready, version = core.serve_view()
    assert ready and version == v_late


def test_publication_knob_validation(monkeypatch):
    assert publish_min_versions(3) == 3
    with pytest.raises(ValueError):
        publish_min_versions(-1)
    monkeypatch.setenv("PSDT_PUBLISH_MIN_VERSIONS", "7")
    assert publish_min_versions() == 7
    assert publish_max_lag_s(250.0) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        publish_max_lag_s(-5.0)
    monkeypatch.setenv("PSDT_PUBLISH_MAX_LAG_MS", "40")
    assert publish_max_lag_s() == pytest.approx(0.04)


def _make_service(core):
    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServerService)

    return ParameterServerService(core, CheckpointManager(
        core, directory=tempfile.mkdtemp(prefix="psdt-freerun-"),
        checkpoint_interval=10**9, check_period_s=3600.0))


def test_serve_cache_hit_rate_stays_high_under_freerun(monkeypatch):
    """The encode-once serve cache regression (satellite 1): per-push
    version advance must NOT thrash the cache — serves between
    publications replay the cached encode.  8 applies at coalescing 4 =
    at most a handful of encodes for 24 serves."""
    monkeypatch.setenv("PSDT_PUBLISH_MIN_VERSIONS", "4")
    monkeypatch.setenv("PSDT_PUBLISH_MAX_LAG_MS", "60000")
    core = make_core(total_workers=2)
    core.initialize_parameters(store(w=np.zeros(64)))
    service = _make_service(core)

    def serve_once():
        for chunk in service._parameter_chunks(0, 0):
            chunk.encode()

    snap0 = counters()
    for it in range(1, 9):
        core.receive_gradients(0, it, store(w=np.ones(64)))
        for _ in range(3):
            serve_once()
    snap1 = counters()
    hits = snap1.get("ps.serve.cache_hit", 0) - snap0.get(
        "ps.serve.cache_hit", 0)
    misses = snap1.get("ps.serve.cache_miss", 0) - snap0.get(
        "ps.serve.cache_miss", 0)
    assert hits + misses == 24
    # without coalescing every apply would invalidate: ~8 misses.  With
    # a 4-apply window at most 3 publications land inside the run.
    assert misses <= 4, (hits, misses)
    assert hits >= 20, (hits, misses)


def test_delta_chain_pairing_survives_coalesced_publication(monkeypatch):
    """Consecutive +1 published versions keep the delta chain building
    pairs, so SubscribeWeights keyed off continuous versions still
    serves O(changed bytes) hops under free-run."""
    monkeypatch.setenv("PSDT_PUBLISH_MIN_VERSIONS", "2")
    monkeypatch.setenv("PSDT_PUBLISH_MAX_LAG_MS", "60000")
    from parameter_server_distributed_tpu.delta.chain import DeltaChain
    core = make_core(total_workers=2)
    core.initialize_parameters(store(w=np.zeros(32)))
    chain = DeltaChain()
    core.set_delta_sink(chain, seed=False)
    for it in range(1, 9):
        core.receive_gradients(0, it, store(w=np.ones(32)))
    head = chain.version
    assert head == core.serve_version()
    # at least one consecutive publication pair chained
    assert chain.pairs_between(head - 1, head)


# ------------------------------------------------------------- convergence

def _run_fleet(core, n_workers, steps, lr_noise=0.0):
    """Each worker pulls the served view, pushes grad = view (the shared
    quadratic loss 0.5*||w||^2), at its own pace."""
    errors = []

    def loop(wid):
        try:
            for it in range(1, steps + 1):
                _, view, _, _ = core.serve_view()
                r = core.receive_gradients(wid, it,
                                           {"w": view["w"].copy()})
                assert r.success, r.message
        except Exception as exc:  # noqa: BLE001
            errors.append((wid, repr(exc)))

    threads = [threading.Thread(target=loop, args=(w,)) for w in
               range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert not [t for t in threads if t.is_alive()]


def _run_barriered(core, n_workers, steps):
    """The same quadratic under an all-of-N barrier: every worker pushes
    the gradient at the iteration's parameters, the last push closes."""
    for it in range(1, steps + 1):
        w = core.get_parameters()["w"].copy()
        for wid in range(n_workers):
            core.receive_gradients(wid, it, {"w": w.copy()})


@pytest.mark.parametrize("freerun,run", [(True, _run_fleet),
                                         (False, _run_barriered)],
                         ids=["freerun", "barriered"])
def test_only_a_freerun_core_counts_applies_and_publications(freerun, run):
    """What a run's counters say of its mode: a free-run fleet records an
    apply for every push and publications of what it applied, a barriered
    run of the same job records neither, and both bring the quadratic's
    loss under a quarter of where it began."""
    n, steps, lr = 4, 8, 0.2
    init = store(w=np.full(16, 8.0))
    core = ParameterServerCore(total_workers=n, optimizer=SGD(lr),
                               freerun=freerun)
    core.initialize_parameters({k: v.copy() for k, v in init.items()})
    before = counters()
    run(core, n, steps)
    after = counters()
    applies, publishes = (
        after.get(name, 0) - before.get(name, 0)
        for name in ("ps.freerun.applies", "ps.freerun.publishes"))
    assert applies == (n * steps if freerun else 0)
    assert (publishes > 0) == freerun and publishes <= applies
    loss, loss_0 = (0.5 * float(np.square(w).sum())
                    for w in (core.get_parameters()["w"], init["w"]))
    assert loss <= 0.25 * loss_0


def test_n_worker_freerun_converges_within_tolerance_of_sync():
    """Acceptance: the async free-run fleet lands the quadratic optimum
    to within tolerance of the synchronous all-of-N baseline."""
    n, steps, lr = 4, 12, 0.2
    init = store(w=np.full(16, 8.0))

    sync = ParameterServerCore(total_workers=n, optimizer=SGD(lr))
    sync.initialize_parameters({k: v.copy() for k, v in init.items()})
    _run_barriered(sync, n, steps)
    sync_final = sync.get_parameters()["w"]
    # geometric decay toward 0: the baseline itself converged
    assert float(np.abs(sync_final).max()) < 1.0

    free = make_core(total_workers=n, lr=lr)
    free.initialize_parameters({k: v.copy() for k, v in init.items()})
    _run_fleet(free, n, steps)
    free_final = free.get_parameters()["w"]
    # same optimum, comparable distance: within tolerance of baseline
    assert float(np.abs(free_final).max()) <= \
        max(0.5, 2.0 * float(np.abs(sync_final).max()))


def test_churn_chaos_zero_failed_steps():
    """Acceptance: 50% churn — half the fleet joins late and leaves
    early (its last push still in flight applies damped) — with ZERO
    failed steps and no barrier for anyone to wedge on."""
    n, steps = 8, 10
    core = make_core(total_workers=n, lr=0.1,
                     gc_iterations=4)  # aggressive GC: nothing to leak
    core.initialize_parameters(store(w=np.full(8, 4.0)))
    results = []
    errors = []
    start_late = threading.Event()

    def loop(wid):
        try:
            if wid % 2:  # the churn half joins late...
                start_late.wait(timeout=30)
            span = steps // 2 if wid % 2 else steps  # ...and leaves early
            for it in range(1, span + 1):
                _, view, _, _ = core.serve_view()
                r = core.receive_gradients(wid, it,
                                           {"w": view["w"].copy()})
                results.append((wid, it, r.success, r.message))
        except Exception as exc:  # noqa: BLE001
            errors.append((wid, repr(exc)))

    threads = [threading.Thread(target=loop, args=(w,)) for w in range(n)]
    for t in threads:
        t.start()
    start_late.set()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert not [t for t in threads if t.is_alive()]
    failed = [r for r in results if not r[2]]
    assert not failed, failed
    assert len(results) == (n // 2) * steps + (n // 2) * (steps // 2)
    # the run made progress toward the optimum despite the churn
    assert float(np.abs(core.get_parameters()["w"]).max()) < 4.0


# ------------------------------------------------------- concurrency/locks

@pytest.mark.lockcheck
def test_concurrent_push_apply_serve_hammer():
    """Pushers, servers, and sync pollers hammer one freerun core under
    PSDT_LOCK_CHECK=1 (conftest arms order-asserting lock proxies): no
    deadlock, no lock-order violation, every push lands or dedups."""
    core = make_core(total_workers=4, lr=0.01)
    core.initialize_parameters(store(w=np.ones(32)))
    stop = threading.Event()
    errors = []

    def pusher(wid):
        try:
            for it in range(1, 40):
                r = core.receive_gradients(wid, it,
                                           store(w=np.full(32, 0.1)))
                assert r.success, r.message
        except Exception as exc:  # noqa: BLE001
            errors.append(("push", wid, repr(exc)))

    def server():
        try:
            while not stop.is_set():
                _, view, ready, version = core.serve_view()
                assert ready and version >= 0
                assert view["w"].shape == (32,)
                core.serve_version()
                core.check_sync_status(1)
        except Exception as exc:  # noqa: BLE001
            errors.append(("serve", repr(exc)))

    pushers = [threading.Thread(target=pusher, args=(w,)) for w in range(4)]
    servers = [threading.Thread(target=server) for _ in range(2)]
    for t in servers + pushers:
        t.start()
    for t in pushers:
        t.join(timeout=60)
    stop.set()
    for t in servers:
        t.join(timeout=10)
    assert not errors, errors
    assert not [t for t in pushers + servers if t.is_alive()]
    applies = counters().get("ps.freerun.applies", 0)
    assert applies > 0


# ----------------------------------------------------------- reset/restore

def test_restore_clears_version_vector_but_not_version_counter():
    """A checkpoint restore rewinds the store: worker step counters
    restart against the restored world (the version vector clears), but
    the published version counter never reuses a served id."""
    core = make_core(total_workers=2)
    core.initialize_parameters(store(w=np.zeros(4)))
    for it in range(1, 6):
        core.receive_gradients(0, it, store(w=np.ones(4)))
    v_before = core.serve_version()
    core.initialize_parameters(store(w=np.zeros(4)))
    core._reset_delta()  # the restore/install/retire hook
    assert core._freerun._published is None
    assert not core._freerun._version_vector
    # step 1 applies again (not deduped against the pre-restore world)
    r = core.receive_gradients(0, 1, store(w=np.ones(4)))
    assert r.success and "applied" in r.message
    assert core.serve_version() >= v_before
