"""The free-run apply-on-arrival engine (ISSUE 16 tentpole).

Owned by :class:`~..core.ps_core.ParameterServerCore` when free-run is
armed (see ``freerun/__init__.py`` for the mode's contract and
downgrade matrix).  Three jobs:

**Apply-on-arrival.**  Each push folds its (possibly chunk-streamed)
gradients into a PRIVATE per-sink accumulator — the sink is owned by
exactly one RPC handler thread, so folds run with no core lock held at
all (cross-push parallelism is real: N handler threads fold N pushes on
N cores; the shared-accumulator striping of the barrier path exists to
parallelize *within* one iteration's accumulator, which free-run does
not have).  The commit takes ``_state_lock`` once: version-vector
dedup, staleness damp, one in-place scale of the private sums, and the
same serialized ``_apply_update`` the async path uses.

**Version-vector dedup.**  The barrier modes dedup per (iteration,
worker) inside ``IterationState``; with no iteration states, free-run
keeps ``{worker_id: highest applied worker_step}``.  A push replays
only on RPC retry — the worker replays an IDENTICAL payload for the
same step — so "step already applied" answers success-without-apply and
retries stay idempotent.  The vector is pruned like iteration states
are GC'd: entries more than ``gc_iterations`` behind the newest step
fall off once the vector outgrows its bound (a departed worker's entry
dies; if it ever returns it resumes at a higher step anyway).

**Coalesced publication.**  With barriers gone every apply bumps the
raw store version; serving THAT version per push would thrash the
encode-once serve cache and the delta chain (delta/chain.py — the knob
doc lives there).  The engine instead snapshots the store into a
published ``(store, version)`` at most once per
``PSDT_PUBLISH_MIN_VERSIONS`` applies (0 = auto: the live fleet width)
or ``PSDT_PUBLISH_MAX_LAG_MS``, whichever fires first; ``serve_view``/
``serve_version`` serve the published snapshot, and consecutive +1
published versions keep the delta chain pairing.  The snapshot is a
dict of array refs — safe torn-free because optimizers return FRESH
param arrays each apply (the RCU invariant the async serve path already
relies on).

Locks: NO new locks.  The version vector and staleness EWMA mutate only
under ``core._state_lock``; publication state mutates only under
``core._apply_lock`` (rank 20 -> 30, the declared order); the published
tuple is read lock-free (GIL-atomic ref load).
"""

from __future__ import annotations

import time

import numpy as np

from ..async_sgd.adaptive import AdaptiveDamping
from ..async_sgd.damping import StalenessDamping
from ..core.ps_core import (PushResult, TIER_AGGREGATE_ID_BASE, _fold_one,
                            _store_ready)
from ..core.tensor import TensorStore
from ..delta.chain import publish_max_lag_s, publish_min_versions
from ..obs import flight
from ..obs import stats as obs_stats

# prune trigger for the version vector: far above any sane live fleet,
# so a stable fleet never pays the sweep
_VV_PRUNE_AT = 4096


class FreeRunSink:
    """One free-run push in progress — the :class:`~..core.ps_core.
    PushSink` interface (``worker_id`` / ``iteration`` / ``fold`` /
    ``commit``), so every streaming RPC handler drives it unchanged.
    The accumulator is private to the handler thread; only
    :meth:`commit` touches core state."""

    __slots__ = ("_engine", "worker_id", "iteration", "_accum", "_counts",
                 "_folded", "stale_map_epoch")

    def __init__(self, engine: "FreeRunEngine", worker_id: int,
                 iteration: int):
        self._engine = engine
        self.worker_id = int(worker_id)
        self.iteration = int(iteration)
        self._accum: TensorStore = {}
        self._counts: dict[str, int] = {}
        # per-sink chunk dedup: a transport-level re-send of one chunk
        # within the same stream must not double-fold a tensor
        self._folded: set[str] = set()
        self.stale_map_epoch: int | None = None

    @property
    def folds_at_once(self) -> bool:
        """``PushSink.folds_at_once``: no.  The accumulator is this
        sink's own and lives until its commit; it is handed owned
        arrays, as ever."""
        return False

    def fold(self, gradients) -> None:
        self._engine.fold(self, gradients)

    def commit(self) -> PushResult:
        return self._engine.commit(self)


class FreeRunEngine:
    def __init__(self, core):
        self._core = core
        # the shared damping policy (fixed-beta oracle) + the optional
        # EWMA-normalized adaptive schedule (PSDT_FREERUN_ADAPTIVE)
        self._damping = StalenessDamping()
        from . import adaptive_enabled
        self._adaptive = (AdaptiveDamping(beta=self._damping.beta)
                          if adaptive_enabled() else None)
        # worker_id -> highest applied worker step (under _state_lock)
        self._version_vector: dict[int, int] = {}
        # publication state (under _apply_lock); the published tuple is
        # additionally read lock-free by serve paths
        self._published: tuple[TensorStore, int] | None = None
        self._published_version = 0
        self._applies_since = 0
        self._last_publish = 0.0
        self._min_versions = publish_min_versions()
        self._lag_s = publish_max_lag_s()
        self._obs_applies = obs_stats.counter("ps.freerun.applies")
        self._obs_dups = obs_stats.counter("ps.freerun.duplicates")
        self._obs_floor = obs_stats.counter("ps.freerun.floor_drops")
        self._obs_publishes = obs_stats.counter("ps.freerun.publishes")
        self._obs_staleness = obs_stats.histogram("ps.freerun.staleness")
        self._obs_beta = obs_stats.gauge("ps.freerun.effective_beta")
        self._obs_beta.set(round(self._damping.beta, 4))

    # ------------------------------------------------------------- push
    def begin_push(self, worker_id: int, iteration: int) -> FreeRunSink:
        return FreeRunSink(self, worker_id, iteration)

    def fold(self, sink: FreeRunSink, gradients) -> None:
        """Fold one chunk into the sink's private accumulator.  Only the
        retired-tensor check needs ``_state_lock`` (briefly); the
        O(bytes) adds run with no lock held."""
        if sink.stale_map_epoch is not None:
            return  # push already doomed to the stale-shard-map answer
        core = self._core
        with core._state_lock:
            gradients, stale_epoch = core._split_retired_locked(gradients)
        if stale_epoch is not None:
            sink.stale_map_epoch = stale_epoch
            return
        for name, g in gradients.items():
            if name in sink._folded:
                continue
            # _fold_one raises (mutating nothing) on a shape mismatch —
            # the name stays unmarked, so a replayed chunk retries it
            _fold_one(sink._accum, sink._counts, name, g, 1)
            sink._folded.add(name)

    def _scale_for(self, staleness: int, worker: int,
                   iteration: int) -> tuple[float, bool]:
        """(damp multiplier, effectively-dropped) for one commit.  The
        adaptive schedule observes first — its own staleness is evidence
        of the fleet's operating point — and the floor check runs on
        both paths (StalenessDamping.floored records the flight event)."""
        if self._adaptive is not None:
            self._adaptive.observe(staleness)
            value = self._adaptive.scale(staleness)
            self._obs_beta.set(round(self._adaptive.effective_beta, 4))
            dropped = self._damping.floored(value, worker=worker,
                                            iteration=iteration,
                                            staleness=staleness)
            return value, dropped
        value = self._damping.scale(staleness, worker=worker,
                                    iteration=iteration)
        return value, (self._damping.floor > 0.0
                       and value < self._damping.floor)

    def commit(self, sink: FreeRunSink) -> PushResult:
        core = self._core
        total = core.barrier_width()  # may RPC: outside every lock
        if sink.worker_id >= TIER_AGGREGATE_ID_BASE:
            # same scoping as the other non-streaming-sync modes: a
            # group SUM applied immediately would land at group-size
            # magnitude (see receive_gradients' tier guard)
            return PushResult(
                False,
                "tier aggregate contributions require the streaming "
                "synchronous aggregation path; replay flat",
                sink.iteration, False, 0, total)
        if sink.stale_map_epoch is not None:
            return core._stale_map_result(sink.iteration,
                                          sink.stale_map_epoch, total)
        accum, counts = sink._accum, sink._counts
        with core._state_lock:
            if core._retired:
                # a reshard fence landed after the folds: drop moved
                # names and bounce the push whole — the worker refreshes
                # its map and replays (nothing was applied)
                hit = [n for n in accum if n in core._retired]
                if hit:
                    epoch = max(core._retired[n] for n in hit)
                    return core._stale_map_result(sink.iteration, epoch,
                                                  total)
            with core._params_lock:
                params_empty = not core._params
            if params_empty:
                if not accum:
                    return PushResult(True, "empty push ignored",
                                      core._current_iteration, True, 0,
                                      total)
                # bootstrap: the pushed payload becomes the parameters
                # (the reference quirk every mode preserves)
                core._apply_update(accum)
                core._bootstrap_iteration = sink.iteration
                core._current_iteration = max(core._current_iteration,
                                              sink.iteration)
                self._version_vector[sink.worker_id] = sink.iteration
                self._obs_applies.add()
                flight.record("freerun.apply", iteration=sink.iteration,
                              worker=sink.worker_id, a=0, b=1_000_000)
                self.maybe_publish(applied=True)
                return PushResult(True, "bootstrap applied (free-run)",
                                  core._current_iteration, True, 1, total)
            if (core._bootstrap_iteration is not None
                    and sink.iteration <= core._bootstrap_iteration):
                # a racing duplicate init push: VALUES, not a gradient
                # (the async path's rule) — drop it
                return PushResult(True, "bootstrap duplicate ignored",
                                  core._current_iteration, True, 0, total)
            last = self._version_vector.get(sink.worker_id)
            if last is not None and sink.iteration <= last:
                # version-vector dedup: this worker step already applied
                # — an RPC retry replaying an identical payload — answer
                # success without a second apply
                self._obs_dups.add()
                flight.record("freerun.dup", iteration=sink.iteration,
                              worker=sink.worker_id, a=last)
                return PushResult(
                    True, "duplicate free-run push ignored "
                          "(version vector)",
                    core._current_iteration, True, 0, total)
            if not accum:
                return PushResult(True, "empty push ignored",
                                  core._current_iteration, True, 0, total)
            staleness = max(0, core._current_iteration - sink.iteration)
            value, dropped = self._scale_for(staleness, sink.worker_id,
                                             sink.iteration)
            self._obs_staleness.observe(staleness)
            if dropped:
                # below the PSDT_DAMP_FLOOR: effectively zero — skip the
                # O(model) apply, but the step still COUNTS (vector
                # advances, retries dedup) so the worker free-runs on
                self._obs_floor.add()
                self._version_vector[sink.worker_id] = sink.iteration
                core._current_iteration = max(core._current_iteration,
                                              sink.iteration)
                return PushResult(
                    True, f"update damped below floor "
                          f"(staleness {staleness}); dropped",
                    core._current_iteration, True, 0, total)
            for name, acc in accum.items():
                f = value / counts.get(name, 1)
                if f != 1.0:
                    if not isinstance(acc, np.ndarray):
                        # defensive: device folds are gated off under
                        # free-run, but a duck-typed array-like fold
                        # could land here — materialize a writable copy
                        acc = np.array(np.asarray(acc), np.float32)
                        accum[name] = acc
                    acc *= np.float32(f)
            core._apply_update(accum)
            core._applied_updates += 1
            self._version_vector[sink.worker_id] = sink.iteration
            core._current_iteration = max(core._current_iteration,
                                          sink.iteration)
            self._obs_applies.add()
            flight.record("freerun.apply", iteration=sink.iteration,
                          worker=sink.worker_id, a=staleness,
                          b=int(1e6 * value))
            self._gc_vv_locked()
            self.maybe_publish(applied=True)
            return PushResult(
                True, f"update applied (free-run, staleness {staleness})",
                core._current_iteration, True, 1, total)

    def _gc_vv_locked(self) -> None:
        """Prune version-vector entries of long-departed workers (caller
        holds _state_lock) — the free-run analogue of iteration-state GC."""
        if len(self._version_vector) <= _VV_PRUNE_AT:
            return
        horizon = (self._core._current_iteration
                   - max(64, self._core._gc_iterations))
        for wid in [w for w, step in self._version_vector.items()
                    if step < horizon]:
            del self._version_vector[wid]

    # ------------------------------------------------------------ serve
    def _publish_every(self) -> int:
        """Applies per publication: the knob, or (auto) the static fleet
        width — one publication per fleet-wide round of pushes, the
        barriered modes' natural version cadence.  Reads the cheap
        static width, never the live provider (this runs under locks)."""
        if self._min_versions > 0:
            return self._min_versions
        return max(1, self._core._static_total_workers)

    def maybe_publish(self, applied: bool = False) -> None:
        """Publish the live store as the served snapshot if the
        coalescing window says so.  ``applied=True`` (the commit paths,
        under ``_state_lock`` — rank 20 -> 30, legal) counts one fresh
        apply toward the window first; serve probes call with no lock
        held, so pending applies publish even when the push stream
        pauses."""
        core = self._core
        with core._apply_lock:
            if applied:
                self._applies_since += 1
            pending = self._applies_since
            now = time.monotonic()
            if self._published is not None and (
                    pending < self._publish_every()
                    and (pending <= 0
                         or now - self._last_publish < self._lag_s)):
                return
            with core._params_lock:
                store = core._params
                raw_version = core._params_version
            if not store or not _store_ready(store):
                return
            if self._published is None:
                # seed PAST the raw version: raw versions were served
                # before the first publish (the fallback below), and a
                # served version id must never be reused for different
                # values (the delta receivers' base contract)
                version = max(self._published_version + 1, raw_version)
            else:
                # consecutive +1 keeps the delta chain pairing
                version = self._published_version + 1
            self._published = (dict(store), version)
            self._published_version = version
            self._applies_since = 0
            self._last_publish = now
            self._obs_publishes.add()
            flight.record("freerun.publish", a=version, b=pending)
            sink = core._delta_sink
            if sink is not None:
                # still under _apply_lock (BLOCKING_ALLOWED): the sink
                # reads values no later publish can be mutating, the
                # same discipline as the barrier close's note_apply
                sink.note_apply(self._published[0], version)

    def serve_view(self) -> tuple[int, TensorStore, bool, int]:
        """The free-run serve: the coalesced published snapshot (raw
        live store only until the first publication)."""
        self.maybe_publish()
        core = self._core
        pub = self._published
        if pub is None:
            with core._params_lock:
                return (core._current_iteration, dict(core._params), True,
                        core._params_version)
        store, version = pub
        return core._current_iteration, dict(store), True, version

    def serve_version(self) -> int:
        self.maybe_publish()
        pub = self._published
        if pub is not None:
            return pub[1]
        with self._core._params_lock:
            return self._core._params_version

    # ------------------------------------------------------------ reset
    def reset(self) -> None:
        """Restore / replication install / reshard retire: the store
        changed outside the apply timeline.  Clear the version vector
        (worker step counters restart against the restored world) and
        drop the published snapshot; the version COUNTER is retained so
        the next publication still never reuses a served id."""
        core = self._core
        with core._state_lock:
            self._version_vector.clear()
            with core._apply_lock:
                self._published = None
                self._applies_since = 0
