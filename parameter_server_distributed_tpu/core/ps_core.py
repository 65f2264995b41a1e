"""Parameter-server aggregation state machine.

TPU-native re-design of the reference's `ParameterServerCore`
(reference: src/parameter_server.cpp, include/parameter_server.h:23-52).
Pure host-side logic — no I/O, no RPC — so it is unit-testable the way the
reference never was.  Observable semantics preserved from the reference:

- synchronous barrier: a gradient push is buffered per (iteration, worker);
  when the number of distinct contributors reaches the barrier width the
  per-element **mean over actual contributors** is taken and applied
  (reference: src/parameter_server.cpp:18-75).
- late pushes to an already-aggregated iteration succeed without
  contributing (reference: src/parameter_server.cpp:28-30).
- bootstrap: if the server holds no parameters, the first aggregated mean
  gradient *becomes* the parameters (reference: src/parameter_server.cpp:78-81).
- `serve_parameters` ignores the requested iteration and returns the latest
  full parameter copy (reference: src/parameter_server.cpp:93-97).
- `current_iteration` is the monotone max of iterations seen
  (reference: src/parameter_server.cpp:22-24).

Deliberate departures (bug fixes / extensions, flagged in SURVEY.md §7):

- iteration states are garbage-collected (the reference grows
  `iteration_states_` without bound).
- the barrier width may be **elastic**: a live-worker provider (usually the
  coordinator registry) can shrink/grow the barrier without restarting the
  process (the reference restarts the PS on scale events —
  scripts/scale_workers.sh:137-144 — losing in-memory state).
- optional bounded-staleness asynchronous mode (staleness_bound > 0):
  updates apply on arrival, gated on `current_iteration - iteration <= bound`;
  the synchronous protocol is the special case bound == 0.
- pluggable optimizer (the reference hardcodes lr=1.0 SGD).

Aggregation data path (PSDT_AGGREGATION, default ``streaming``):

- **streaming** — every push folds its gradients into a per-iteration
  running float32 accumulator on arrival (per *chunk* when the push is
  stream-chunked — see :meth:`ParameterServerCore.begin_push`), so barrier
  close shrinks from an O(workers × model) sweep to an O(model)
  scale-and-apply, and peak buffered gradient memory drops from N× model
  to ~1× model.  The optimizer apply runs OUTSIDE ``_state_lock`` (an
  "aggregating" phase flag guards the iteration), so pushes for other
  iterations and sync polls are never blocked behind the apply.  Duplicate
  pre-barrier pushes from the same worker are **first-push-wins**: later
  payloads are ignored per tensor name, which makes an RPC retry of a push
  that actually landed (the worker replays an identical payload —
  worker/worker.py) converge to exactly one contribution.
- **buffered** — the classic escape hatch: per-worker gradients are
  buffered whole and the contributor mean is taken at barrier close under
  ``_state_lock`` (duplicate pushes are last-push-wins, the original
  semantics).  Same contributor-mean math; use it when the per-worker
  buffers themselves are wanted (debugging, exact reference timing).

Striped hot path (``PSDT_STRIPES``, default = usable cores; ISSUE 5):
the store is partitioned into S fixed stripes by tensor name
(core/stripes.py — a stripe never splits one tensor's reduction, so
striped results are bit-for-bit equal to serial).  Streaming folds run
their numpy adds OUTSIDE ``_state_lock`` under per-stripe locks — the
reservation (dedup, seal check) stays under ``_state_lock``, the O(bytes)
``np.add`` does not, so concurrent pushes fold different stripes on
different cores.  The barrier close seals the iteration and DRAINS
in-flight folds (``IterationState.inflight`` over the barrier condition
variable) before taking the accumulator, then runs the scale and the
optimizer apply on the shared named executor: both are elementwise, so
a host store is cut by ELEMENT RANGES and not by name (S nearly equal
ranges whatever the tensors are called; ``HostOptimizer.tick`` once,
``prepare``, then ``update_range`` per piece), out of place into the
buffers of the store retired two closes ago (core/close_buffers.py);
a device store keeps ``apply_shard`` per stripe of names.
``PSDT_STRIPES=1`` bypasses every striped branch — the exact serial
code path, timing included.

Accelerator-resident apply (``PSDT_DEVICE_APPLY=1``; ISSUE 11): with a
device-resident sharded optimizer selected
(async_sgd/device_optimizer.ShardedDeviceOptimizer), fold chunks land
as DEVICE buffers — quantized payloads dequantize on device
(rpc/data_plane.decode_gradients → core/device_apply) — the
accumulator holds device sums (:func:`_fold_one` is type-driven), the
contributor-mean scale and the striped optimizer apply run as
jit-compiled device programs, and the fresh store's D2H readback
starts asynchronously right after the swap so a serve-side encode
never stalls on the transfer (:meth:`ParameterServerCore.
_note_device_apply`).  Flag off (the default): every path above is
byte-identical to the pre-existing host-numpy behavior, wire bytes
included.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Mapping

import numpy as np

from ..analysis.lock_order import checked_lock
from ..async_sgd.damping import StalenessDamping, async_damping
from ..elastic import quorum as equorum
from ..obs import flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from ..replication.messages import STALE_SHARD_MAP
from . import arena as arena_mod
from . import device_apply
from .close_buffers import CloseBuffers
from .fold_buffers import FoldBuffers
from .optimizer import HostOptimizer, SGD, split_updates
from .stripes import (partition_names, partition_ranges, run_striped,
                      stripe_count, stripe_of)
from .tensor import TensorStore, store_nbytes, tree_like

log = logging.getLogger("pst.core")

AGGREGATION_MODES = ("streaming", "buffered")

# Synthetic pusher-id namespace of the hierarchical-aggregation tier
# (tiers/messages.py re-exports this as the protocol constant): a pusher
# at or above this base is a leaf aggregator's GROUP contribution and is
# only accepted when the contribution map names it — an unknown
# aggregate id is rejected RETRYABLY rather than folded as a phantom
# weight-1 worker, because folding it would double-count its members'
# gradients the moment they replay flat.  Real worker ids must stay
# below this base (docs/training.md).
TIER_AGGREGATE_ID_BASE = 1 << 20


class IterationState:
    __slots__ = ("worker_gradients", "aggregated", "aggregating", "sealed",
                 "workers_at_aggregation", "accum", "counts", "folded",
                 "folding", "inflight", "contributors", "buffer_bytes",
                 "quorum_at")

    def __init__(self):
        # buffered mode: whole per-worker gradient stores
        self.worker_gradients: dict[int, TensorStore] = {}
        # streaming mode: running per-name f32 sums + per-name contributor
        # counts (per-name so workers pushing disjoint tensor subsets —
        # the sharded topology — average correctly, exactly like the
        # buffered _mean_over_workers did)
        self.accum: TensorStore = {}
        self.counts: dict[str, int] = {}
        # streaming dedup: worker -> tensor names already folded, so a
        # retried (replayed) push or a duplicate never double-counts
        self.folded: dict[int, set[str]] = {}
        # striped folds: worker -> names RESERVED under _state_lock whose
        # numpy adds are still running outside it (moved to `folded` on
        # success, released on failure so a retry is not dropped), plus
        # the count of fold operations currently outside the lock — the
        # barrier close drains it to zero before taking the accumulator
        self.folding: dict[int, set[str]] = {}
        self.inflight = 0
        # Workers whose push COMPLETED (stream fully received) — only
        # these count toward the barrier width.  Folded VALUES from a
        # stream still in flight are already in `accum` (fold-on-arrival
        # is the point); if the barrier closes without that worker — a
        # worker dying mid-stream whose eviction shrinks the elastic
        # width — its already-folded tensors stay in their per-name
        # means.  Each tensor remains a true mean of real worker
        # gradients for that tensor (per-name counts divide correctly);
        # the contributor SET can differ across tensors in that rare
        # case, exactly as it legitimately does under sharded
        # disjoint-subset pushes.  A worker that instead retries
        # completes the same contribution via the dedup set.
        self.contributors: set[int] = set()
        self.aggregated = False
        # streaming close in flight: the accumulator has been taken and
        # the O(model) scale+apply is running outside _state_lock
        self.aggregating = False
        # Set (and never cleared) the first time a close is ATTEMPTED: the
        # contributor set is frozen from that point — later folds are
        # discarded and later commits read "in progress".  A failed apply
        # (aggregating comes back down, close retried by the next poll)
        # must not let a straggler mix into the restored accumulator,
        # whose sums are already scaled to means.
        self.sealed = False
        self.workers_at_aggregation = 0
        self.buffer_bytes = 0
        # K-of-N quorum close (elastic/quorum.py, ISSUE 13): monotonic
        # stamp of the moment the contributor count first reached the
        # quorum threshold — the grace window counts from here.  Reset
        # to None if an elastic width change lifts the threshold back
        # above the count.  None while quorum is off or unreached.
        self.quorum_at: float | None = None


class PushResult:
    """Result of a gradient push (mirrors PushResponse fields —
    reference: proto/parameter_server.proto:26-33)."""
    __slots__ = ("success", "message", "iteration", "aggregation_complete",
                 "workers_received", "total_workers")

    def __init__(self, success: bool, message: str, iteration: int,
                 aggregation_complete: bool, workers_received: int,
                 total_workers: int):
        self.success = success
        self.message = message
        self.iteration = iteration
        self.aggregation_complete = aggregation_complete
        self.workers_received = workers_received
        self.total_workers = total_workers


class PushSink:
    """One worker's push in progress (possibly chunk-streamed).

    Returned by :meth:`ParameterServerCore.begin_push`.  RPC handlers feed
    each decoded chunk through :meth:`fold` as it arrives and call
    :meth:`commit` when the request stream ends, so decode ⊕ accumulate
    overlap the transport of later chunks.  In streaming sync mode each
    fold adds straight into the iteration's shared running accumulator (no
    per-worker copy is ever buffered); in buffered or async mode folds
    stage into a private dict and commit routes through the classic
    whole-push paths (an async apply must be atomic)."""

    __slots__ = ("_core", "worker_id", "iteration", "_buffer", "_group",
                 "stale_map_epoch", "weight", "members", "stale_redirect")

    def __init__(self, core: "ParameterServerCore", worker_id: int,
                 iteration: int, streaming: bool,
                 weight: int = 1, members: tuple[int, ...] | None = None):
        self._core = core
        self.worker_id = int(worker_id)
        self.iteration = int(iteration)
        # Tier contribution (tiers/, ISSUE 9): a leaf aggregator's ONE
        # upstream push carries its whole group — the fold weights the
        # per-name counts by the group size (the PS mean stays a mean
        # over WORKERS) and the commit marks every member id a barrier
        # contributor.  (1, (worker,)) for ordinary pushes — behavior
        # identical to pre-tier.  Group pushes STAGE their chunks and
        # fold atomically at commit (one _state_lock hold checks member
        # overlap, folds, and publishes the cover): a member's racing
        # flat push — the mid-iteration downgrade recovery — then lands
        # strictly before (group rejected, members replay flat) or
        # strictly after (member dedups as a duplicate), never half-way
        # into a double count.
        self.weight = int(weight)
        self.members = members if members is not None else (self.worker_id,)
        # != 1 (not > 1): an EMPTY member tuple is the unknown-aggregate
        # rejection marker — staged like a group push so the commit can
        # bounce it whole (see _contribution_for / _commit_group_push)
        self._group = streaming and len(self.members) != 1
        self._buffer: dict | None = ({} if (not streaming or self._group)
                                     else None)
        # set when any folded chunk touched a tensor a live reshard moved
        # to another owner (core._retired): the commit then reports the
        # whole push rejected with the stale-shard-map marker so the
        # sharded client refreshes its map and replays the round
        self.stale_map_epoch: int | None = None
        # set when chunks arrived after the iteration's (quorum) seal
        # and were folded FORWARD into a later iteration's accumulator
        # (elastic/, ISSUE 13): (target iteration, staleness).  The
        # commit then marks the worker a contributor of the TARGET
        # instead of reporting a bare late push.
        self.stale_redirect: tuple[int, int] | None = None

    @property
    def folds_at_once(self) -> bool:
        """Whether :meth:`fold` has read its arrays by the time it returns
        and keeps none of them: the streaming single-member sink, which
        sums them into the iteration's accumulator inside the call.  Its
        caller may then hand it views of a buffer about to be refilled
        (``decode_gradients(borrow=True)``).  A sink that stages what it
        is given (buffered, async, a tier group) keeps the arrays and
        must own them."""
        return self._buffer is None

    def fold(self, gradients: Mapping[str, np.ndarray]) -> None:
        if self._buffer is not None:
            self._buffer.update(gradients)
        else:
            # in a streamed push the folds run in the stream loop, beside
            # the transport, outside ps/apply: a leg of their own
            with obs_trace.span("ps/fold", worker=self.worker_id,
                                iteration=self.iteration):
                stale, redirect = self._core._fold_chunk(
                    self.worker_id, self.iteration, gradients)
            if stale is not None:
                self.stale_map_epoch = stale
            if redirect is not None and (
                    self.stale_redirect is None
                    or redirect[0] > self.stale_redirect[0]):
                self.stale_redirect = redirect

    def commit(self) -> PushResult:
        if self.stale_map_epoch is not None:
            return self._core._stale_map_result(self.iteration,
                                                self.stale_map_epoch)
        if self._group:
            return self._core._commit_group_push(
                self.worker_id, self.iteration, self._buffer, self.weight,
                self.members)
        if self._buffer is not None:
            return self._core.receive_gradients(self.worker_id,
                                                self.iteration, self._buffer)
        if self.stale_redirect is not None:
            return self._core._commit_stale_push(
                self.worker_id, self.iteration, *self.stale_redirect)
        return self._core._commit_push(self.worker_id, self.iteration)


def _fold_one(accum: "TensorStore", counts: dict[str, int], name: str, g,
              weight: int, buffers: FoldBuffers | None = None) -> int:
    """Fold one tensor into the running accumulator — type-driven
    (ISSUE 11): numpy gradients keep the exact pre-existing
    np.array/np.add values (byte-identical with the device path off;
    with ``buffers`` the seed is the same one-pass convert-and-copy, into
    a buffer the core kept from the accumulator it closed last and not
    into new pages — core/fold_buffers.py); ``g`` is only READ, so it may
    be a read-only view of a received frame;
    device-decoded gradients (rpc/data_plane.decode_gradients) seed an
    owned device array and accumulate via the correctly-rounded device
    add, so a leaf aggregator's member folds run as device reductions
    and the sharded device apply consumes the sums with no host
    round-trip.  Returns bytes newly resident (the seeding copy), 0 for
    an accumulate.  Raises (mutating nothing, the name unmarked) on a
    shape mismatch — the fold-retry contract on both paths (the device
    add's shape check happens at trace time, before its donation)."""
    acc = accum.get(name)
    if acc is None:
        if device_apply.is_device_array(g):
            # FORCED-OWNED copy, not an adoption (the numpy branch's
            # np.array seed, on device): decoded wire buffers can be
            # zero-copy views of host memory, and donating such a
            # buffer makes every later fold_add fall back to a fresh
            # allocation INSIDE the barrier close — the copy here runs
            # at ingress time, overlapped with the arriving stream
            acc = device_apply.owned_copy(g)
        else:
            # owned f32 copy in ONE pass (convert-and-copy fused;
            # asarray-then-astype would sweep twice for non-f32 decodes)
            # — the exact pre-existing path for numpy AND for duck-typed
            # array-likes that only implement __array__
            if buffers is None:
                acc = np.array(g, dtype=np.float32)
            else:
                src = np.asarray(g)
                acc = buffers.take(name, src.shape)
                np.copyto(acc, src, casting="unsafe")
        accum[name] = acc
        counts[name] = weight
        return int(acc.nbytes)
    if isinstance(acc, np.ndarray):
        # a mixed stream (legacy repeated-float chunks decode host-side
        # even when packed chunks land on device) converges to the
        # accumulator's residence
        np.add(acc, np.asarray(g, np.float32), out=acc)
    else:
        accum[name] = device_apply.fold_add(acc, g)
    counts[name] += weight
    return 0


def _store_ready(store: "TensorStore") -> bool:
    """True iff every array is materialized.  numpy arrays always are;
    jax Arrays expose non-blocking ``is_ready()`` (False while the async
    dispatch that produces them is still running)."""
    for v in store.values():
        ready = getattr(v, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


def _block_on_store(store: "TensorStore") -> None:
    for v in store.values():
        wait = getattr(v, "block_until_ready", None)
        if wait is not None:
            wait()


def _close_leg(close):
    """``_close_barrier_locked`` as the ``ps/close`` leg: one span and one
    observation of ``ps.barrier_close_s`` per attempt (scale + optimizer,
    the drain of in-flight folds before them)."""
    @functools.wraps(close)
    def timed_close(self, iteration, state, received, total=0):
        with obs_trace.timed("ps/close", self._obs_barrier_close,
                             iteration=iteration, workers=received):
            return close(self, iteration, state, received, total)
    return timed_close


class ParameterServerCore:
    def __init__(self,
                 total_workers: int = 2,
                 optimizer: HostOptimizer | None = None,
                 staleness_bound: int = 0,
                 live_workers_fn: Callable[[], int] | None = None,
                 live_workers_ttl_s: float = 0.0,
                 gc_iterations: int = 64,
                 aggregation: str | None = None,
                 stripes: int | None = None,
                 contributions_fn: Callable[
                     [], Mapping[int, tuple[int, tuple[int, ...]]] | None]
                 | None = None,
                 contributions_ttl_s: float = 1.0,
                 quorum: float | None = None,
                 quorum_grace_ms: float | None = None,
                 freerun: bool | None = None):
        mode = (aggregation or os.environ.get("PSDT_AGGREGATION")
                or "streaming").lower()
        if mode not in AGGREGATION_MODES:
            raise ValueError(f"unknown aggregation mode {mode!r}; "
                             f"options: {AGGREGATION_MODES}")
        self._aggregation = mode
        self._params: TensorStore = {}
        # Locks come from the analysis subsystem's factory: plain
        # threading.Lock normally, an order-asserting CheckedLock proxy
        # under PSDT_LOCK_CHECK=1 (analysis/lock_order.py — the declared
        # rank table the static analyzer checks is enforced live).
        self._params_lock = checked_lock(
            "ParameterServerCore._params_lock")  # reference: params_mutex_ (h:44)
        self._state_lock = checked_lock(
            "ParameterServerCore._state_lock")   # reference: state_mutex_ (h:52)
        # Serializes streaming-mode barrier applies, which run OUTSIDE
        # _state_lock so pushes/polls for other iterations proceed during
        # the optimizer apply.  Never held while acquiring _state_lock.
        self._apply_lock = checked_lock("ParameterServerCore._apply_lock")
        # Stripe partition of the hot path (PSDT_STRIPES / constructor
        # override; 1 = exact serial behavior).  One lock per stripe, all
        # at one shared declared rank: a stripe lock is only ever taken
        # with no other lock held, and never two at once (core/stripes.py,
        # analysis/lock_order.py).
        self._stripes = stripe_count(stripes)
        self._stripe_locks = [
            checked_lock("ParameterServerCore._stripe_lock")
            for _ in range(self._stripes)]
        # striped-apply observability: per-stripe apply wall time and the
        # achieved parallelism (sum of stripe times / wall time) of the
        # last stripe-parallel optimizer apply
        self._obs_stripe_ms = obs_stats.histogram("ps.apply.stripe_ms")
        self._obs_parallelism = obs_stats.gauge("ps.apply.parallelism")
        # where the range-cut close writes the store it is about to
        # publish: the buffers of the store retired two closes ago
        self._close_buffers = CloseBuffers()
        # where a host accumulator's sums are seeded: the buffers of the
        # accumulator closed last
        self._fold_buffers = FoldBuffers()
        # accelerator-resident applies (ISSUE 11): count of barrier
        # closes whose fresh store is device-resident (the pst-status
        # "device apply" rollup line reads this)
        self._obs_device_applies = obs_stats.counter("ps.apply.device")
        # Barrier-completion broadcast over _state_lock: the fused data
        # plane (PushPullStream) parks here and is woken the instant an
        # aggregation fires, instead of being polled at 20 Hz like the
        # reference's CheckSyncStatus loop (src/worker.cpp:372-389).
        self._barrier_cv = threading.Condition(self._state_lock)
        self._iteration_states: "OrderedDict[int, IterationState]" = OrderedDict()
        self._static_total_workers = int(total_workers)
        self._live_workers_fn = live_workers_fn
        self._live_ttl = float(live_workers_ttl_s)
        self._live_cache: tuple[int, float] = (0, 0.0)  # (value, expiry)
        # Registry-generation invalidation (elastic/, ISSUE 13): a
        # provider exposing a cheap ``generation()`` (the coordinator's
        # registry generation / membership epoch) lets barrier_width()
        # refresh the TTL cache the instant the live set changed — a
        # reaped worker shrinks the barrier at the next width read
        # instead of a TTL lapse.  None for plain callables: exactly the
        # pre-existing TTL behavior.
        self._live_gen_fn = getattr(live_workers_fn, "generation", None)
        self._live_gen: int | None = None
        # DRAINING ids ride the same refresh (fleet/, ISSUE 14
        # satellite — the PR 13 leftover): a provider exposing
        # ``draining()`` (an iterable of worker ids) lets the K-of-N
        # quorum threshold pre-shrink by the announced drains, and lets
        # the close skip the grace window only when the absentees
        # really ARE the drains (see _quorum_ready_locked).  Providers
        # without it (plain callables, pre-elastic topologies) leave it
        # empty — byte-identical thresholds.
        self._live_draining_fn = getattr(live_workers_fn, "draining", None)
        self._live_draining_ids: frozenset[int] = frozenset()
        # Guards _live_cache: barrier_width() is called from many handler
        # threads at once, and an unguarded expiry race both issues
        # redundant remote registry calls and can publish a torn
        # (value, expiry) pair.  Held across the provider call so exactly
        # one thread refreshes per expiry; the others briefly queue and
        # read the fresh value (they would have paid their own remote
        # round-trip otherwise).
        self._live_lock = checked_lock("ParameterServerCore._live_lock")
        # Hierarchical aggregation (tiers/, ISSUE 9): provider of the
        # {aggregate_id: (weight, member ids)} contribution map — a leaf
        # aggregator's upstream push folds with its group's weight and
        # covers its member ids on the barrier.  TTL-cached exactly like
        # the live-worker count (the provider may be a coordinator RPC;
        # _tier_lock single-flights the refresh — BLOCKING_ALLOWED).
        # None provider / empty map = flat: every push weighs 1.
        self._contributions_fn = contributions_fn
        self._contrib_ttl = float(contributions_ttl_s)
        self._contrib_cache: tuple[
            Mapping[int, tuple[int, tuple[int, ...]]] | None, float] = \
            (None, 0.0)
        self._tier_lock = checked_lock("ParameterServerCore._tier_lock")
        # Barrier relay (tiers/leaf.py, ISSUE 9): when set, the streaming
        # barrier close hands (iteration, sums, counts) to the relay
        # instead of running scale + optimizer apply, and installs the
        # store the relay returns — the leaf aggregator's "apply" is one
        # quantized upstream push whose fused response IS the fresh
        # params its group gets served.  Runs under _apply_lock
        # (BLOCKING_ALLOWED — same discipline as sync replication).
        self._barrier_relay: Callable[
            [int, TensorStore, dict[str, int]], TensorStore] | None = None
        self._optimizer = optimizer or SGD(learning_rate=1.0)
        self._staleness_bound = int(staleness_bound)
        # Free-running barrier-free training (freerun/, ISSUE 16): armed
        # by PSDT_FREERUN / the constructor, default off = every
        # existing path byte-identical.  Every push applies on arrival
        # damped by beta^staleness, dedup'd by a per-(worker, step)
        # version vector, served through a coalesced publication
        # (FreeRunEngine).  Downgrade matrix (docs/training.md): the
        # buffered escape hatch and bounded-staleness async mode both
        # win over free-run — the first because free-run reuses the
        # streaming fold machinery, the second because it is the
        # narrower contract; an armed quorum is force-disabled below.
        # (lazy import: freerun/engine.py imports back into this module)
        from .. import freerun as freerun_mod
        self._freerun = None
        if freerun_mod.enabled(freerun):
            reason = None
            if not self._streaming:
                reason = "buffered aggregation is armed"
            elif self._staleness_bound > 0:
                reason = "bounded-staleness async mode is armed"
            if reason is not None:
                log.warning("PSDT_FREERUN requested but %s; free-run "
                            "disabled (downgrade matrix, docs/training.md)",
                            reason)
            else:
                self._freerun = freerun_mod.FreeRunEngine(self)
        # Flat arena apply (core/arena.py, ISSUE 15): per-stripe
        # mega-array layout for fold, close, readback, and encode.
        # Armed by PSDT_ARENA for streaming-sync cores whose optimizer
        # speaks the flat-slab stage family (ShardedDeviceOptimizer);
        # default off = the PR 11 per-tensor path, byte-identical.  Any
        # shape the flat layout cannot represent exactly downgrades the
        # affected CLOSE to the per-tensor path (counter + flight code),
        # and a packing exception latches the arena off — never a boot
        # or close failure.
        self._arena = (
            arena_mod.ArenaManager(self._stripes)
            if (arena_mod.enabled()
                and self._streaming and self._staleness_bound == 0
                and self._freerun is None
                and getattr(self._optimizer, "supports_arena", False)
                and device_apply.available())
            else None)
        # K-of-N quorum barriers (elastic/quorum.py, ISSUE 13): 0.0 =
        # off, the default — every pre-existing path byte-identical.
        # Armed (PSDT_QUORUM / constructor), the streaming sync barrier
        # seals once ceil(quorum * width) contributors committed AND the
        # grace window past the K-th commit elapsed; stragglers sealed
        # out fold forward into the next iteration's accumulator damped
        # by beta^staleness (async_sgd/damping.py — the shared policy),
        # bounded by max(1, staleness_bound).
        self._quorum = equorum.quorum_fraction(quorum)
        self._quorum_grace_s = equorum.grace_s(quorum_grace_ms)
        if self._freerun is not None and self._quorum:
            # mutual exclusion (docs/training.md downgrade matrix):
            # free-run has no barrier for a K-of-N quorum to close
            log.warning("PSDT_QUORUM ignored: free-run mode has no "
                        "barrier to close")
            self._quorum = 0.0
        self._damping = StalenessDamping() if self._quorum else None
        # bounded-staleness async damping: armed ONLY by an explicit
        # PSDT_STALENESS_BETA (pre-existing async runs stay
        # byte-identical without it)
        self._async_damping = (async_damping()
                               if self._staleness_bound > 0 else None)
        self._obs_quorum_closes = obs_stats.counter(
            "ps.barrier.quorum_closes")
        self._obs_stale_folds = obs_stats.counter("ps.stale.folds")
        self._gc_iterations = int(gc_iterations)
        self._current_iteration = 0
        self._epoch = 0
        self._applied_updates = 0  # async mode: count of applied pushes
        # Monotone store version: bumped on every parameter mutation
        # (apply/initialize/restore).  The serve-side encode-once cache
        # (server/ps_service.py) keys on it, and a version probe lets a
        # cache-hit serve skip the per-request store copy entirely.
        self._params_version = 0
        self._serving_version = 0
        # Resident buffered-gradient accounting (accumulators + buffered
        # worker stores across live iteration states), for the
        # ps.peak_grad_buffer_bytes gauge (tests/test_aggregation.py).
        self._grad_buffer_bytes = 0
        self._peak_grad_buffer_bytes = 0
        self._obs_peak_buffer = obs_stats.gauge("ps.peak_grad_buffer_bytes")
        # Wall time of the barrier close (mean/scale + optimizer apply) —
        # O(model) in streaming mode, O(workers × model) in buffered.
        self._obs_barrier_close = obs_stats.histogram("ps.barrier_close_s")
        # Highest iteration whose aggregation has completed.  Needed so a
        # straggler push for a GC'd iteration is recognized as late (no-op)
        # instead of re-buffering a stale gradient into a fresh state.
        self._aggregated_watermark = -1
        # Async mode: iteration of the bootstrap push, so racing duplicate
        # init pushes from other workers are recognized and dropped.
        self._bootstrap_iteration: int | None = None
        # Bumped by restore().  The streaming barrier close applies outside
        # _state_lock; a checkpoint restore that lands inside that window
        # obsoletes the in-flight aggregate, and the closer checks this
        # generation to drop it instead of applying a stale mean on top of
        # the restored store (or resurrecting the watermark restore reset).
        self._restore_epoch = 0
        # Reshard tombstones (replication/): tensor name -> shard-map
        # epoch at which the name moved to another owner.  Pushes that
        # touch a retired name are rejected with the stale-shard-map
        # marker (the worker refreshes its map and repartitions); folds
        # drop them so a half-folded push never pollutes the accumulator.
        # Guarded by _state_lock on the fold paths.
        self._retired: dict[str, int] = {}
        # Replication hook (replication/replicator.py): invoked by the
        # streaming barrier close right after the optimizer apply, while
        # _apply_lock is still held (applies stay serialized, so the
        # hook may read the store consistently and — in sync mode —
        # block on the ship; _apply_lock is BLOCKING_ALLOWED).
        self._on_apply: Callable[[], None] | None = None
        # Cross-replica sharded update (replication/sharded_update.py):
        # when armed, the arena close offers the primary's fold sums to
        # the updater, which partitions the stage sweep across the
        # replica set and all-gathers the fresh slabs — replication
        # bandwidth becomes the collective.  None = every close is local.
        self._sharded_updater = None
        # Delta sink (delta/chain.py DeltaChain, ISSUE 10): told about
        # every SYNCHRONOUS apply's (store, version) right after the
        # swap — still inside the serialized apply section, so the sink
        # reads values no later apply can be mutating — and reset()
        # whenever the store changes outside the apply timeline
        # (restore / replication install / reshard retire), because a
        # delta against a pre-reset base would patch the wrong world.
        # The sink must not raise (DeltaChain.note_apply catches).
        self._delta_sink = None
        # Async non-blocking serve: device optimizers dispatch their apply
        # asynchronously (jax), so right after a push the new store is a
        # promise.  Reads must not stall on that compute — bounded
        # staleness already tolerates serving the previous version — so
        # this holds the latest fully-materialized store until the
        # in-flight apply lands (serve_parameters promotes it).  None in
        # sync mode and whenever _params is known materialized.
        self._serving: TensorStore | None = None
        # Lock order: _state_lock before _apply_lock before _params_lock,
        # everywhere; _apply_lock is never held while acquiring
        # _state_lock (the streaming closer drops _apply_lock first).

    # ------------------------------------------------------------------ props
    @property
    def synchronous(self) -> bool:
        return self._staleness_bound == 0

    @property
    def aggregation_mode(self) -> str:
        return self._aggregation

    @property
    def stripes(self) -> int:
        return self._stripes

    @property
    def _streaming(self) -> bool:
        return self._aggregation == "streaming"

    @property
    def device_fold(self) -> bool:
        """True when push chunks should decode to DEVICE buffers
        (rpc/data_plane.decode_gradients, ISSUE 11): the accelerator-
        resident apply is enabled (``PSDT_DEVICE_APPLY``) and this core
        either applies on device (the sharded device optimizer family)
        or is a leaf aggregator whose member folds should run as device
        reductions (the PR-9 in-process intra-host tier).  Streaming
        sync mode only — the buffered escape hatch, async mode, and
        free-run mode stage and apply host-side, unchanged."""
        if self._freerun is not None or not (
                self._streaming and self.synchronous
                and device_apply.enabled()):
            return False
        return ((device_apply.wants_device_fold(self._optimizer)
                 or self._barrier_relay is not None)
                and device_apply.available())

    def _note_device_apply(self, store: TensorStore, t0: float) -> None:
        """Post-swap bookkeeping of a device-resident apply: start the
        async D2H readback of every fresh device value — so a serve-side
        encode (behind the encode-once cache) finds the host bytes
        already in flight instead of stalling on the transfer — and
        record the apply.device flight code + counter.  No-op for
        host-numpy stores, so every pre-existing path is untouched."""
        if not device_apply.is_device_store(store):
            return
        device_apply.readback_async(store)
        flight.record("apply.readback", a=len(store))
        self._obs_device_applies.add()
        flight.record("apply.device",
                      a=int(1e6 * (time.perf_counter() - t0)),
                      b=self._stripes)

    @property
    def current_iteration(self) -> int:
        return self._current_iteration

    @property
    def params_version(self) -> int:
        return self._params_version

    @property
    def grad_buffer_bytes(self) -> int:
        """Currently-resident buffered gradient bytes (accumulators plus
        buffered per-worker stores)."""
        return self._grad_buffer_bytes

    @property
    def peak_grad_buffer_bytes(self) -> int:
        return self._peak_grad_buffer_bytes

    @property
    def epoch(self) -> int:
        return self._epoch

    @epoch.setter
    def epoch(self, value: int) -> None:
        self._epoch = int(value)

    def barrier_width(self) -> int:
        """Current barrier width.  Elastic when a live-worker provider is
        installed: the barrier follows live registrations instead of a
        process-lifetime constant (reference fixes it at startup —
        src/parameter_main.cpp:14-15)."""
        if self._live_workers_fn is not None:
            with self._live_lock:
                live, expiry = self._live_cache
                gen = (self._live_gen_fn()
                       if self._live_gen_fn is not None else None)
                if (self._live_ttl <= 0 or time.monotonic() >= expiry
                        or (gen is not None and gen != self._live_gen)):
                    # TTL cache: the provider may be a remote registry RPC;
                    # the barrier width is read on every push and 20 Hz
                    # sync poll, so don't issue hot-path I/O for a value
                    # that changes in seconds.  One refresher per expiry
                    # (see _live_lock above).  A registry GENERATION move
                    # (cheap local read — elastic/, ISSUE 13) invalidates
                    # early: a reaped or drained worker narrows the
                    # barrier at the next width read, not a TTL lapse.
                    live = int(self._live_workers_fn())
                    self._live_cache = (live, time.monotonic() + self._live_ttl)
                    self._live_gen = gen
                    if self._live_draining_fn is not None:
                        # last-seen drain ids, refreshed with the width
                        # (the provider answers from the same membership
                        # response — no extra RPC)
                        self._live_draining_ids = frozenset(
                            int(w) for w in self._live_draining_fn())
            if live > 0:
                return live
        return self._static_total_workers

    def set_total_workers(self, n: int) -> None:
        self._static_total_workers = int(n)

    # ------------------------------------------------------------------ tiers
    def set_contributions_fn(self, fn, ttl_s: float | None = None) -> None:
        """Install (or clear) the tier contribution-map provider
        (tiers/topology.py TierContributionProvider)."""
        with self._tier_lock:
            self._contributions_fn = fn
            if ttl_s is not None:
                self._contrib_ttl = float(ttl_s)
            self._contrib_cache = (None, 0.0)

    def set_barrier_relay(self, relay) -> None:
        """Install the leaf-aggregator barrier relay (tiers/leaf.py): the
        streaming close calls ``relay(iteration, sums, counts)`` under
        _apply_lock instead of scale+apply and installs the returned
        store.  A raise leaves the barrier retryable exactly like a
        failed optimizer apply (the accumulator is put back, counts
        intact — the relay must not mutate ``sums``)."""
        self._barrier_relay = relay

    def _contribution_for(self, worker_id: int
                          ) -> tuple[int, tuple[int, ...]]:
        """(weight, member ids) of a pusher — (1, (worker_id,)) unless
        the tier topology maps it to a group contribution.  Called with
        NO core lock held (the provider may RPC); the map is TTL-cached
        under _tier_lock, single-flight per expiry like barrier_width's
        live cache.

        An AGGREGATE id (>= TIER_AGGREGATE_ID_BASE) absent from the map
        returns ``(0, ())`` — the retryable-rejection marker — instead
        of a phantom weight-1 contribution: the cache is force-refreshed
        once first (a just-confirmed group is routinely fresher than the
        TTL), and a group push the PS cannot attribute must bounce so
        its members replay flat rather than be double-counted."""
        wid = int(worker_id)
        if self._contributions_fn is None:
            return ((1, (wid,)) if wid < TIER_AGGREGATE_ID_BASE
                    else (0, ()))
        with self._tier_lock:
            contrib, expiry = self._contrib_cache
            if (time.monotonic() >= expiry
                    or (wid >= TIER_AGGREGATE_ID_BASE
                        and wid not in (contrib or {}))):
                fresh = self._contributions_fn()
                if fresh is not None:
                    contrib = fresh
                # a provider hiccup (None with a map already cached)
                # keeps serving the stale map rather than flapping the
                # weights mid-iteration
                self._contrib_cache = (contrib,
                                       time.monotonic() + self._contrib_ttl)
            entry = (contrib or {}).get(wid)
        if entry is None:
            return ((1, (wid,)) if wid < TIER_AGGREGATE_ID_BASE
                    else (0, ()))
        weight, members = entry
        return int(weight), tuple(int(m) for m in members)

    # ------------------------------------------------------------------ delta
    def set_delta_sink(self, sink, *, seed: bool = True) -> None:
        """Install (or clear) the versioned-delta sink (delta/chain.py):
        ``sink.note_apply(store, version)`` after every synchronous
        apply, ``sink.reset()`` on restore/install/retire.  note_apply
        runs inside the serialized apply section (under _apply_lock on
        the streaming path, _state_lock on the buffered path) and MUST
        NOT raise.

        ``seed=True`` requires a quiescent core: the snapshot below
        encodes OUTSIDE the apply serialization, so it is only safe
        before the server starts taking traffic.  A sink installed
        while applies may be in flight (the service's lazy arming on
        the first dtype-compatible delta request) passes ``seed=False``
        — the next serialized apply reseeds the retained image instead,
        costing one extra full serve but never a torn base."""
        self._delta_sink = sink
        if sink is None or not seed:
            return
        # seed from the live store so a core initialized BEFORE the sink
        # was installed still diffs from its very next apply (no traffic
        # is flowing at install time — the service owns the core before
        # the server starts — so this is effectively serialized)
        with self._params_lock:
            store, version = self._params, self._params_version
        if store and _store_ready(store):
            sink.note_apply(store, version)

    def _notify_delta(self, store: TensorStore, version: int) -> None:
        if self._freerun is not None:
            # free-run coalesces publication (freerun/engine.py): the
            # engine notes the sink at each coalesced publish, so a
            # per-push raw-version advance never rebuilds a delta pair
            # or wakes subscribers per push
            return
        if self._delta_sink is not None:
            self._delta_sink.note_apply(store, version)

    def _reset_delta(self) -> None:
        if self._freerun is not None:
            # restore/install/retire: published snapshot + version
            # vector belong to the pre-reset world
            self._freerun.reset()
        if self._delta_sink is not None:
            self._delta_sink.reset()

    # ----------------------------------------------------------------- params
    def initialize_parameters(self, params: Mapping[str, np.ndarray]) -> None:
        with self._params_lock:
            self._params = tree_like(params)
            self._params_version += 1
            store, version = self._params, self._params_version
        # seed the delta chain from the init so the FIRST apply already
        # serves a delta (outside _params_lock: the encode is O(model))
        self._notify_delta(store, version)

    def get_parameters(self) -> TensorStore:
        with self._params_lock:
            return dict(self._params)

    @property
    def has_parameters(self) -> bool:
        with self._params_lock:
            return bool(self._params)

    @property
    def has_retired(self) -> bool:
        """True when a live reshard has tombstoned tensors on this shard
        (replication/): pushes touching them answer stale-shard-map."""
        with self._state_lock:
            return bool(self._retired)

    def serve_parameters(self, iteration: int = 0) -> tuple[int, TensorStore, bool]:
        """Return (current_iteration, params copy, ready).  The iteration
        argument is accepted and ignored, matching the reference
        (src/parameter_server.cpp:93-97)."""
        it, params, ready, _ = self.serve_view(iteration)
        return it, params, ready

    def serve_view(self, iteration: int = 0) -> tuple[int, TensorStore, bool, int]:
        """(current_iteration, params copy, ready, store version) — the
        versioned serve the encode-once broadcast cache keys on.

        Async mode never blocks a read on an in-flight device apply: while
        the newest store is still a dispatched-but-unmaterialized promise,
        the previous (materialized) version is served — one extra step of
        staleness, which bounded-staleness mode tolerates by definition.
        Sync mode always serves ``_params`` itself: barrier clients must
        observe exactly the post-aggregation values they were promised.
        Free-run mode serves the engine's coalesced published snapshot
        (freerun/engine.py), so the served version advances at the
        publication cadence rather than per push."""
        if self._freerun is not None:
            return self._freerun.serve_view()
        with self._params_lock:
            if self._serving is not None:
                if _store_ready(self._params):
                    self._serving = None  # in-flight apply landed: promote
                else:
                    return (self._current_iteration, dict(self._serving),
                            True, self._serving_version)
            return (self._current_iteration, dict(self._params), True,
                    self._params_version)

    def serve_version(self) -> int:
        """The version :meth:`serve_view` would serve right now, WITHOUT
        copying the store — the cache-hit fast path: a serve whose encoded
        bytes are already cached never touches the parameters at all."""
        if self._freerun is not None:
            return self._freerun.serve_version()
        with self._params_lock:
            if self._serving is not None and not _store_ready(self._params):
                return self._serving_version
            return self._params_version

    # ------------------------------------------------------------------- push
    def begin_push(self, worker_id: int, iteration: int) -> PushSink:
        """Open a (possibly chunk-streamed) push.  The streaming handlers
        fold each decoded chunk as it arrives and commit at end-of-stream;
        the whole-store :meth:`receive_gradients` is the one-chunk case.
        The tier contribution lookup happens HERE, outside every core
        lock (tiers require the streaming sync path; buffered/async
        modes keep flat weight-1 semantics)."""
        if self._freerun is not None:
            # free-run (ISSUE 16): a private-accumulator sink — folds
            # run with no core lock at all, the commit applies on
            # arrival (freerun/engine.py)
            return self._freerun.begin_push(worker_id, iteration)
        streaming = self._streaming and self.synchronous
        weight, members = ((1, (int(worker_id),)) if not streaming
                           else self._contribution_for(worker_id))
        return PushSink(self, worker_id, iteration, streaming=streaming,
                        weight=weight, members=members)

    def receive_gradients(self, worker_id: int, iteration: int,
                          gradients: Mapping[str, np.ndarray]) -> PushResult:
        if self._freerun is not None:
            # the one-chunk case of the free-run sink (tier aggregate
            # ids are rejected retryably inside the commit)
            sink = self._freerun.begin_push(worker_id, iteration)
            sink.fold(gradients)
            return sink.commit()
        if (worker_id >= TIER_AGGREGATE_ID_BASE
                and not (self.synchronous and self._streaming)):
            # Tier group contributions exist ONLY on the streaming sync
            # path (weighted folds + member covers).  Under the buffered
            # escape hatch the push would count as one phantom worker
            # (members double-count on their flat replay), and in async
            # mode the raw group SUM would apply immediately at
            # group-size magnitude — reject retryably instead; the
            # leaf's members replay flat (config-skew protection).
            return PushResult(
                False,
                "tier aggregate contributions require the streaming "
                "synchronous aggregation path; replay flat",
                iteration, False, 0, self.barrier_width())
        if not self.synchronous:
            return self._receive_async(worker_id, iteration, gradients)
        if self._streaming:
            weight, members = self._contribution_for(worker_id)
            if len(members) != 1:
                # a whole-store group contribution (the leaf's unary
                # fallback path): atomic overlap-check + fold + cover —
                # or, with EMPTY members, the unknown-aggregate bounce
                return self._commit_group_push(worker_id, iteration,
                                               dict(gradients), weight,
                                               members)
            with obs_trace.span("ps/fold", worker=worker_id,
                                iteration=iteration):
                stale_epoch, redirect = self._fold_chunk(
                    worker_id, iteration, gradients)
            if stale_epoch is not None:
                return self._stale_map_result(iteration, stale_epoch)
            if redirect is not None:
                return self._commit_stale_push(worker_id, iteration,
                                               *redirect)
            return self._commit_push(worker_id, iteration)
        return self._receive_sync(worker_id, iteration, gradients)

    # ------------------------------------------------- streaming aggregation
    def _grad_buffer_note(self, delta: int) -> None:
        """Track resident buffered gradient bytes (caller holds
        _state_lock)."""
        self._grad_buffer_bytes += delta
        if self._grad_buffer_bytes > self._peak_grad_buffer_bytes:
            self._peak_grad_buffer_bytes = self._grad_buffer_bytes
            self._obs_peak_buffer.set(self._peak_grad_buffer_bytes)

    def _sync_state_locked(self, iteration: int) -> IterationState | None:
        """The iteration's state, created on first touch; None when the
        iteration is late (already aggregated and GC'd).  Caller holds
        _state_lock."""
        state = self._iteration_states.get(iteration)
        if state is None:
            if iteration <= self._aggregated_watermark:
                return None
            state = IterationState()
            self._iteration_states[iteration] = state
            self._gc_locked()
        return state

    def _stale_map_result(self, iteration: int, map_epoch: int,
                          total: int | None = None) -> PushResult:
        """The whole-push rejection for a push that touched tensors a
        live reshard moved to another owner: the sharded client matches
        the marker, refreshes the shard map (waiting for the epoch to
        advance past ``map_epoch``), repartitions, and replays the round
        — per-(worker, tensor) dedup makes the replay idempotent.
        ``total`` must be passed by callers holding _state_lock
        (barrier_width may hit a remote live-worker provider)."""
        return PushResult(
            False,
            f"{STALE_SHARD_MAP}: tensors moved at map epoch {map_epoch}; "
            f"refresh the shard map and repartition",
            iteration, False, 0,
            total if total is not None else self.barrier_width())

    def _split_retired_locked(
            self, gradients: Mapping[str, np.ndarray]
    ) -> tuple[Mapping[str, np.ndarray], int | None]:
        """(still-owned gradients, stale map epoch | None).  Caller holds
        _state_lock.  Retired (moved-away) tensors are dropped so they
        can never pollute this shard's accumulator; the surviving subset
        still folds — the replay under the new partition dedups it."""
        if not self._retired:
            return gradients, None
        hit = [n for n in gradients if n in self._retired]
        if not hit:
            return gradients, None
        stale_epoch = max(self._retired[n] for n in hit)
        return ({n: g for n, g in gradients.items()
                 if n not in self._retired}, stale_epoch)

    def _fold_chunk(self, worker_id: int, iteration: int,
                    gradients: Mapping[str, np.ndarray]
                    ) -> tuple[int | None, tuple[int, int] | None]:
        """Fold one chunk of a worker's push into the iteration's running
        accumulator (streaming sync mode).  Idempotent per (worker, tensor
        name): a replayed chunk — an RPC retry of a push that actually
        landed — is skipped, so retries converge to exactly one
        contribution (first-push-wins).  Chunks for an aggregated (or
        currently-aggregating) iteration are discarded — except under an
        armed quorum (ISSUE 13), where a straggler sealed out of its
        iteration folds FORWARD into the next open iteration's
        accumulator as a damped staleness-tagged contribution
        (:meth:`_stale_fold_locked`).  Returns ``(stale map epoch | None,
        stale redirect | None)``: the first when the chunk touched
        retired (reshard-moved) tensors — the caller turns that into a
        stale-shard-map push rejection — and the second as the
        ``(target iteration, staleness)`` of a forward fold.

        Striped (stripes > 1): only the reservation — dedup, seal check,
        state bookkeeping — runs under ``_state_lock``; the O(bytes)
        numpy adds run outside it under per-stripe locks, so concurrent
        pushes (and the stripes of ONE large chunk, fanned across the
        shared executor) fold on multiple cores at once."""
        with self._state_lock:
            self._current_iteration = max(self._current_iteration, iteration)
            gradients, stale_epoch = self._split_retired_locked(gradients)
            state = self._sync_state_locked(iteration)
            if (state is None or state.aggregated or state.sealed
                    or worker_id in state.contributors):
                # late / close-attempted / already-committed worker: chunk
                # is discarded (commit reports the push late or duplicate)
                # — unless the quorum sealed this worker out, in which
                # case the gradient folds forward damped
                redirect = None
                if (self._quorum_on() and gradients
                        and worker_id < TIER_AGGREGATE_ID_BASE
                        and (state is None
                             or worker_id not in state.contributors)):
                    redirect = self._stale_fold_locked(worker_id, iteration,
                                                       gradients)
                return stale_epoch, redirect
            # flight evidence (sampled: one per chunk is the hottest
            # event class): which worker reserved which fold when — the
            # per-chunk arrival record a postmortem orders folds by
            flight.record("fold.reserve", iteration=iteration,
                          worker=worker_id, a=len(gradients))
            if gradients:
                self._maybe_arena_accum_locked(state)
            folded = state.folded.setdefault(worker_id, set())
            if self._stripes <= 1:
                self._fold_into_locked(state, folded, gradients)
                return stale_epoch, None
            folding = state.folding.setdefault(worker_id, set())
            todo = [(name, g) for name, g in gradients.items()
                    if name not in folded and name not in folding]
            if not todo:
                return stale_epoch, None
            # reserve: a concurrent duplicate fold of the same (worker,
            # name) — e.g. a fast retry racing the original — sees the
            # reservation and skips instead of double-adding
            folding.update(name for name, _ in todo)
            state.inflight += 1
        self._fold_striped(state, worker_id, iteration, todo)
        return stale_epoch, None

    def _stale_fold_locked(self, worker_id: int, iteration: int,
                           gradients: Mapping[str, np.ndarray]
                           ) -> tuple[int, int] | None:
        """Quorum straggler fold (ISSUE 13; caller holds _state_lock):
        fold a push sealed out of ``iteration`` into the next OPEN
        iteration's accumulator, damped by ``beta ** staleness``
        (async_sgd/damping.py), bounded by ``max(1, staleness_bound)``.
        Returns ``(target iteration, staleness)`` or None when every
        in-bound target is already sealed/aggregated (the push degrades
        to the pre-existing late-push no-op).

        Dedup is the TARGET iteration's per-(worker, tensor) set: a
        retried stale push replays into the same names and folds
        nothing twice, and the worker's own REAL push for the target
        later dedups as a duplicate instead of double-counting — the
        straggler's carried gradient IS its contribution to that
        barrier.  The fold runs serial under _state_lock (the stale
        path is rare by construction — one straggler per quorum close)."""
        if (self._bootstrap_iteration is not None
                and iteration <= self._bootstrap_iteration):
            # the seed iteration: a slow worker's duplicate init push is
            # init-magnitude VALUES, not a gradient — folding it forward
            # would poison the next mean.  Plain late-push no-op; the
            # worker pulls the seeded store and proceeds.
            return None
        bound = max(1, self._staleness_bound)
        base = max(iteration + 1, self._aggregated_watermark + 1)
        for target in range(base, iteration + bound + 1):
            st = self._sync_state_locked(target)
            if st is None or st.aggregated or st.sealed:
                continue
            staleness = target - iteration
            folded = st.folded.setdefault(worker_id, set())
            reserved = st.folding.get(worker_id, ())
            todo = {name: g for name, g in gradients.items()
                    if name not in folded and name not in reserved}
            if todo:
                self._maybe_arena_accum_locked(st)
                self._fold_into_locked(
                    st, folded, self._damping.damp(todo, staleness))
                self._obs_stale_folds.add()
                flight.record("stale.fold", iteration=target,
                              worker=worker_id, a=staleness, b=len(todo))
            return target, staleness
        return None

    def _commit_stale_push(self, worker_id: int, iteration: int,
                           target: int, staleness: int) -> PushResult:
        """End-of-stream for a push whose chunks folded FORWARD
        (:meth:`_stale_fold_locked`): mark the worker a contributor of
        the TARGET iteration — its carried gradient counts toward that
        barrier, so no later barrier waits on a straggler that already
        contributed — and answer for the ORIGINAL iteration (complete
        once its apply published, in-progress while the close is still
        in flight, so the worker observes readiness exactly when it is
        real)."""
        total = self.barrier_width()
        with self._state_lock:
            orig = self._iteration_states.get(iteration)
            complete = orig is None or orig.aggregated
            if orig is None:
                received = total  # GC'd: the late-push convention
            elif orig.aggregated:
                received = orig.workers_at_aggregation
            else:
                # close still in flight: report the true contributor
                # count, the _push_guard_locked sealed-case convention
                received = len(orig.contributors)
            st = self._iteration_states.get(target)
            if st is not None and not st.aggregated and not st.sealed:
                if worker_id not in st.contributors:
                    st.contributors.add(worker_id)
                    flight.record("push.commit", iteration=target,
                                  worker=worker_id,
                                  a=len(st.contributors), b=total)
                self._maybe_aggregate_locked(target, st, total)
            return PushResult(
                True,
                f"stale push folded into iteration {target} "
                f"(staleness {staleness}, lr damped)",
                iteration, complete, received, total)

    def _maybe_arena_accum_locked(self, state: IterationState) -> None:
        """Decide a fresh iteration state's accumulator residence
        (caller holds _state_lock): with the flat arena armed and a
        packing table available for the live store, the running sums
        live as per-stripe flat device slabs (core/arena.py ArenaAccum)
        from the first fold on.  Residency is fixed at first fold — a
        state that already accumulated per-tensor stays per-tensor."""
        if self._arena is None or not self._arena.active:
            return
        if isinstance(state.accum, arena_mod.ArenaAccum):
            return
        if state.accum or state.counts:
            return
        with self._params_lock:
            store = self._params
        table = self._arena.ensure_table(store)
        if table is not None:
            state.accum = self._arena.new_accum(table)

    def _arena_fold(self, state: IterationState, folded: set,
                    gradients: Mapping[str, np.ndarray],
                    weight: int) -> int:
        """Fold into the arena accumulator: one scatter per (chunk,
        stripe, lane), index ranges precomputed from the packing table.
        Names the table cannot represent exactly (unknown, or the host
        fold's legal broadcast-up) take the pre-existing per-tensor
        ``_fold_one`` path into the accumulator's overflow dict — their
        presence downgrades the close to the per-tensor apply.  Returns
        bytes newly resident; marks folded names as their fold lands.
        Caller holds the lock covering the touched stripes (_state_lock
        on the serial path, the stripe lock on the striped path)."""
        accum: arena_mod.ArenaAccum = state.accum
        table = accum.table
        added = 0
        by_stripe: dict[int, list] = {}
        for name, g in gradients.items():
            if name in folded:
                continue
            if (table.compatible(name, g) and name not in accum.overflow
                    and name not in accum.popped):
                by_stripe.setdefault(table.entries[name].stripe,
                                     []).append((name, g))
            else:
                # a name the slab cannot take (unknown, the host fold's
                # legal broadcast-up, or already converged per-tensor):
                # its running sum must live in exactly ONE place, so a
                # slab-resident partial sum is EVICTED into overflow
                # first — otherwise the fallback close would divide by
                # a count covering contributions it cannot see
                accum.evict_to_overflow(name)
                added += _fold_one(accum.overflow, state.counts, name, g,
                                   weight)
                folded.add(name)
        for stripe in sorted(by_stripe):
            items = by_stripe[stripe]
            added += accum.fold_group(stripe, items, state.counts,
                                      weight)
            folded.update(name for name, _ in items)
        return added

    def _fold_into_locked(self, state: IterationState, folded: set,
                          gradients: Mapping[str, np.ndarray],
                          weight: int = 1) -> None:
        """The serial fold (caller holds _state_lock) — the exact
        pre-stripe code path, used at stripes == 1."""
        if isinstance(state.accum, arena_mod.ArenaAccum):
            added = self._arena_fold(state, folded, gradients, weight)
            if added:
                state.buffer_bytes += added
                self._grad_buffer_note(added)
            return
        added = 0
        try:
            for name, g in gradients.items():
                if name in folded:
                    continue
                # _fold_one raises (mutating nothing) on a shape
                # mismatch — only THEN is the name marked folded, so a
                # retry of a failed fold is not silently dropped
                added += _fold_one(state.accum, state.counts, name, g,
                                   weight, self._fold_buffers)
                folded.add(name)
        finally:
            if added:
                state.buffer_bytes += added
                self._grad_buffer_note(added)

    def _fold_striped(self, state: IterationState, worker_id: int,
                      iteration: int, todo: list) -> None:
        """Phases 2+3 of a striped fold: the numpy adds, grouped per
        stripe under per-stripe locks OUTSIDE ``_state_lock``, then the
        publication of what landed back under it.  The barrier close
        seals the state and drains ``state.inflight`` before taking the
        accumulator, so an in-flight add never races the close's scale;
        per-stripe accounting slots (one writer each) keep this function
        exception-safe without cross-thread counters."""
        groups: dict[int, list] = {}
        for name, g in todo:
            groups.setdefault(stripe_of(name, self._stripes),
                              []).append((name, g))
        work = sorted(groups.items())
        done_by: list[list[str]] = [[] for _ in work]
        added_by = [0] * len(work)

        def fold_group(idx: int, stripe: int, items: list) -> None:
            with self._stripe_locks[stripe]:
                if isinstance(state.accum, arena_mod.ArenaAccum):
                    # arena residence: one scatter per lane over the
                    # stripe's slab (the reservation already filtered
                    # duplicates, so a local folded set suffices)
                    local: set[str] = set()
                    added_by[idx] += self._arena_fold(
                        state, local, dict(items), 1)
                    done_by[idx].extend(local)
                    return
                for name, g in items:
                    # _fold_one raises (mutating nothing) on a shape
                    # mismatch — the name stays unpublished, so a retry
                    # of the failed fold is not silently dropped
                    added_by[idx] += _fold_one(state.accum, state.counts,
                                               name, g, 1,
                                               self._fold_buffers)
                    done_by[idx].append(name)

        try:
            thunks = [
                (lambda i=i, s=stripe, it=items: fold_group(i, s, it))
                for i, (stripe, items) in enumerate(work)]
            todo_view = dict(todo)
            if (device_apply.is_device_store(todo_view)
                    and not device_apply.stripe_dispatch(todo_view)):
                # large device tensors: dispatch the folds from THIS
                # thread — the adds data-parallelize inside the XLA
                # runtime, and executor fan-out only contends with the
                # intra-op pool (same policy as the device apply/scale)
                for thunk in thunks:
                    thunk()
            else:
                run_striped(thunks)
        finally:
            with self._state_lock:
                state.inflight -= 1
                folding = state.folding.get(worker_id)
                if folding is not None:
                    folding.difference_update(name for name, _ in todo)
                added = sum(added_by)
                if self._retired:
                    # a reshard RETIRE landed while these adds ran outside
                    # _state_lock: its purge could not see sums still in
                    # flight, so drop any retired name this fold just
                    # (re)published — otherwise a pre-fence reservation
                    # re-inserts a moved tensor's gradient, and on a shard
                    # the retire left empty the bootstrap rule would turn
                    # it into a parameter
                    for names in done_by:
                        for name in [n for n in names
                                     if n in self._retired]:
                            names.remove(name)
                            acc = state.accum.pop(name, None)
                            if acc is not None:
                                added -= acc.nbytes
                            state.counts.pop(name, None)
                # only names whose add actually landed become folded —
                # a failed name stays retryable, exactly like the serial
                # path's fold-then-mark ordering
                state.folded.setdefault(worker_id, set()).update(
                    name for names in done_by for name in names)
                # a restore() racing this fold may have orphaned `state`;
                # its buffer bytes then die with it — never re-note them
                # against the reset global gauge
                if added and self._iteration_states.get(iteration) is state:
                    state.buffer_bytes += added
                    self._grad_buffer_note(added)
                # wake a barrier closer draining inflight folds
                self._barrier_cv.notify_all()

    def _commit_group_push(self, worker_id: int, iteration: int,
                           gradients: Mapping[str, np.ndarray],
                           weight: int, members: tuple[int, ...]
                           ) -> PushResult:
        """Commit a leaf aggregator's STAGED group contribution (tiers/,
        ISSUE 9) in one ``_state_lock`` hold: overlap check, weighted
        fold, member cover, barrier evaluation — atomic, so a member's
        racing flat push (the mid-iteration downgrade recovery) lands
        strictly before it (the group is rejected and its members replay
        flat) or strictly after (the member dedups as a duplicate);
        there is no interleaving that double-counts a gradient.

        The fold increments each name's count by the GROUP SIZE — the
        close's per-name mean stays a true mean over workers — and the
        cover marks every member id a barrier contributor, so the
        barrier counts CONTRIBUTIONS (groups + singletons) whose member
        ids sum to the worker width and elastic membership composes
        unchanged.  Idempotent: a relay retry of a landed contribution
        answers duplicate/late exactly like a worker's."""
        ids = tuple(int(i) for i in members)
        total = self.barrier_width()
        if not ids:
            # unknown aggregate id (_contribution_for could not attribute
            # it even after a forced topology refresh — provider absent,
            # or the group not yet/no longer visible): bounce RETRYABLY.
            # The leaf's relay fails, its barrier stays retryable, and
            # either the next attempt finds the map fresh or its members
            # give up and replay flat.
            return PushResult(
                False,
                "unknown tier aggregate id: this PS cannot attribute the "
                "group contribution (topology not visible); retry or "
                "replay flat", iteration, False, 0, total)
        with self._state_lock:
            self._current_iteration = max(self._current_iteration, iteration)
            gradients, stale_epoch = self._split_retired_locked(gradients)
            if stale_epoch is not None:
                # reject whole (nothing folded): the leaf refreshes via
                # its members' repartition, same as a worker push
                return self._stale_map_result(iteration, stale_epoch, total)
            state = self._sync_state_locked(iteration)
            early = self._push_guard_locked(state, ids, iteration, total)
            if early is not None:
                return early
            if any(i in state.contributors or i in state.folded
                   or i in state.folding for i in ids):
                # the group sum overlaps a member that (also) landed
                # individually — folding it would double-count that
                # member's gradient.  Reject the WHOLE contribution; the
                # leaf's relay fails, its barrier stays retryable, and
                # the members replay flat, exactly once each.
                return PushResult(
                    False,
                    "tier group contribution overlaps individual "
                    "contributions; members must replay flat",
                    iteration, False, len(state.contributors), total)
            flight.record("fold.reserve", iteration=iteration,
                          worker=worker_id, a=len(gradients))
            if gradients:
                self._maybe_arena_accum_locked(state)
            self._fold_into_locked(
                state, state.folded.setdefault(worker_id, set()),
                gradients, weight)
            state.contributors.update(ids)
            flight.record("push.commit", iteration=iteration,
                          worker=worker_id, a=len(state.contributors),
                          b=total)
            received = self._maybe_aggregate_locked(iteration, state, total)
            if state.aggregated:
                return PushResult(True, "aggregation complete", iteration,
                                  True, received, total)
            return PushResult(True, "gradient received", iteration,
                              False, received, total)

    def _push_guard_locked(self, state: IterationState | None,
                           ids: tuple[int, ...], iteration: int,
                           total: int) -> PushResult | None:
        """Early verdict of a streaming commit against the iteration's
        barrier state — shared by the worker and group commit paths
        (caller holds _state_lock; None = proceed to contribute):

        - GC'd state: a straggler push for an already-aggregated
          iteration succeeds without contributing (the late-push
          invariant holds across GC);
        - aggregated: late push succeeds without contributing
          (reference: src/parameter_server.cpp:28-30);
        - sealed: a close was attempted (in flight or being retried)
          without this pusher; the apply has NOT landed yet, so do not
          report complete — readiness is observed via the sync poll /
          condition variable exactly when it is real;
        - all ids already contributed: the documented streaming
          duplicate policy, first-push-wins (a relay retry of a landed
          group contribution answers the same way)."""
        if state is None:
            return PushResult(True, "iteration already aggregated",
                              iteration, True, total, total)
        if state.aggregated:
            return PushResult(True, "iteration already aggregated",
                              iteration, True,
                              state.workers_at_aggregation, total)
        if state.sealed:
            return PushResult(True, "aggregation in progress", iteration,
                              False, len(state.contributors), total)
        if all(i in state.contributors for i in ids):
            return PushResult(True, "duplicate push ignored (streaming "
                                    "aggregation is first-push-wins)",
                              iteration, False,
                              len(state.contributors), total)
        return None

    def _commit_push(self, worker_id: int, iteration: int) -> PushResult:
        """End-of-stream for a streaming push: mark the worker a barrier
        contributor and fire the barrier if the width is reached."""
        total = self.barrier_width()
        with self._state_lock:
            self._current_iteration = max(self._current_iteration, iteration)
            state = self._sync_state_locked(iteration)
            early = self._push_guard_locked(state, (worker_id,), iteration,
                                            total)
            if early is not None:
                return early
            state.contributors.add(worker_id)
            # the (iteration, worker) commit stamp: the postmortem's
            # straggler attribution is the spread of these across workers,
            # and the LAST one is the event that closes the barrier
            flight.record("push.commit", iteration=iteration,
                          worker=worker_id, a=len(state.contributors),
                          b=total)
            received = self._maybe_aggregate_locked(iteration, state, total)
            if state.aggregated:
                return PushResult(True, "aggregation complete", iteration,
                                  True, received, total)
            return PushResult(True, "gradient received", iteration,
                              False, received, total)

    # -------------------------------------------------- buffered aggregation
    def _receive_sync(self, worker_id: int, iteration: int,
                      gradients: Mapping[str, np.ndarray]) -> PushResult:
        total = self.barrier_width()
        with self._state_lock:
            self._current_iteration = max(self._current_iteration, iteration)
            gradients, stale_epoch = self._split_retired_locked(gradients)
            if stale_epoch is not None:
                # buffered mode rejects the push whole (nothing buffered):
                # last-push-wins makes the post-repartition replay exact
                return self._stale_map_result(iteration, stale_epoch, total)
            state = self._sync_state_locked(iteration)
            if state is None:
                return PushResult(True, "iteration already aggregated",
                                  iteration, True, total, total)
            if state.aggregated:
                # late push: succeed without contributing
                # (reference: src/parameter_server.cpp:28-30)
                return PushResult(True, "iteration already aggregated", iteration,
                                  True, state.workers_at_aggregation, total)
            store = tree_like(gradients)
            prev = state.worker_gradients.get(worker_id)
            delta = store_nbytes(store) - (store_nbytes(prev) if prev else 0)
            state.worker_gradients[worker_id] = store
            state.buffer_bytes += delta
            self._grad_buffer_note(delta)
            flight.record("push.commit", iteration=iteration,
                          worker=worker_id,
                          a=len(state.worker_gradients), b=total)
            received = self._maybe_aggregate_locked(iteration, state, total)
            if state.aggregated:
                return PushResult(True, "aggregation complete", iteration,
                                  True, received, total)
            return PushResult(True, "gradient received", iteration,
                              False, received, total)

    # ---------------------------------------------------------- barrier close
    @property
    def quorum(self) -> float:
        """The armed quorum fraction (0.0 = off, all-of-N)."""
        return self._quorum

    def _quorum_on(self) -> bool:
        """Quorum applies only to the streaming synchronous barrier —
        the buffered escape hatch and async mode are untouched (the
        same scoping as the tier weighted folds)."""
        return self._quorum > 0 and self._streaming and self.synchronous

    def _quorum_ready_locked(self, state: IterationState, received: int,
                             total: int) -> bool:
        """True when the K-of-N close may fire NOW: the contributor
        count reached ``K = ceil(quorum * total)`` — pre-shrunk by the
        announced DRAINING count (elastic/quorum.py, ISSUE 14
        satellite) — and the grace window past the K-th commit elapsed.
        When every NON-draining member has committed, the grace is
        skipped outright: the only absentees are workers that announced
        they are leaving, and waiting a grace window for a commit that
        is not coming is exactly the cost the drain announcement exists
        to remove.  The check counts only commits from workers NOT in
        the draining set — a draining worker finishing its last
        in-flight iteration must not let the close cut off a healthy
        worker that was milliseconds behind (the grace window exists
        for exactly that worker).  Stamps/clears ``state.quorum_at`` as
        the count crosses the (possibly elastic) threshold; callers on
        the poll/CV cadence re-evaluate the grace.  Caller holds
        _state_lock."""
        draining_ids = self._live_draining_ids
        draining = len(draining_ids)
        k = equorum.threshold(self._quorum, total, draining)
        if received < k:
            state.quorum_at = None  # width grew past the old quorum
            return False
        now = time.monotonic()
        if state.quorum_at is None:
            state.quorum_at = now
        if draining > 0:
            healthy_received = received - len(state.contributors
                                              & draining_ids)
            if healthy_received >= total - draining:
                return True  # every still-staying member is in: the
                #              absent set is exactly (a subset of) the
                #              announced drains — no grace to pay
        return now - state.quorum_at >= self._quorum_grace_s

    def _maybe_aggregate_locked(self, iteration: int, state: IterationState,
                                total: int) -> int:
        """Fire the barrier if the contributor count has reached the current
        width — or, with the quorum armed (PSDT_QUORUM, ISSUE 13), the
        K-of-N threshold with its grace window elapsed.  Called from push
        AND from sync-status polls / CV waits so that an elastic barrier
        shrink (worker evicted mid-iteration) releases already-buffered
        iterations instead of stranding them, and so the quorum grace
        window is re-evaluated on the poll cadence without any push.
        Caller holds _state_lock.  Returns the contributor count."""
        if state.aggregated:
            return state.workers_at_aggregation
        received = (len(state.contributors) if self._streaming
                    else len(state.worker_gradients))
        if state.aggregating or received == 0:
            return received
        if received < total:
            if not (self._quorum_on()
                    and self._quorum_ready_locked(state, received, total)):
                return received
            # K-of-N close: seal over the contributors we have — the
            # mean stays a mean over contributors (per-name counts);
            # stragglers landing after this seal fold forward damped
            self._obs_quorum_closes.add()
            flight.record(
                "quorum.seal", iteration=iteration, a=received, b=total,
                note=",".join(str(w) for w in
                              sorted(state.contributors)[:12]))
        self._close_barrier_locked(iteration, state, received, total)
        return (state.workers_at_aggregation if state.aggregated
                else received)

    @_close_leg
    def _close_barrier_locked(self, iteration: int, state: IterationState,
                              received: int, total: int = 0) -> None:
        """Close the barrier.  Streaming mode: take the accumulator, flag
        the iteration "aggregating", RELEASE _state_lock for the O(model)
        scale-and-apply (serialized by _apply_lock), then reacquire to
        publish completion — pushes for other iterations and sync polls
        run concurrently with the apply.  Buffered mode applies inline
        under _state_lock (the escape hatch preserves the original
        semantics and timing exactly).  Caller holds _state_lock; it is
        held again on return."""
        # remember whether THIS close is the bootstrap (store empty →
        # the aggregated payload becomes the parameters): a straggler's
        # late replay of the seed push must then be a plain late-push
        # no-op, never a forward stale fold — its payload is
        # init-magnitude VALUES, not a gradient (see _stale_fold_locked)
        if self._streaming and self._quorum_on() \
                and self._bootstrap_iteration is None:
            with self._params_lock:
                if not self._params:
                    # stamped AT SEAL, not after publish: the straggler's
                    # seed replay typically lands exactly while the
                    # bootstrap close runs outside _state_lock, and the
                    # _stale_fold_locked guard must already see it
                    self._bootstrap_iteration = iteration
        state.sealed = True  # contributor set frozen, even across retries
        state.aggregating = True  # set BEFORE the drain below: the wait
        # releases _state_lock, and a concurrent poll re-entering
        # _maybe_aggregate_locked must see the close already in flight
        flight.record("barrier.seal", iteration=iteration, a=received,
                      b=total)
        inflight_at_seal = state.inflight
        try:
            if self._streaming:
                while state.inflight:
                    # striped folds reserved BEFORE the seal are still
                    # running their numpy adds outside _state_lock; their
                    # sums belong to this aggregate — drain them before
                    # taking the accumulator (their publish step lands
                    # while the cv wait has the lock released and
                    # notifies here)
                    self._barrier_cv.wait(0.05)
                flight.record("barrier.drain", iteration=iteration,
                              a=inflight_at_seal)
                if not self._close_streaming_locked(state, iteration):
                    # a checkpoint restore landed inside the close window:
                    # the aggregate belongs to the pre-restore world —
                    # drop it and leave the (already-cleared) state
                    # unpublished
                    state.aggregating = False
                    return
            else:
                ta = time.perf_counter()
                flight.record("apply.start", iteration=iteration)
                if not self._apply_fused_mean_sgd(state.worker_gradients):
                    mean = _mean_over_workers(state.worker_gradients)
                    self._apply_update(mean)
                flight.record("apply.end", iteration=iteration,
                              a=int(1e6 * (time.perf_counter() - ta)))
                state.worker_gradients.clear()  # free memory promptly
                self._grad_buffer_note(-state.buffer_bytes)
                state.buffer_bytes = 0
        except BaseException:
            # a failed apply must leave the barrier RETRYABLE, as the old
            # inline close did: the phase flag comes back down (buffered
            # gradients / the restored accumulator are still in place) and
            # the next push or sync poll re-fires the aggregation
            state.aggregating = False
            flight.record("barrier.retry", iteration=iteration, a=received)
            raise
        state.aggregating = False
        state.aggregated = True
        state.workers_at_aggregation = received
        self._aggregated_watermark = max(self._aggregated_watermark, iteration)
        flight.record("barrier.publish", iteration=iteration, a=received,
                      b=total)
        self._barrier_cv.notify_all()  # wake fused-RPC barrier waiters

    def _close_streaming_locked(self, state: IterationState,
                                iteration: int = -1) -> bool:
        """The streaming half of the barrier close: take the accumulator,
        run the O(model) scale-and-apply outside _state_lock (serialized
        by _apply_lock), reacquire.  Returns False when a concurrent
        checkpoint restore obsoleted the aggregate.  On an apply failure
        the accumulator is PUT BACK (already-scaled sums are means, so
        their counts reset to 1) and the exception propagates — the next
        push/poll retries the close instead of wedging the iteration.
        Once the sweep has read a host accumulator its buffers go back
        for the next iteration's seeds (core/fold_buffers.py); sums that
        were put back keep theirs."""
        gen = self._restore_epoch
        sums, counts = state.accum, state.counts
        state.accum, state.counts = {}, {}
        state.folded.clear()
        freed = state.buffer_bytes
        self._grad_buffer_note(-freed)
        state.buffer_bytes = 0
        scaled = False
        try:
            self._state_lock.release()
            try:
                with self._apply_lock:
                    if self._restore_epoch == gen:
                        ta = time.perf_counter()
                        flight.record("apply.start", iteration=iteration)
                        if self._barrier_relay is not None:
                            # leaf-aggregator close (tiers/leaf.py): the
                            # raw per-name SUMS go upstream as ONE
                            # quantized group contribution and the fused
                            # response becomes this core's store — the
                            # params its parked group gets served.  A
                            # raise takes the ordinary failed-apply path
                            # below: sums put back unscaled (counts
                            # intact — the relay must not mutate them),
                            # barrier retryable, relay retry idempotent
                            # upstream via the PS's per-(worker, tensor)
                            # dedup and member cover.
                            if isinstance(sums, arena_mod.ArenaAccum):
                                # arena-resident leaf sums: one readback
                                # per stripe, then writable per-name
                                # host copies (same relay contract as
                                # the per-tensor device branch below)
                                sums = sums.to_host_dict()
                            elif device_apply.is_device_store(sums):
                                # leaf with device member folds (PR-9
                                # intra-host tier): start every D2H,
                                # then materialize HOST sums for the
                                # relay — the EF residual math and the
                                # native quantize kernels are numpy, and
                                # the device adds that built these sums
                                # are correctly rounded, so the bytes
                                # match a numpy-folded leaf exactly.
                                # (A relay raise puts back the HOST
                                # sums; later member folds re-seed the
                                # device residence on the next fold.
                                # np.array, not np.asarray: asarray of
                                # a jax CPU array is a READ-ONLY view,
                                # and a put-back accumulator must stay
                                # foldable in place for replayed member
                                # pushes.)
                                device_apply.readback_async(sums)
                                sums = {name: np.array(
                                            np.asarray(v), np.float32)
                                        for name, v in sums.items()}
                            fresh = self._barrier_relay(iteration, sums,
                                                        counts)
                            with self._params_lock:
                                self._params = dict(fresh)
                                self._params_version += 1
                                _dstore = self._params
                                _dver = self._params_version
                            self._notify_delta(_dstore, _dver)
                        else:
                            if isinstance(sums, arena_mod.ArenaAccum):
                                # flat arena close (ISSUE 15): anything
                                # the flat layout cannot represent
                                # exactly converts to the per-tensor
                                # path for THIS close (counter + flight
                                # code), never fails
                                reason = self._arena_fallback_reason(
                                    sums, counts)
                                if reason is not None:
                                    self._arena.fallback(reason,
                                                         iteration)
                                    sums = sums.to_tensor_dict()
                            if isinstance(sums, arena_mod.ArenaAccum):
                                # contributor-mean scale as ONE kernel
                                # per stripe (counts proven uniform —
                                # the same f32 scalar as the per-tensor
                                # scale), then the fused flat apply
                                sums.scale_uniform(
                                    next(iter(counts.values())))
                                scaled = True
                                self._apply_arena_sync(sums, iteration)
                            else:
                                # contributor mean without a per-worker
                                # sweep: one in-place O(model) scale of
                                # the running sums (per-name counts —
                                # see IterationState.counts), stripe-
                                # parallel; a FULL scale pass completes
                                # before the apply so the put-back
                                # semantics on an apply failure stay
                                # exact (counts reset to 1)
                                self._scale_striped(sums, counts)
                                scaled = True
                                self._apply_update(sums)
                        flight.record(
                            "apply.end", iteration=iteration,
                            a=int(1e6 * (time.perf_counter() - ta)))
                        if self._on_apply is not None:
                            # replication hook, still under _apply_lock
                            # (BLOCKING_ALLOWED): sync mode ships the
                            # post-apply state to the backup BEFORE the
                            # barrier publishes, so a primary death after
                            # this point can never lose an applied
                            # iteration (replication/replicator.py)
                            self._on_apply()
            finally:
                # _apply_lock is released BEFORE reacquiring _state_lock
                # (lock-order: never hold _apply_lock while taking
                # _state_lock)
                self._state_lock.acquire()
        except BaseException:
            if self._restore_epoch == gen:
                state.accum = sums
                state.counts = (dict.fromkeys(sums, 1) if scaled
                                else counts)
                state.buffer_bytes = freed
                self._grad_buffer_note(freed)
            raise
        if isinstance(sums, dict):
            # the sweep has read the sums: the next iteration seeds over
            # their buffers, unless somebody kept one (fold_buffers.py)
            self._fold_buffers.give_back(sums)
        return self._restore_epoch == gen

    def _receive_async(self, worker_id: int, iteration: int,
                       gradients: Mapping[str, np.ndarray]) -> PushResult:
        """Bounded-staleness apply-on-arrival (extension; no reference
        analogue — the reference protocol is strictly synchronous)."""
        with self._state_lock:
            gradients, stale_epoch = self._split_retired_locked(gradients)
            if stale_epoch is not None:
                return self._stale_map_result(iteration, stale_epoch,
                                              self._static_total_workers)
            with self._params_lock:
                params_empty = not self._params
            if params_empty:
                # bootstrap: the pushed payload becomes the parameters
                self._apply_update(tree_like(gradients))
                self._bootstrap_iteration = iteration
                self._current_iteration = max(self._current_iteration, iteration)
                return PushResult(True, "bootstrap applied",
                                  self._current_iteration, True, 1,
                                  self.barrier_width())
            if (self._bootstrap_iteration is not None
                    and iteration <= self._bootstrap_iteration):
                # another worker raced the same bootstrap init push: without
                # the sync barrier to dedup it, applying it as a gradient
                # would compute params - lr*init (zero at the reference's
                # lr=1.0).  Drop it; the worker re-pulls real params next.
                return PushResult(True, "bootstrap duplicate ignored",
                                  self._current_iteration, True, 0,
                                  self.barrier_width())
            staleness = self._current_iteration - iteration
            if staleness > self._staleness_bound:
                return PushResult(False,
                                  f"stale push: worker iteration {iteration} is "
                                  f"{staleness} behind bound {self._staleness_bound}",
                                  self._current_iteration, False, 0,
                                  self.barrier_width())
            if self._async_damping is not None and staleness > 0:
                # staleness-aware lr damping (async_sgd/damping.py,
                # ISSUE 13): an accepted stale push applies at
                # lr * beta^staleness — armed only by an explicit
                # PSDT_STALENESS_BETA, so default async runs are
                # byte-identical
                gradients = self._async_damping.damp(gradients, staleness)
            self._apply_update(tree_like(gradients))
            self._applied_updates += 1
            # current_iteration stays the monotone max of worker iterations
            # seen (matching the sync path); the applied-update count is the
            # PS "version" and is tracked separately.
            self._current_iteration = max(self._current_iteration, iteration)
            return PushResult(True, "update applied", self._current_iteration,
                              True, 1, self.barrier_width())

    @property
    def applied_updates(self) -> int:
        """Async mode: number of updates applied (the PS version counter)."""
        return self._applied_updates

    def _apply_fused_mean_sgd(self, worker_gradients: Mapping[int, TensorStore]) -> bool:
        """Single-sweep native mean+SGD barrier apply (psdt_mean_sgd —
        native/psdt_native.cpp): `param -= lr * mean(worker grads)` without
        materializing the mean, mirroring the reference's fused C++
        aggregation loop (src/parameter_server.cpp:40-91).  Returns False —
        requesting the generic mean-then-optimizer path — for non-SGD
        optimizers, an uninitialized store (bootstrap needs the mean itself),
        or when the native library is unavailable.  Buffered mode only; the
        streaming path's accumulator makes the close O(model) without it.
        Caller holds _state_lock."""
        from ..native import lib, mean_sgd_native

        if type(self._optimizer) is not SGD or lib() is None:
            return False
        by_name: dict[str, list[np.ndarray]] = {}
        for grads in worker_gradients.values():
            for name, g in grads.items():
                by_name.setdefault(name, []).append(
                    np.ascontiguousarray(g, np.float32))
        lr = float(self._optimizer.learning_rate)
        with self._params_lock:
            if not self._params:
                return False
            new_params: TensorStore = {}
            for name, p in self._params.items():
                arrays = by_name.get(name)
                if not arrays:
                    new_params[name] = np.asarray(p, np.float32)
                    continue
                p_new = np.array(p, np.float32)  # fresh contiguous copy
                if not mean_sgd_native(p_new, arrays, lr):
                    acc = arrays[0].copy()
                    for g in arrays[1:]:
                        acc += g
                    p_new = p_new - np.float32(lr / len(arrays)) * acc
                new_params[name] = p_new
            self._params = new_params
            self._params_version += 1
            version = self._params_version
        # still under _state_lock (buffered path), outside _params_lock
        self._notify_delta(new_params, version)
        return True

    def _scale_striped(self, sums: TensorStore,
                       counts: dict[str, int]) -> None:
        """In-place sums -> means (the per-element op is unchanged, so the
        result is bit-for-bit the serial loop's).  Host sums are cut by
        element ranges like the optimizer sweep after them
        (:meth:`_sweep_by_range`); device sums keep the grouping by name.
        Caller holds _apply_lock."""
        def scale_one(name: str) -> None:
            acc = sums[name]
            if isinstance(acc, np.ndarray):
                acc *= np.float32(1.0 / counts[name])
            else:
                # device accumulator (jax arrays are immutable): the
                # scaled array rebinds; scale_mean donates the sum
                # buffer and uses the SAME f32 scalar as the numpy path
                sums[name] = device_apply.scale_mean(acc, counts[name])

        on_device = device_apply.is_device_store(sums)
        if (self._stripes <= 1 or not sums or (on_device and (
                len(sums) <= 1 or not device_apply.stripe_dispatch(sums)))):
            # large device sums scale from ONE dispatcher for the same
            # reason the device apply does (see _apply_update): big
            # kernels parallelize inside XLA, and stripe-thread
            # dispatch only contends
            for name in sums:
                scale_one(name)
            return

        if on_device:
            def scale_group(names: list[str]) -> None:
                for name in names:
                    scale_one(name)

            run_striped([(lambda ns=ns: scale_group(ns))
                         for ns in partition_names(sums, self._stripes)])
            return

        # one contributor: x * 1.0f is x, and the pass would read and
        # write the whole store to say so
        flat = []
        for name, acc in sums.items():
            if counts[name] == 1:
                continue
            if acc.flags.c_contiguous:
                flat.append((acc.reshape(-1),
                             np.float32(1.0 / counts[name])))
            else:
                scale_one(name)     # reshape would copy: scale it whole

        def scale_ranges(pieces: list) -> None:
            for i, lo, hi in pieces:
                acc, inv = flat[i]
                np.multiply(acc[lo:hi], inv, out=acc[lo:hi])

        run_striped([(lambda ps=ps: scale_ranges(ps)) for ps in
                     partition_ranges([acc.size for acc, _ in flat],
                                      self._stripes)])

    # ------------------------------------------------------ arena close
    def _arena_fallback_reason(self, sums: "arena_mod.ArenaAccum",
                               counts: dict[str, int]) -> str | None:
        """None when the flat close may run; otherwise the reason the
        per-tensor path must take this close (core/arena.py downgrade
        matrix).  Caller holds _apply_lock, so the store and table are
        stable for the rest of the close."""
        if self._arena is None or not self._arena.active:
            return "disabled"
        table = sums.table
        with self._params_lock:
            store = self._params
        live = self._arena.ensure_table(store)
        if live is None or live.epoch != table.epoch:
            # the store's shape moved under the open accumulator (the
            # epoch fence) — or the table build latched off
            return "epoch"
        if not sums.full_coverage():
            # pass-through names, retired (popped) names, or overflow
            # folds the table could not represent
            return "coverage"
        values = iter(counts.values())
        first = next(values, None)
        if first is None or any(c != first for c in values):
            # non-uniform per-name contributor counts (quorum straggler
            # folds, sharded disjoint-subset pushes): the flat scale is
            # one scalar per stripe, so these keep the per-name path
            return "counts"
        ready = getattr(self._optimizer, "arena_ready", None)
        if ready is None or not ready(table):
            return "slots"  # mixed momentum seeding (reshard merges)
        return None

    def _apply_arena_sync(self, sums: "arena_mod.ArenaAccum",
                          iteration: int) -> None:
        """The flat barrier close (ISSUE 15; caller holds _apply_lock,
        ``sums`` already scaled to contributor means): every optimizer
        stage runs as ONE fused kernel per stripe over the flat slabs,
        the D2H readback is ONE contiguous transfer per stripe, and the
        published store is an ArenaStore of zero-copy numpy views the
        serve encode / delta build / checkpoint slice by table offset.
        A packing failure latches the arena off and completes THIS close
        on the per-tensor path — the close never fails for arena
        reasons (optimizer-stage exceptions keep the ordinary put-back/
        retry contract)."""
        t0 = time.perf_counter()
        table = sums.table
        with self._params_lock:
            prev = self._params
        try:
            param_slabs = self._arena.ensure_param_slabs(prev, table,
                                                         iteration)
        except Exception as exc:  # noqa: BLE001 — packing must never
            # fail a close; the per-tensor device path is always correct
            self._arena.latch_off(f"{type(exc).__name__}: {exc}")
            self._apply_update(sums.to_tensor_dict())
            return
        opt = self._optimizer
        opt.tick()
        td = time.perf_counter()
        sharded = None
        if self._sharded_updater is not None:
            # cross-replica sharded close: each replica applies only its
            # owned stripe slices and the fresh slabs all-gather back.
            # try_close never raises; None means this close runs local
            # (no in-sync peers, a mid-exchange death, a refusal) — the
            # slot slabs and sums are untouched on that path, so the
            # local apply below is bit-identical to an unsharded close.
            sharded = self._sharded_updater.try_close(
                prev, table, param_slabs, sums, iteration)
        if sharded is not None:
            new_slabs, host_slabs = sharded
            dispatch_us = int(1e6 * (time.perf_counter() - td))
            readback_us = 0
        else:
            new_slabs = opt.apply_arena(table, param_slabs, sums.slabs)
            dispatch_us = int(1e6 * (time.perf_counter() - td))
            # ONE contiguous D2H per stripe: start every transfer, then
            # materialize the host slabs the per-tensor views slice
            tr = time.perf_counter()
            device_apply.readback_async(new_slabs)
            host_slabs = {s: np.asarray(a) for s, a in new_slabs.items()}
            readback_us = int(1e6 * (time.perf_counter() - tr))
        per_stripe = {s: table.views(s, h) for s, h in host_slabs.items()}
        views: TensorStore = {}
        for name in prev:
            # the store's key order is preserved, so serve chunking and
            # wire bytes are identical to the per-tensor path's
            views[name] = per_stripe[table.entries[name].stripe][name]
        store = arena_mod.ArenaStore(views, table, host_slabs)
        with self._params_lock:
            if self._params is not prev:
                # initialize_parameters() landed during the close: the
                # newer store wins (the _apply_striped_sync rule)
                return
            self._params = store
            self._params_version += 1
            version = self._params_version
        self._arena.adopt(store, new_slabs)
        self._arena.note_close()
        self._obs_device_applies.add()
        flight.record("apply.arena", iteration=iteration, a=dispatch_us,
                      b=readback_us)
        flight.record("apply.device",
                      a=int(1e6 * (time.perf_counter() - t0)),
                      b=self._stripes)
        self._notify_delta(store, version)

    def _apply_striped_sync(self, prev: TensorStore,
                            mean_grads: TensorStore) -> None:
        """Stripe-parallel synchronous apply: tick the optimizer once,
        then fan the step across the shared executor — by element ranges
        where the store and the means are host arrays, by name where
        they live on a device; the new store is swapped in under
        _params_lock.  The caller serializes applies (_apply_lock
        on the streaming close, _state_lock on the buffered path), so the
        optimizer never sees two concurrent logical steps.  Serves during
        the compute read the previous store at its previous version —
        safe, because the barrier is not published until the close
        returns, so no client can mistake the pre-apply store for the
        post-barrier one."""
        opt = self._optimizer
        opt.tick()
        by_range = not (device_apply.wants_device_fold(opt)
                        or device_apply.is_device_store(prev)
                        or device_apply.is_device_store(mean_grads))
        t0 = time.perf_counter()
        new_params, task_s = (self._sweep_by_range if by_range
                              else self._sweep_by_name)(prev, mean_grads)
        wall = time.perf_counter() - t0
        for dt in task_s:
            self._obs_stripe_ms.observe(1e3 * dt)
        if wall > 0:
            self._obs_parallelism.set(round(sum(task_s) / wall, 2))
        with self._params_lock:
            if self._params is not prev:
                # initialize_parameters() landed during the striped
                # compute (it takes only _params_lock; restore() is
                # fenced separately via _restore_epoch).  The serial
                # path's outcome for that interleaving is "apply, then
                # the initialize wins" — keep the newer store rather
                # than clobbering it with params derived from the
                # pre-initialize world.
                return
            self._params = new_params
            self._params_version += 1
            version = self._params_version
        if by_range:
            self._close_buffers.publish()
        # readback first, then the delta build, both after the swap and
        # outside _params_lock (the caller's _apply_lock/_state_lock
        # still serializes applies) — the sink's encode then overlaps
        # the D2H copies already in flight
        self._note_device_apply(new_params, t0)
        self._notify_delta(new_params, version)

    def _sweep_by_name(self, prev: TensorStore, mean_grads: TensorStore
                       ) -> tuple[TensorStore, list[float]]:
        """One optimizer step over a store on a device, ``apply_shard``
        per stripe of NAMES (a jax array is not sliced in place): each
        stripe updates its own optimizer-state slice and emits fresh
        param arrays for its names.  Returns the new store and each
        task's seconds."""
        opt = self._optimizer
        name_groups = partition_names(prev, self._stripes)
        task_s = [0.0] * len(name_groups)

        def apply_group(idx: int, names: list[str]) -> TensorStore:
            t1 = time.perf_counter()
            res = opt.apply_shard(
                {n: prev[n] for n in names},
                {n: mean_grads[n] for n in names if n in mean_grads})
            task_s[idx] = time.perf_counter() - t1
            return res

        parts = run_striped([(lambda i=i, ns=ns: apply_group(i, ns))
                             for i, ns in enumerate(name_groups)])
        by_name: TensorStore = {}
        for part in parts:
            by_name.update(part)
        return {name: by_name[name] for name in prev}, task_s  # stable order

    def _sweep_by_range(self, prev: TensorStore, mean_grads: TensorStore
                        ) -> tuple[TensorStore, list[float]]:
        """One optimizer step over a host store, cut by ELEMENT RANGES:
        the tensors that have a gradient, laid end to end, are cut into
        as many nearly equal ranges as there are stripes, one task each;
        a large tensor is split across tasks, small ones ride together.
        Every host rule is elementwise (``HostOptimizer.update_range``),
        so the cut changes no bit of the result, and how wide the sweep
        runs no longer depends on what the tensors are called (a cut by
        name cannot run wider than total / largest stripe: 2.7 for a
        scanned transformer, whose two MLP matrices are half the store).

        Out of place: each task reads the served arrays and writes the
        ranges of the new ones, which lie in the buffers of the store
        retired two closes ago when nobody reads them any more
        (core/close_buffers.py).  Returns the new store and each task's
        seconds."""
        opt = self._optimizer
        new_params, todo = split_updates(prev, mean_grads)
        opt.prepare({name: g for name, _, g in todo})
        new_params.update(self._close_buffers.take(
            {name: p.shape for name, p, _ in todo}))
        ranges = partition_ranges([p.size for _, p, _ in todo],
                                  self._stripes)
        task_s = [0.0] * len(ranges)

        def sweep(idx: int, pieces: list) -> None:
            t1 = time.perf_counter()
            for i, lo, hi in pieces:
                name, p, g = todo[i]
                opt.update_range(name, p, g, new_params[name], lo, hi)
            task_s[idx] = time.perf_counter() - t1

        run_striped([(lambda i=i, ps=ps: sweep(i, ps))
                     for i, ps in enumerate(ranges)])
        return new_params, task_s

    def _apply_update(self, mean_grads: TensorStore) -> None:
        """Applies are serialized by the caller: _state_lock on the
        async/buffered paths, _apply_lock on the streaming barrier close.
        Only _params_lock is taken here, and only briefly — in async mode
        the depth-bound fence on the previous in-flight apply happens
        OUTSIDE it, so concurrent serves keep reading the materialized
        snapshot instead of queueing behind device compute; the striped
        sync apply likewise computes outside it and swaps."""
        t0 = time.perf_counter()
        with self._params_lock:
            if not self._params:
                # bootstrap quirk preserved from the reference (cpp:78-81)
                self._params = dict(mean_grads)
                self._params_version += 1
                store, version = self._params, self._params_version
                boot = True
            else:
                prev = self._params
                boot = False
        if boot:
            self._note_device_apply(store, t0)
            self._notify_delta(store, version)
            return
        if not self.synchronous:
            # Depth bound: at most ONE apply in flight — if the previous
            # apply hasn't materialized yet, fence on it now so push
            # latency absorbs the pipeline backpressure instead of the XLA
            # queue growing without bound under a push rate faster than
            # the apply rate.
            if not _store_ready(prev):
                _block_on_store(prev)
            new_params = self._optimizer.apply(prev, mean_grads)
            with self._params_lock:
                self._serving = prev  # materialized: serve this while the
                self._serving_version = self._params_version
                self._params = new_params  # new apply is in flight
                self._params_version += 1
            self._note_device_apply(new_params, t0)
        elif (self._stripes > 1
              and getattr(self._optimizer, "supports_striping", False)
              and (not device_apply.wants_device_fold(self._optimizer)
                   or (device_apply.stripe_dispatch(mean_grads)
                       and len(mean_grads) > 1))):
            # Host optimizers always fan the apply across stripe
            # threads (real multi-core sweeps, cut by element ranges
            # whatever the tensors are called and however many there
            # are: one huge tensor runs as wide as a thousand small
            # ones).  A device-resident optimizer is cut by name, so
            # it needs two names, and fans out only while tensors are
            # SMALL (dispatch-bound regime); past device_apply's mean-size
            # bound its kernels data-parallelize inside the XLA runtime
            # and a second dispatcher only contends with the intra-op
            # pool, so the close dispatches from one thread (the serial
            # branch below — stripes still partition fold ingress and
            # the store either way).
            self._apply_striped_sync(prev, mean_grads)
        else:
            # serial / device-optimizer sync apply: under _params_lock,
            # exactly the pre-stripe behavior (see analysis/baseline.json)
            with self._params_lock:
                self._params = self._optimizer.apply(self._params,
                                                     mean_grads)
                self._params_version += 1
                store, version = self._params, self._params_version
            # readback + delta build outside _params_lock, still inside
            # the caller's serialized apply section
            self._note_device_apply(store, t0)
            if _store_ready(store):
                self._notify_delta(store, version)

    # ------------------------------------------------------------------- sync
    def check_sync_status(self, iteration: int) -> tuple[int, bool, int, int]:
        """Returns (iteration, ready, workers_received, total_workers)
        (reference: src/parameter_server.cpp:99-110)."""
        total = self.barrier_width()
        if self._freerun is not None or not self.synchronous:
            # free-run: no per-iteration barrier state exists — a poll
            # must never create one (the async-mode convention)
            return iteration, True, 1, total
        with self._state_lock:
            state = self._iteration_states.get(iteration)
            if state is None:
                if iteration <= self._aggregated_watermark:
                    # aggregated long ago, state GC'd
                    return iteration, True, total, total
                return iteration, False, 0, total
            # Re-evaluate the barrier here too: if the width shrank (worker
            # evicted mid-iteration) a fully-buffered iteration must fire on
            # the next poll rather than strand the surviving workers.
            received = self._maybe_aggregate_locked(iteration, state, total)
            if state.aggregated:
                return iteration, True, state.workers_at_aggregation, total
            return iteration, False, received, total

    def wait_for_aggregation(self, iteration: int,
                             timeout: float) -> tuple[bool, int, int]:
        """Block until ``iteration``'s aggregation completes (or timeout).
        Returns (ready, workers_received, total_workers).

        This is the serve-when-complete primitive of the fused data plane:
        instead of N workers polling CheckSyncStatus at 20 Hz, their
        PushPullStream handlers park on a condition variable and are
        notified the instant the barrier closes.  The wait wakes at a
        bounded cadence regardless, re-reading the (possibly elastic)
        barrier width so a mid-iteration shrink releases a fully-buffered
        iteration exactly as the polled path does."""
        if self._freerun is not None or not self.synchronous:
            # free-run never barriers: every push already applied
            return True, 1, self.barrier_width()
        deadline = time.monotonic() + timeout
        while True:
            # barrier_width() may hit a remote live-worker provider; keep
            # it outside the lock like every other caller
            total = self.barrier_width()
            with self._barrier_cv:
                state = self._iteration_states.get(iteration)
                if state is None:
                    if iteration <= self._aggregated_watermark:
                        return True, total, total
                    received = 0
                else:
                    received = self._maybe_aggregate_locked(iteration, state,
                                                            total)
                    if state.aggregated:
                        return True, state.workers_at_aggregation, total
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False, received, total
                # 250 ms cap: elastic width changes have no notification
                # of their own, so re-evaluate on a short heartbeat.
                # With a quorum grace window running (ISSUE 13) the wake
                # tightens to its expiry, so a K-of-N close fires within
                # grace instead of a heartbeat later.
                cap = 0.25
                if (state is not None and state.quorum_at is not None
                        and not state.sealed):
                    cap = min(cap, max(
                        0.005,
                        state.quorum_at + self._quorum_grace_s
                        - time.monotonic()) + 0.002)
                self._barrier_cv.wait(min(remaining, cap))

    # --------------------------------------------------------------------- gc
    def _gc_locked(self) -> None:
        excess = len(self._iteration_states) - self._gc_iterations
        if excess <= 0:
            return
        for iteration in list(self._iteration_states):
            if excess <= 0:
                break
            old = self._iteration_states[iteration]
            if old.sealed and not old.aggregated:
                # mid-close (apply in flight outside _state_lock, or a
                # failed apply awaiting its retry): evicting now would let
                # a replayed push recreate the state and fire a SECOND
                # aggregation for the same iteration before the watermark
                # publishes.  Skip; it becomes collectable once published.
                continue
            del self._iteration_states[iteration]
            excess -= 1
            if old.buffer_bytes:
                self._grad_buffer_note(-old.buffer_bytes)
                old.buffer_bytes = 0

    @property
    def tracked_iterations(self) -> int:
        with self._state_lock:
            return len(self._iteration_states)

    # ------------------------------------------------------------- checkpoint
    def snapshot(self) -> tuple[int, int, TensorStore]:
        """Consistent (epoch, current_iteration, params) snapshot.  Takes
        _state_lock, then _apply_lock (so a streaming barrier apply in
        flight completes first), then _params_lock, so a concurrent push
        cannot produce a torn view (iteration bumped but its update not
        yet applied)."""
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    return (self._epoch, self._current_iteration,
                            dict(self._params))

    def optimizer_state(self) -> dict:
        """Optimizer slot state (Momentum velocity / Adam moments), for
        checkpointing alongside :meth:`snapshot`."""
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    return self._optimizer.state_dict()

    def restore(self, epoch: int, iteration: int,
                params: Mapping[str, np.ndarray],
                optimizer_state: dict | None = None,
                params_version: int | None = None) -> None:
        """``params_version`` (checkpoint meta sidecar) is the version
        counter AT SAVE TIME: the restored store resumes numbering past
        both it and anything this process served since — a previously-
        served version id must never be reused for different values,
        because a versioned-delta receiver would silently patch against
        the wrong base (ISSUE 10; within one process ``_params_version``
        only ever increments, so the max ever served is bounded by it)."""
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    self._params = tree_like(params)
                    self._params_version = max(
                        self._params_version,
                        int(params_version or 0)) + 1
                    if optimizer_state is not None:
                        self._optimizer.load_state_dict(optimizer_state)
                # bumped while _apply_lock is held: an in-flight streaming
                # barrier close observes it either before its apply (and
                # skips) or after (and drops its publication) — see
                # _close_barrier_locked
                self._restore_epoch += 1
            self._epoch = int(epoch)
            self._current_iteration = int(iteration)
            self._iteration_states.clear()
            self._grad_buffer_bytes = 0
            self._aggregated_watermark = -1
            self._bootstrap_iteration = None
            flight.record("ckpt.restore", iteration=int(iteration),
                          a=int(epoch))
        # the restored store is a new world: stale delta pairs must not
        # patch receivers toward it (outside the core locks — reset is
        # cheap but the sink has its own lock), and the arena's adopted
        # param slabs no longer describe the live store
        self._reset_delta()
        if self._arena is not None:
            self._arena.invalidate()

    # ------------------------------------------------------------ replication
    def set_replication_hook(self, hook: Callable[[], None] | None) -> None:
        """Install the post-apply replication hook (replication/
        Replicator.on_apply).  Invoked by the streaming barrier close
        right after the optimizer apply with _apply_lock held — applies
        stay serialized, so the hook reads a consistent store, and sync
        replication may block there (the lock is BLOCKING_ALLOWED).  The
        hook MUST NOT raise: a raise would put the accumulator back and
        retry the close (the failed-apply path).  Buffered/async
        aggregation modes never invoke it — the replicator's reconcile
        loop covers them on its poll cadence."""
        self._on_apply = hook

    def set_sharded_updater(self, updater) -> None:
        """Install (or clear) the cross-replica sharded-update driver
        (replication/sharded_update.ShardedUpdater).  Its ``try_close``
        is offered every arena close from under _apply_lock; it must
        never raise (return None to decline — the close then runs the
        ordinary local apply against untouched slots and sums)."""
        self._sharded_updater = updater

    def install_sharded_close(self, store, *, epoch: int,
                              iteration: int) -> int:
        """Adopt one cross-replica sharded close on a BACKUP: ``store``
        is the primary's next version, assembled from this replica's own
        freshly-applied slices plus the gathered ones
        (replication/sharded_update.ShardedUpdateSink).

        Unlike :meth:`install_tensors` this is an IN-TIMELINE advance —
        the replica co-computed the same optimizer step the primary is
        publishing — so the restore fence does NOT bump (an in-flight
        local close on a promoted replica is a different, refused world)
        and the arena manager is left alone (the sink owns the backup's
        slab cache; the optimizer slot slabs were advanced by the sink's
        range commits).  Iteration bookkeeping matches a replication
        replace: the aggregated watermark advances and superseded
        iteration states drop, so failover retries of an applied
        iteration stay idempotent."""
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    self._params = store
                    self._params_version += 1
                    version = self._params_version
            self._epoch = int(epoch)
            it = int(iteration)
            self._current_iteration = max(self._current_iteration, it)
            self._aggregated_watermark = max(self._aggregated_watermark,
                                             it)
            for stale_it in [i for i in self._iteration_states
                             if i <= self._aggregated_watermark]:
                old = self._iteration_states.pop(stale_it)
                if old.buffer_bytes:
                    self._grad_buffer_note(-old.buffer_bytes)
                    old.buffer_bytes = 0
            self._serving = None
            flight.record("shard.install", iteration=it,
                          a=store_nbytes(store), b=version)
            self._barrier_cv.notify_all()
        # stale delta pairs must not patch receivers across a version
        # they did not watch being built (restore() discipline)
        self._reset_delta()
        return version

    def replica_snapshot(self, in_close: bool = False
                         ) -> tuple[int, int, int, TensorStore, dict]:
        """Consistent (epoch, iteration, params_version, params copy,
        optimizer state) for a replication ship.  ``in_close=True`` is
        the sync-hook path: the caller is the barrier closer and already
        holds _apply_lock (applies serialized), so only _params_lock is
        taken — re-entering snapshot()'s _state_lock→_apply_lock order
        from there would self-deadlock."""
        if in_close:
            with self._params_lock:
                params = dict(self._params)
                version = self._params_version
            # _apply_lock (held by the caller) serializes every slot
            # mutation, so the state dict read is consistent lock-free
            return (self._epoch, self._current_iteration, version, params,
                    self._optimizer.state_dict())
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    return (self._epoch, self._current_iteration,
                            self._params_version, dict(self._params),
                            self._optimizer.state_dict())

    def install_tensors(self, tensors: Mapping[str, np.ndarray], *,
                        epoch: int | None = None,
                        iteration: int | None = None,
                        optimizer_state: dict | None = None,
                        optimizer_merge: bool = False,
                        mark_aggregated: bool = True,
                        replace: bool = False) -> int:
        """Install externally-sourced parameter state: a replication ship
        (``replace=True`` — the store becomes exactly the primary's) or a
        reshard stripe handoff (``replace=False`` — the tensors merge into
        whatever this shard already owns).  Unlike :meth:`restore` this
        does NOT clear live iteration states (a reshard target may already
        be serving pushes for other stripes) and it advances — never
        rewinds — ``current_iteration``.  ``mark_aggregated`` raises the
        aggregated watermark to ``iteration`` so a worker's RETRY of an
        iteration the dead primary already applied is answered "already
        aggregated" instead of waiting out a barrier that can never
        re-fire — the promoted-replica dedup that makes failover retries
        idempotent.  Returns the new store version."""
        store = tree_like(tensors)
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    if replace:
                        self._params = store
                    else:
                        merged = dict(self._params)
                        merged.update(store)
                        self._params = merged
                    self._params_version += 1
                    version = self._params_version
                    if optimizer_state is not None and optimizer_merge:
                        # reshard stripe handoff: the moved tensors'
                        # slot entries join this shard's state; its own
                        # scalars (step counts) and other names' slots
                        # stay untouched
                        current = self._optimizer.state_dict()
                        for slot, value in optimizer_state.items():
                            if isinstance(value, dict):
                                cur = current.get(slot)
                                if isinstance(cur, dict):
                                    cur.update(value)
                                else:
                                    current[slot] = dict(value)
                        self._optimizer.load_state_dict(current)
                    elif optimizer_state is not None:
                        self._optimizer.load_state_dict(optimizer_state)
                if replace:
                    # an in-flight streaming close must not publish a mean
                    # computed against the pre-install world on top of the
                    # replaced store (same fence as restore())
                    self._restore_epoch += 1
            if epoch is not None:
                # a replication replace tracks the primary's epoch
                # verbatim; a reshard merge install must never REWIND a
                # live shard's training epoch
                self._epoch = (int(epoch) if replace
                               else max(self._epoch, int(epoch)))
            if iteration is not None:
                it = int(iteration)
                self._current_iteration = max(self._current_iteration, it)
                if mark_aggregated:
                    self._aggregated_watermark = max(
                        self._aggregated_watermark, it)
                    # REPLACE installs only: release any LIVE iteration
                    # state the watermark just superseded.  A worker's
                    # failover retry can race the dead primary's final
                    # in-flight ship — retry lands first, creates the
                    # state, parks on the barrier; the install then
                    # proves the iteration was already applied
                    # cluster-wide.  The state lookup would shadow the
                    # watermark forever (1/N contributors, no one else
                    # will push), so drop it — the woken waiter
                    # re-checks, finds no state, reads the watermark,
                    # and serves the just-installed store.  A reshard
                    # MERGE install must NOT do this: on a shard that
                    # keeps its tensors, a live fence-iteration state
                    # holds real partial sums whose remaining
                    # contributors are still coming — the state's
                    # existence (checked before the watermark) lets it
                    # complete normally.
                    if replace:
                        for stale_it in [i for i in self._iteration_states
                                         if i <= self._aggregated_watermark]:
                            old = self._iteration_states.pop(stale_it)
                            if old.buffer_bytes:
                                self._grad_buffer_note(-old.buffer_bytes)
                                old.buffer_bytes = 0
            for name in store:
                # a stripe can move back here on a later merge reshard
                self._retired.pop(name, None)
            self._serving = None
            flight.record(
                "repl.install" if replace else "reshard.install",
                iteration=(int(iteration) if iteration is not None else -1),
                a=store_nbytes(store), b=version)
            self._barrier_cv.notify_all()
        # the store changed outside the apply timeline: stale delta pairs
        # must not patch receivers toward the installed state (restore()
        # discipline — outside the core locks); the arena re-proves its
        # table and repacks param slabs at next use
        self._reset_delta()
        if self._arena is not None:
            self._arena.invalidate()
        return version

    def retire_tensors(self, names, map_epoch: int
                       ) -> tuple[int, int, int, TensorStore, dict]:
        """The resharding version fence: atomically remove ``names`` from
        the store, tombstone them at ``map_epoch``, and return the removed
        values — all under one lock hold, so the copied stripe is exactly
        the last state this shard ever applied to it (an in-flight barrier
        apply completes first behind _apply_lock; pushes arriving after
        see the tombstones and are rejected stale-shard-map).  The moved
        names' optimizer slot entries (momentum/moments) are extracted
        and removed too, so the new owner continues the SAME optimization
        trajectory and a stale slot can never linger here to resurrect on
        a later merge.  Returns (epoch, iteration, params_version, moved
        tensors, moved optimizer slots {slot: {name: arr}})."""
        name_set = set(names)
        with self._state_lock:
            with self._apply_lock:
                with self._params_lock:
                    moved: TensorStore = {}
                    store = dict(self._params)
                    for name in names:
                        if name in store:
                            moved[name] = store.pop(name)
                    if moved:
                        self._params = store
                        self._params_version += 1
                    version = self._params_version
                    moved_opt: dict = {}
                    opt_state = self._optimizer.state_dict()
                    remaining: dict = {}
                    for slot, value in opt_state.items():
                        if isinstance(value, dict):
                            taken = {n: a for n, a in value.items()
                                     if n in name_set}
                            if taken:
                                moved_opt[slot] = taken
                            remaining[slot] = {
                                n: a for n, a in value.items()
                                if n not in name_set}
                        else:
                            remaining[slot] = value
                    if moved_opt:
                        self._optimizer.load_state_dict(remaining)
            for name in names:
                self._retired[name] = int(map_epoch)
            # Purge the retired names from every LIVE iteration state:
            # sums folded before the fence belong to the stripe's new
            # owner's timeline now, and — worse — on a shard left empty
            # by the retire, a later barrier close would run the
            # bootstrap rule and turn those folded GRADIENTS into
            # parameters.  (Contributor sets are untouched: a worker that
            # pushed stays counted, its still-owned tensors folded fine.)
            for state in self._iteration_states.values():
                freed = 0
                for name in names:
                    acc = state.accum.pop(name, None)
                    if acc is not None:
                        freed += acc.nbytes
                    state.counts.pop(name, None)
                    for folded in state.folded.values():
                        folded.discard(name)
                    for folding in state.folding.values():
                        folding.discard(name)
                if freed:
                    state.buffer_bytes -= freed
                    self._grad_buffer_note(-freed)
            flight.record("reshard.fence", iteration=self._current_iteration,
                          a=len(moved), b=int(map_epoch))
            result = (self._epoch, self._current_iteration, version, moved,
                      moved_opt)
        # a retire reshapes the store: delta pairs built against the
        # pre-fence world must not serve (restore() discipline), and the
        # packing table rebuilds without the tombstoned names — they
        # vacate their slab at the next epoch (core/arena.py)
        self._reset_delta()
        if self._arena is not None:
            self._arena.invalidate()
        return result


def _mean_over_workers(worker_gradients: Mapping[int, TensorStore]) -> TensorStore:
    """Element-wise mean over the gradients of the workers that actually
    contributed (reference: src/parameter_server.cpp:40-63 — sum then divide
    by contributor count, NOT by configured total).  Uses the fused native
    C++ kernel when available (native/psdt_native.cpp psdt_mean), numpy
    otherwise."""
    from ..native import mean_over_workers_native

    by_name: dict[str, list[np.ndarray]] = {}
    for grads in worker_gradients.values():
        for name, g in grads.items():
            by_name.setdefault(name, []).append(np.asarray(g, np.float32))

    out: TensorStore = {}
    for name, arrays in by_name.items():
        native = mean_over_workers_native(arrays)
        if native is not None:
            out[name] = native
            continue
        acc = arrays[0].copy()
        for g in arrays[1:]:
            acc += g
        out[name] = acc * np.float32(1.0 / len(arrays))
    return out
