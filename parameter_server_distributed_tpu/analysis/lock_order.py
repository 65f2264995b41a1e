"""The declared lock order — one table, checked two ways.

``LOCK_RANKS`` is the project's total order over the known long-lived
locks: a thread may only acquire a lock whose rank is STRICTLY GREATER
than every lock it already holds (re-acquiring an RLock it owns is
exempt).  The static pass (:mod:`lockcheck`) checks every intra-procedural
acquisition edge against this table; the runtime mode wraps the same locks
in :class:`CheckedLock` proxies that enforce it live, per thread, under
the real concurrency tests.

Runtime mode is off by default and costs nothing when off:
:func:`checked_lock` returns a plain ``threading.Lock`` unless
``PSDT_LOCK_CHECK=1`` (read at lock creation, i.e. core construction).

``BLOCKING_ALLOWED`` marks locks whose entire PURPOSE is to serialize a
blocking section (the streaming close's ``_apply_lock``, the checkpoint
writer's lock, the trainer's XLA dispatch serializer, the native build
single-flight): the static blocking-while-holding rule skips them, and
anything else blocking under a lock must be fixed or baselined with a
justification (docs/analysis.md).
"""

from __future__ import annotations

import os
import threading

# Qualified lock name -> rank.  Acquire in ascending rank only.
# Class-attribute locks are "ClassName._attr"; module-level locks are
# "module_basename.NAME".
LOCK_RANKS: dict[str, int] = {
    # checkpoint writer: holds its lock across core.snapshot()/restore(),
    # so it must come before every core lock
    "CheckpointManager._lock": 10,
    # coordinator registry + shard map (core/coordinator_core.py, ISSUE
    # 7): leaf in the coordinator process; ranked before the PS core
    # locks so a colocated test topology stays ordered
    "CoordinatorCore._lock": 14,
    # backup-side sharded-update sink (replication/sharded_update.py,
    # ISSUE 18): held across the owned-slice range applies (device
    # dispatch) and core.install_sharded_close (ranks 20..40), and it
    # advances the replica sink's high-water mark inside (rank 16) — so
    # it must come before both
    "ShardedUpdateSink._lock": 15,
    # backup-side replication sink (replication/replicator.py): held
    # across core.install_tensors (ranks 20..40), so it must come first —
    # it serializes whole delta installs against each other and against a
    # racing promotion
    "ReplicaSink._lock": 16,
    # ps_core (core/ps_core.py): the documented order — _state_lock before
    # _apply_lock before _params_lock; _apply_lock is never held while
    # ACQUIRING _state_lock (the streaming closer drops it first)
    "ParameterServerCore._state_lock": 20,
    "ParameterServerCore._apply_lock": 30,
    "ParameterServerCore._params_lock": 40,
    # ALL stripe locks share this one rank (core/stripes.py, ISSUE 5): a
    # stripe lock is always acquired with no other lock held (striped
    # folds reserve under _state_lock, RELEASE it, then take exactly one
    # stripe lock), and the shared rank makes holding two stripes at once
    # a checked violation by construction — no nested-stripe deadlocks.
    "ParameterServerCore._stripe_lock": 44,
    # accelerator-resident sharded apply (async_sgd/device_optimizer.py
    # ShardedDeviceOptimizer, ISSUE 11): guards the per-stripe device
    # partition table + staged slot buffers.  The stripe partitions
    # themselves follow the rank-44 stripe discipline (disjoint name
    # subsets, one touch per apply — no per-partition locks needed); this
    # single lock serializes layout builds/spills and the checkpoint
    # slot readback.  Acquired by stripe-pool apply tasks (no lock held)
    # and by state_dict under the core lock chain 20/30/40, hence 45.
    "ShardedDeviceOptimizer._lock": 45,
    # primary-side replicator (replication/replicator.py): _lock is the
    # wake condition variable's lock (pending flag only, leaf); _ship_lock
    # serializes one state ship to the backup end to end — the replication
    # RPC under it IS the serialized blocking section, and in sync mode it
    # is acquired while the barrier closer holds _apply_lock (30), hence
    # the rank after the core locks
    "Replicator._lock": 46,
    # primary-side sharded-update driver (replication/sharded_update.py,
    # ISSUE 18): fences the lazily-built per-peer clients and the
    # permanent-downgrade set against stop().  Acquired on the barrier
    # closer under _apply_lock (30) and by the per-peer exchange
    # threads; client construction under it may touch the channel
    # (BLOCKING_ALLOWED).
    "ShardedUpdater._lock": 47,
    "Replicator._ship_lock": 48,
    # flat arena apply (core/arena.py, ISSUE 15): serializes packing-
    # table builds and param-slab packs/adoption.  Acquired under
    # _state_lock (20, the fold-side table check), the stripe locks
    # (44), and _apply_lock (30, the close-side pack) — never the other
    # way; the fold hot path reads only the published table reference
    # (a GIL-atomic attribute load).  Device dispatch (H2D packing)
    # under it is its purpose (BLOCKING_ALLOWED).
    "ArenaManager._lock": 49,
    # leaves: never held while acquiring anything else
    "ParameterServerCore._live_lock": 50,
    # membership-backed barrier-width provider (elastic/membership.py,
    # ISSUE 13): single-flights the UpdateMembership poll and guards the
    # last-seen membership epoch.  barrier_width() calls the provider
    # while holding _live_lock (50), hence 51; the RPC under it is the
    # lock's purpose (BLOCKING_ALLOWED).
    "MembershipWidthProvider._lock": 51,
    # tier contribution-weight cache (core/ps_core.py, ISSUE 9): held
    # across the topology provider call — single-flight refresh per TTL
    # expiry, exactly the _live_lock pattern, and the provider may be a
    # coordinator RPC (BLOCKING_ALLOWED)
    "ParameterServerCore._tier_lock": 52,
    # worker-side tier runtime (tiers/group_client.py, ISSUE 9): guards
    # the topology/leaf-connection state during activation and the
    # permanent downgrade swap; never held across an RPC
    "TierClient._lock": 53,
    # shm transport (rpc/shm_transport.py, ISSUE 6): the client-side lock
    # serializes one fused round end to end over the SPSC rings (ring
    # doorbell waits run under it — see BLOCKING_ALLOWED); the server-side
    # lock guards only the connection registry.  Both are leaves: no other
    # declared lock is ever acquired under them.
    "ShmClientConnection._lock": 54,
    "ShmServer._lock": 56,
    # exactly-once shm segment release (ISSUE 8 double-reap fix): leaf,
    # guards only the released flag — the reaper (serve thread exit) and
    # the shutdown path (ShmServer.close -> unlink) must not both unmap
    "_ServerConnection._release_lock": 58,
    # versioned delta chain (delta/chain.py, ISSUE 10): guards the pair
    # map + the subscriber condition variable.  The heavy wire-space
    # encode/diff runs OUTSIDE it; inside are only dict ops and the CV
    # notify.  Acquired under the core locks (the post-apply build hook
    # runs inside the barrier close) and before the serve cache's.
    "DeltaChain._lock": 59,
    # the serve cache and its delta-frame tier (server/ps_service.py)
    # SHARE a rank deliberately (the stripe-lock pattern): each is a leaf
    # held only around dict ops, and the shared rank makes holding both
    # at once a checked violation by construction
    "EncodedServeCache._lock": 60,
    "EncodedDeltaCache._lock": 60,
    # weight-subscription follower mailbox (delta/subscriber.py): leaf,
    # guards only the one-slot pending store + status flags
    "WeightFollower._lock": 61,
    "ClusterAggregator._lock": 62,
    # live-subscription admission counter (server/ps_service.py
    # SubscribeWeights): leaf, guards only the active-subscriber count
    # the bounded handler pool is sized against
    "ParameterServerService._sub_lock": 63,
    "trainer._DISPATCH_LOCK": 64,
    # colocated decode servers' jax-dispatch serializer (fleet/decode.py,
    # ISSUE 14): the serving twin of trainer._DISPATCH_LOCK — concurrent
    # dispatch deadlocks the CPU client when several FleetDecodeServers
    # share a process (tests, bench); uncontended one-per-process in
    # production.  Leaf; the dispatch under it is its purpose.
    "decode._DISPATCH_LOCK": 65,
    "native._lock": 66,
    # single-flight creation of the shared stripe executor
    "stripes._pool_lock": 68,
    # flight recorder (obs/flight.py, ISSUE 8): serializes only
    # enable/disable/atexit — ring creation is file I/O, which is the
    # lock's purpose (BLOCKING_ALLOWED).  The record() hot path is
    # LOCK-FREE (GIL-atomic slot counter + slice stores), so flight
    # events are legal inside _state_lock and the stripe locks; this
    # rank is a leaf regardless.
    "FlightRecorder._lock": 70,
    # pst-status --watch snapshot ring (obs/stats.py): leaf, guards only
    # the bounded deque of timestamped snapshots
    "TimeSeriesRing._lock": 72,
    # decode fleet control plane (fleet/, ISSUE 14).  The fleet server's
    # lock guards its version store / rollback pin / stream bookkeeping
    # (leaf — dict ops only; swaps run on the decode thread with NO lock
    # held).  The router's lock guards its backend table / claims /
    # client cache AND the poll-in-flight flag (leaf: the UpdateFleet
    # poll itself runs with no lock held — admissions route on the
    # stale table instead of queueing behind a coordinator RPC).
    "FleetDecodeServer._lock": 74,
    "FleetRouter._lock": 75,
}

# Locks that exist to serialize a blocking section: the static
# blocking-while-holding rule does not fire under them.
BLOCKING_ALLOWED: frozenset[str] = frozenset({
    # serializes the O(model) scale + optimizer apply OUTSIDE _state_lock
    # (the documented apply-outside-lock pattern, core/ps_core.py)
    "ParameterServerCore._apply_lock",
    # serializes checkpoint file writes (atomic .tmp + os.replace)
    "CheckpointManager._lock",
    # serializes trainer XLA dispatch (concurrent dispatch deadlocked the
    # CPU client — worker/trainer.py)
    "trainer._DISPATCH_LOCK",
    # single-flight g++ build of the native kernels
    "native._lock",
    # serializes one fused shm round (write frames, doorbell-wait, read
    # and consume the response frames) — the ring waits ARE the
    # serialized blocking section
    "ShmClientConnection._lock",
    # single-flight tier-topology refresh: the provider under it may be a
    # coordinator RPC (core/ps_core.py _contribution_for, ISSUE 9)
    "ParameterServerCore._tier_lock",
    # single-flight membership poll: the UpdateMembership RPC under it
    # is the point of the lock (elastic/membership.py, ISSUE 13)
    "MembershipWidthProvider._lock",
    # serializes device-partition layout builds (jit compiles) and the
    # checkpoint slot D2H readback — device dispatch under it is the
    # lock's purpose (ShardedDeviceOptimizer, ISSUE 11)
    "ShardedDeviceOptimizer._lock",
    # serializes arena packing-table builds + param-slab packs: the H2D
    # uploads under it are the point of the lock (core/arena.py, ISSUE 15)
    "ArenaManager._lock",
    # serializes one replication ship (encode + PushReplicaDelta RPC +
    # ack) to the backup — the RPC under it is the point of the lock
    "Replicator._ship_lock",
    # backup-side sharded close: the owned-slice device applies and the
    # store install under it are the lock's purpose (replication/
    # sharded_update.py, ISSUE 18)
    "ShardedUpdateSink._lock",
    # primary-side sharded-update driver: gRPC client construction under
    # it may touch the channel (replication/sharded_update.py)
    "ShardedUpdater._lock",
    # serializes flight-ring creation/teardown (mmap + file I/O is the
    # lock's purpose; the record() hot path never takes it)
    "FlightRecorder._lock",
    # serializes jax dispatch across colocated decode servers — the
    # dispatch under it IS the serialized section (fleet/decode.py)
    "decode._DISPATCH_LOCK",
})

ENV_FLAG = "PSDT_LOCK_CHECK"


def runtime_check_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "") not in ("", "0")


class LockOrderError(RuntimeError):
    """An acquire that violates the declared lock order (runtime mode)."""


_tls = threading.local()


def _held() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def held_locks() -> tuple[str, ...]:
    """Qualified names of the locks the calling thread holds, in
    acquisition order (runtime mode introspection, used by tests)."""
    return tuple(lock.name for lock in _held())


class CheckedLock:
    """Order-asserting proxy over a ``threading.Lock``/``RLock``.

    Drop-in for the ``with`` protocol, raw ``acquire``/``release``, and
    ``threading.Condition(lock)`` (which needs only acquire/release plus
    an optional ``_is_owned``).  Each acquire asserts that every lock the
    thread already holds ranks strictly below this one; violations raise
    :class:`LockOrderError` naming the held chain, which is exactly the
    deadlock witness a hang would never print."""

    __slots__ = ("_lock", "name", "rank", "_reentrant")

    def __init__(self, name: str, rank: int, *, reentrant: bool = False):
        self._lock = threading.RLock() if reentrant else threading.Lock()
        self.name = name
        self.rank = rank
        self._reentrant = reentrant

    # ------------------------------------------------------------- checks
    def _assert_order(self) -> None:
        stack = _held()
        worst = None
        for held in stack:
            if held is self:
                if self._reentrant:
                    return  # RLock re-acquire by the owner: always legal
                raise LockOrderError(
                    f"self-deadlock: thread re-acquiring non-reentrant "
                    f"{self.name} (held: {[h.name for h in stack]})")
            if held.rank >= self.rank and (worst is None
                                           or held.rank > worst.rank):
                worst = held
        if worst is not None:
            raise LockOrderError(
                f"lock-order violation: acquiring {self.name} "
                f"(rank {self.rank}) while holding {worst.name} "
                f"(rank {worst.rank}); held: {[h.name for h in stack]} — "
                f"declared order: analysis/lock_order.py LOCK_RANKS")

    # ------------------------------------------------------ lock protocol
    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        self._assert_order()
        got = self._lock.acquire(blocking, timeout)
        if got:
            _held().append(self)
        return got

    def release(self) -> None:
        self._lock.release()
        stack = _held()
        # remove the most recent entry for this lock (RLock acquires can
        # nest, and ps_core's streaming close releases out of LIFO order)
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is self:
                del stack[i]
                break

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        probe = getattr(self._lock, "locked", None)
        if probe is not None:
            return probe()
        # RLock grows .locked() only in 3.13; emulate: owned by me, or a
        # non-blocking probe acquire fails (owned by someone else)
        if self._is_owned():
            return True
        if self._lock.acquire(False):
            self._lock.release()
            return False
        return True

    def _is_owned(self) -> bool:
        # threading.Condition probes this to assert wait()/notify() are
        # called with the lock held
        return any(held is self for held in _held())


def checked_lock(name: str, *, reentrant: bool = False):
    """A lock for the known slot ``name`` (a ``LOCK_RANKS`` key): a plain
    ``threading.Lock``/``RLock`` normally, a :class:`CheckedLock` proxy
    under ``PSDT_LOCK_CHECK=1``.  Unknown names raise — a new long-lived
    lock must be placed in the declared order before it ships."""
    if name not in LOCK_RANKS:
        raise KeyError(f"lock {name!r} has no declared rank; add it to "
                       f"analysis/lock_order.py LOCK_RANKS")
    if not runtime_check_enabled():
        return threading.RLock() if reentrant else threading.Lock()
    return CheckedLock(name, LOCK_RANKS[name], reentrant=reentrant)
