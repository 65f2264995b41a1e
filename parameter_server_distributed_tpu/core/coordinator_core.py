"""Coordinator membership registry + epoch-numbered PS shard map.

Re-design of the reference's `CoordinatorCore`
(reference: src/coordinator.cpp, include/coordinator.h:10-37): a
mutex-guarded map worker_id -> registry entry with heartbeat timestamps,
stale-worker eviction, and static PS address config.  Extended with a
`live_worker_count` used as the elastic barrier width by
`ParameterServerCore` (the reference instead restarts the PS with a new
TOTAL_WORKERS — scripts/scale_workers.sh:137-144) and, for the
replication subsystem, a dynamic **shard map**: one
:class:`ShardMapEntry` per PS shard with an optional backup replica
address, under a monotone map epoch.  `promote_shard` swaps a dead
primary for its backup (hot failover) and `set_shard_map` replaces the
layout wholesale (live resharding); both bump the epoch so workers can
tell a fresh map from the one they already hold.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Sequence

from ..analysis.lock_order import checked_lock
from ..elastic import messages as emsg
from ..obs import flight
from ..obs import stats as obs_stats
from ..rpc.messages import WorkerStatus
from ..tiers import messages as tmsg
from ..tiers import topology as tier_topology


@dataclasses.dataclass
class WorkerRegistryEntry:
    """reference: include/coordinator.h:10-17."""
    worker_id: int
    address: str
    port: int
    hostname: str
    status: int = WorkerStatus.IDLE
    last_heartbeat: float = 0.0


@dataclasses.dataclass
class FleetMember:
    """One decode server in the serving fleet (fleet/, ISSUE 14):
    identity + capacity + the load signals the router scores on.
    ``state`` reuses the elastic membership constants — scale-in is the
    PR 13 drain-before-stop path applied to serving processes."""
    server_id: int
    address: str
    slots: int
    free_slots: int = 0
    queue_depth: int = 0
    weight_version: int = 0
    active_streams: int = 0
    state: int = emsg.MEMBER_JOINING
    epoch: int = 0            # fleet epoch at the last state transition
    last_heartbeat: float = 0.0
    # radix prefix-cache fingerprint (ISSUE 20): opaque packed block
    # hashes the router scores prompt overlap against; empty = no cache
    prefix_fp: bytes = b""


@dataclasses.dataclass
class ShardMapEntry:
    """One PS shard: its serving primary, an optional backup replica
    that can be promoted, and the map epoch at which this entry last
    changed (replication/ subsystem)."""
    primary: str
    backup: str = ""
    epoch: int = 1


class CoordinatorCore:
    def __init__(self, ps_address: str, ps_port: int,
                 ps_shards: tuple[str, ...] = (),
                 ps_backups: Sequence[str] = (),
                 time_fn: Callable[[], float] = time.monotonic):
        self._ps_address = ps_address
        self._ps_port = int(ps_port)
        # additional shards beyond the primary (see CoordinatorConfig)
        self._ps_shards = tuple(ps_shards)
        self._workers: dict[int, WorkerRegistryEntry] = {}
        # Guards the worker registry AND the shard map/address fields:
        # with failover and resharding the map mutates mid-run from many
        # handler threads, so every read/write goes through it (the
        # pre-replication code left the _ps_address/_ps_shards accessors
        # unguarded — benign for launch-frozen config, a torn-read race
        # once the map is dynamic).
        self._lock = checked_lock("CoordinatorCore._lock")
        self._time = time_fn
        # epoch-numbered shard map (replication/): index = shard index,
        # entry 0 = the primary PS the reference protocol sees
        addresses = [f"{ps_address}:{int(ps_port)}", *self._ps_shards]
        backups = list(ps_backups) + [""] * max(
            0, len(addresses) - len(ps_backups))
        self._shard_epoch = 1
        self._shard_map: list[ShardMapEntry] = [
            ShardMapEntry(primary=addr, backup=backups[i], epoch=1)
            for i, addr in enumerate(addresses)]
        self._obs_promotions = obs_stats.counter("ps.replica.promotions")
        # Hierarchical aggregation registry (tiers/, ISSUE 9): worker ->
        # (host_id, leaf address), the epoch-numbered group list the
        # GetReductionTopology extension serves, dissolved leaf addresses
        # (a dead leaf's group never re-forms on the same address), and
        # workers latched permanently flat (members of a dissolved or
        # broken group — the worker side downgraded permanently too, so
        # re-grouping them would only produce a leaf nobody uses).
        self._tier_workers: dict[int, tuple[str, str]] = {}
        self._tier_groups: list[tmsg.TierGroupEntry] = []
        self._tier_dissolved: set[str] = set()
        self._tier_flat: set[int] = set()
        # Leaf addresses whose group has been SERVED TO ITS LEADER at
        # least once: the leader arms its leaf synchronously on seeing
        # the group, so members (and the PS weight provider) are only
        # shown confirmed groups — without this, a member's first tier
        # round routinely races the election and eats a not-armed
        # refusal.
        self._tier_confirmed: set[str] = set()
        self._tier_epoch = 0
        # Elastic membership (elastic/, ISSUE 13): worker id -> state
        # (JOINING/ACTIVE/DRAINING/GONE) under a monotone membership
        # epoch bumped on EVERY transition, plus a registry generation
        # bumped whenever the live set changes (register of a new
        # worker, graceful leave, reap eviction) — the PS barrier-width
        # TTL cache invalidates on generation movement instead of
        # waiting out the TTL (core/ps_core.py barrier_width).
        self._member_states: dict[int, int] = {}
        self._member_epochs: dict[int, int] = {}
        self._membership_epoch = 0
        self._registry_generation = 0
        # Decode fleet registry (fleet/, ISSUE 14): server id -> row
        # under a monotone fleet epoch bumped on every STATE transition
        # (heartbeat load refreshes don't bump — the router polls the
        # table anyway and an epoch that moved on every heartbeat would
        # carry no information).  ``_fleet_target`` is the manual scale
        # target (``pst-ctl scale``); 0 = the autoscaler's watermarks
        # decide.
        self._fleet: dict[int, FleetMember] = {}
        self._fleet_epoch = 0
        self._fleet_target = 0

    def register_worker(self, worker_id: int, address: str, port: int,
                        hostname: str) -> int:
        """Upsert + heartbeat stamp (reference: src/coordinator.cpp:7-17).
        Returns the total registered worker count.  A worker NEW to the
        registry (first join, or a rejoin after GONE) enters the
        membership table as JOINING and bumps the registry generation —
        a legacy worker without the membership extension simply stays
        JOINING (advisory; the live count is unchanged)."""
        now = self._time()
        with self._lock:
            fresh = worker_id not in self._workers
            self._workers[worker_id] = WorkerRegistryEntry(
                worker_id=worker_id, address=address, port=int(port),
                hostname=hostname, status=WorkerStatus.IDLE, last_heartbeat=now)
            if fresh:
                self._registry_generation += 1
            if self._member_states.get(worker_id) in (None, emsg.MEMBER_GONE):
                self._member_transition_locked(worker_id,
                                               emsg.MEMBER_JOINING)
            return len(self._workers)

    def update_heartbeat(self, worker_id: int, status: int) -> bool:
        """Refresh timestamp + status; False if unknown worker
        (reference: src/coordinator.cpp:19-31)."""
        with self._lock:
            entry = self._workers.get(worker_id)
            if entry is None:
                return False
            entry.last_heartbeat = self._time()
            entry.status = status
            return True

    def list_workers(self) -> list[WorkerRegistryEntry]:
        with self._lock:
            return [dataclasses.replace(e) for e in self._workers.values()]

    def live_worker_count(self) -> int:
        with self._lock:
            return len(self._workers)

    def get_parameter_server_address(self) -> tuple[str, int]:
        """Static config echo (reference: src/coordinator.cpp:46-50)."""
        with self._lock:
            return self._ps_address, self._ps_port

    def set_parameter_server_address(self, address: str, port: int) -> None:
        """Re-point discovery (extension: the reference address is fixed at
        construction; needed for ephemeral ports and PS failover)."""
        with self._lock:
            self._ps_address = address
            self._ps_port = int(port)
            self._shard_map[0].primary = f"{address}:{int(port)}"
            self._shard_map[0].epoch = self._shard_epoch

    def get_parameter_server_shards(self) -> list[str]:
        """All PS shard addresses (current map primaries), shard 0 first.
        A single-element list means the unsharded (reference) topology."""
        with self._lock:
            return [e.primary for e in self._shard_map]

    def set_parameter_server_shards(self, shards: tuple[str, ...]) -> None:
        """Replace the shards beyond the primary (legacy config surface);
        entries whose address is unchanged keep their backup."""
        with self._lock:
            self._ps_shards = tuple(shards)
            old = {e.primary: e for e in self._shard_map[1:]}
            self._shard_epoch += 1
            self._shard_map[1:] = [
                old.get(addr) or ShardMapEntry(primary=addr,
                                               epoch=self._shard_epoch)
                for addr in shards]

    # --------------------------------------------------------- shard map
    def get_shard_map(self) -> tuple[int, list[ShardMapEntry]]:
        """(map epoch, entry copies).  The epoch is monotone: any
        promotion or reshard bumps it, so a worker holding entries at
        epoch E knows a response with epoch > E supersedes them."""
        with self._lock:
            return self._shard_epoch, [dataclasses.replace(e)
                                       for e in self._shard_map]

    def set_shard_backups(self, backups: Sequence[str]) -> None:
        """Attach/replace backup replica addresses by shard index."""
        with self._lock:
            for i, backup in enumerate(backups):
                if i < len(self._shard_map):
                    self._shard_map[i].backup = backup

    def promote_shard(self, shard_index: int,
                      observed_primary: str) -> tuple[int, list[ShardMapEntry]]:
        """Hot failover: swap shard ``shard_index``'s backup in as the
        primary.  Idempotent by construction — the promotion only fires
        when ``observed_primary`` still IS the primary, so N workers
        racing to report the same dead shard promote exactly once and
        the rest just read the fresh map.  Returns the current map."""
        with self._lock:
            if 0 <= shard_index < len(self._shard_map):
                entry = self._shard_map[shard_index]
                if entry.primary == observed_primary and entry.backup:
                    entry.primary, entry.backup = entry.backup, ""
                    self._shard_epoch += 1
                    entry.epoch = self._shard_epoch
                    if shard_index == 0:
                        host, _, port = entry.primary.rpartition(":")
                        self._ps_address = host
                        self._ps_port = int(port)
                    self._obs_promotions.add()
                    # the one place that knows which racing report caused
                    # the swap — the postmortem's PROMOTION line
                    flight.record("failover.promote", a=shard_index,
                                  b=self._shard_epoch, note=entry.primary)
            return self._shard_epoch, [dataclasses.replace(e)
                                       for e in self._shard_map]

    def set_shard_map(self, entries: Sequence[ShardMapEntry]) -> int:
        """Replace the whole layout (live resharding) and bump the epoch.
        Returns the new epoch.  Shard 0's primary becomes the discovery
        address reference peers see."""
        if not entries:
            raise ValueError("shard map must keep at least one shard")
        with self._lock:
            self._shard_epoch += 1
            self._shard_map = [
                ShardMapEntry(primary=e.primary, backup=e.backup,
                              epoch=self._shard_epoch)
                for e in entries]
            host, _, port = self._shard_map[0].primary.rpartition(":")
            self._ps_address = host
            self._ps_port = int(port)
            self._ps_shards = tuple(e.primary for e in self._shard_map[1:])
            flight.record("reshard.epoch", a=self._shard_epoch,
                          b=len(self._shard_map))
            return self._shard_epoch

    # --------------------------------------------------------- membership
    def _member_transition_locked(self, worker_id: int, state: int) -> bool:
        """Move ``worker_id`` to ``state``, bumping the membership epoch
        iff it actually changed (caller holds _lock).  Returns whether a
        transition happened."""
        wid = int(worker_id)
        if self._member_states.get(wid) == state:
            return False
        self._member_states[wid] = state
        self._membership_epoch += 1
        self._member_epochs[wid] = self._membership_epoch
        return True

    def registry_generation(self) -> int:
        """Monotone counter of live-set changes (register/leave/evict) —
        the PS barrier-width cache invalidator (elastic/, ISSUE 13)."""
        with self._lock:
            return self._registry_generation

    def membership(self) -> tuple[int, list[tuple[int, int, int]]]:
        """(membership epoch, [(worker id, state, transition epoch)])
        sorted by worker id — the ``UpdateMembership`` response body."""
        with self._lock:
            return self._membership_epoch, [
                (wid, self._member_states[wid],
                 self._member_epochs.get(wid, 0))
                for wid in sorted(self._member_states)]

    def member_state(self, worker_id: int) -> int | None:
        with self._lock:
            return self._member_states.get(int(worker_id))

    def member_join(self, worker_id: int) -> int:
        """The worker's post-registration join announce: JOINING (or a
        re-join after GONE) -> ACTIVE.  Returns the membership epoch."""
        with self._lock:
            if self._member_transition_locked(worker_id,
                                              emsg.MEMBER_ACTIVE):
                flight.record("elastic.join", worker=int(worker_id),
                              a=self._membership_epoch)
            return self._membership_epoch

    def drain_worker(self, worker_id: int, reason: str = "ctl") -> bool:
        """Mark ``worker_id`` DRAINING (``pst-ctl drain``): it keeps its
        registry entry — and its barrier slot — until it finishes the
        in-flight iteration and announces leave.  False when the worker
        is unknown or already gone."""
        with self._lock:
            wid = int(worker_id)
            state = self._member_states.get(wid)
            if wid not in self._workers and state in (None,
                                                      emsg.MEMBER_GONE):
                return False
            if self._member_transition_locked(wid, emsg.MEMBER_DRAINING):
                flight.record("elastic.drain", worker=wid,
                              a=self._membership_epoch, note=reason[:48])
            return True

    def deregister_worker(self, worker_id: int) -> bool:
        """Graceful leave (drain completion / SIGTERM shutdown): drop
        the registry entry NOW — the barrier narrows at the next width
        refresh (the generation bump makes that immediate for
        generation-aware providers) instead of a stale-heartbeat reap —
        and mark the member GONE."""
        with self._lock:
            wid = int(worker_id)
            removed = self._workers.pop(wid, None) is not None
            if removed:
                self._registry_generation += 1
                if self._tier_workers.pop(wid, None) is not None:
                    self._tier_regroup_locked(tier_topology.min_group_size())
            if self._member_transition_locked(wid, emsg.MEMBER_GONE):
                flight.record("elastic.drain", worker=wid,
                              a=self._membership_epoch, note="leave")
            return removed

    # --------------------------------------------------------- decode fleet
    def _fleet_transition_locked(self, member: FleetMember,
                                 state: int) -> bool:
        """Move ``member`` to ``state``, bumping the fleet epoch iff it
        actually changed (caller holds _lock)."""
        if member.state == state:
            return False
        member.state = state
        self._fleet_epoch += 1
        member.epoch = self._fleet_epoch
        return True

    def fleet_register(self, server_id: int, address: str,
                       slots: int) -> int:
        """A decode server announces itself (or re-announces after GONE):
        straight to ACTIVE — serving has no barrier to join, a registered
        server is routable the moment it heartbeats capacity.  Returns
        the fleet epoch."""
        now = self._time()
        with self._lock:
            sid = int(server_id)
            member = self._fleet.get(sid)
            if member is None or member.state == emsg.MEMBER_GONE:
                member = FleetMember(server_id=sid, address=address,
                                     slots=int(slots),
                                     free_slots=int(slots))
                self._fleet[sid] = member
            member.address = address
            member.slots = int(slots)
            member.last_heartbeat = now
            if self._fleet_transition_locked(member, emsg.MEMBER_ACTIVE):
                flight.record("fleet.register", worker=sid,
                              a=int(slots), b=self._fleet_epoch,
                              note=address[:48])
            return self._fleet_epoch

    def fleet_heartbeat(self, server_id: int, free_slots: int,
                        queue_depth: int, weight_version: int,
                        active_streams: int,
                        prefix_fp: bytes = b"") -> int | None:
        """Load refresh; returns the server's own state (the drain
        signal) or None for an unknown/GONE server — the decode process
        re-registers on None.  ``prefix_fp`` rides every beat (the
        cache churns continuously, so the row always carries the
        latest snapshot; heartbeats deliberately do not bump the
        epoch)."""
        now = self._time()
        with self._lock:
            member = self._fleet.get(int(server_id))
            if member is None or member.state == emsg.MEMBER_GONE:
                return None
            member.last_heartbeat = now
            member.free_slots = int(free_slots)
            member.queue_depth = int(queue_depth)
            member.weight_version = int(weight_version)
            member.active_streams = int(active_streams)
            member.prefix_fp = bytes(prefix_fp)
            return member.state

    def fleet_drain(self, server_id: int) -> bool:
        """Mark a decode server DRAINING (scale-in / ``pst-ctl``): it
        stops admitting new streams, finishes the in-flight ones, and
        leaves.  False when unknown or already gone."""
        with self._lock:
            member = self._fleet.get(int(server_id))
            if member is None or member.state == emsg.MEMBER_GONE:
                return False
            if self._fleet_transition_locked(member, emsg.MEMBER_DRAINING):
                flight.record("fleet.drain", worker=int(server_id),
                              a=self._fleet_epoch)
            return True

    def fleet_leave(self, server_id: int) -> bool:
        """Graceful leave: the row goes GONE now (it stays in the table
        as history — ids are operator-chosen and a rejoin reuses it)."""
        with self._lock:
            member = self._fleet.get(int(server_id))
            if member is None:
                return False
            return self._fleet_transition_locked(member, emsg.MEMBER_GONE)

    def fleet_table(self) -> tuple[int, list[FleetMember], int]:
        """(fleet epoch, row copies sorted by server id, scale target)."""
        with self._lock:
            return (self._fleet_epoch,
                    [dataclasses.replace(self._fleet[sid])
                     for sid in sorted(self._fleet)],
                    self._fleet_target)

    def fleet_state(self, server_id: int) -> int | None:
        with self._lock:
            member = self._fleet.get(int(server_id))
            return None if member is None else member.state

    def set_fleet_target(self, n: int) -> int:
        """Manual scale target (``pst-ctl scale <n>``; 0 = hand control
        back to the autoscaler's watermarks).  Returns the fleet epoch."""
        with self._lock:
            self._fleet_target = max(0, int(n))
            self._fleet_epoch += 1
            flight.record("fleet.scale", a=self._fleet_target,
                          b=self._fleet_epoch)
            return self._fleet_epoch

    def remove_stale_fleet(self, timeout_s: float = 30.0) -> list[int]:
        """Mark decode servers silent for > timeout_s GONE (the serving
        reap — run by the coordinator's reaper thread next to the worker
        reap).  Returns the newly-gone ids."""
        now = self._time()
        evicted: list[int] = []
        with self._lock:
            for member in self._fleet.values():
                if (member.state not in (emsg.MEMBER_GONE,)
                        and now - member.last_heartbeat > timeout_s):
                    if self._fleet_transition_locked(member,
                                                     emsg.MEMBER_GONE):
                        evicted.append(member.server_id)
                        flight.record("fleet.evict",
                                      worker=member.server_id,
                                      a=self._fleet_epoch)
        return evicted

    def width_provider(self):
        """An in-process ``live_workers_fn`` with the ``generation``
        attribute ``ParameterServerCore.barrier_width`` invalidates on —
        the zero-RPC analogue of
        :class:`~..elastic.membership.MembershipWidthProvider` for
        colocated topologies (tests, bench, single-process demos)."""
        core = self

        class _Provider:
            def __call__(self) -> int:
                return core.live_worker_count()

            def generation(self) -> int:
                return core.registry_generation()

            def draining(self) -> tuple[int, ...]:
                # DRAINING workers still hold a barrier slot but are
                # leaving: the quorum threshold pre-shrinks by their
                # count so a graceful drain never costs a grace window,
                # and the IDS let the close verify the absentees really
                # are the drains (elastic/quorum.py + ps_core
                # _quorum_ready_locked, ISSUE 14 satellite)
                return core.draining_worker_ids()

        return _Provider()

    def draining_worker_ids(self) -> tuple[int, ...]:
        """Registered workers currently marked DRAINING — the quorum
        pre-shrink input (a DRAINING worker counts toward the barrier
        width until it leaves, but the K-of-N close must not wait a
        grace window for a contribution it knows is not coming)."""
        with self._lock:
            return tuple(wid for wid in self._workers
                         if self._member_states.get(wid)
                         == emsg.MEMBER_DRAINING)

    # ------------------------------------------------- reduction topology
    def tier_register(self, worker_id: int, host_id: str = "",
                      leaf_address: str = "", dead_leaf: str = ""
                      ) -> tuple[int, list[tmsg.TierGroupEntry], bool, int,
                                 bool]:
        """Register-and-query of the two-tier reduction topology
        (tiers/messages.py GetReductionTopology).  Returns (epoch, group
        copies, enabled, min group size, requester latched flat).
        ``worker_id < 0`` or an empty ``host_id`` registers nothing (the
        PS weight provider's pure read); ``dead_leaf`` dissolves the
        named group — its members latch permanently flat, matching
        their own worker-side downgrade (and told so, so a rebuilt
        client stops polling)."""
        enabled = tier_topology.tiers_enabled()
        min_group = tier_topology.min_group_size()
        with self._lock:
            if dead_leaf:
                self._tier_dissolved.add(dead_leaf)
            if (enabled and worker_id >= 0 and host_id
                    and worker_id not in self._tier_flat):
                prev = self._tier_workers.get(worker_id)
                self._tier_workers[worker_id] = (
                    host_id, leaf_address or (prev[1] if prev else ""))
            if enabled:
                self._tier_regroup_locked(min_group)
            visible = []
            for g in self._tier_groups:
                if int(g.leader_worker_id) == worker_id:
                    # serving the group to its leader confirms it (the
                    # leader arms before using the response)
                    self._tier_confirmed.add(g.leaf_address)
                if (g.leaf_address in self._tier_confirmed
                        or int(g.leader_worker_id) == worker_id):
                    visible.append(g)
            return (self._tier_epoch, visible, enabled, min_group,
                    worker_id in self._tier_flat)

    def _tier_regroup_locked(self, min_group: int) -> None:
        """Recompute the group list (caller holds _lock).  Pass 1:
        members of a group that fell apart (dissolved leaf, evicted
        member) latch permanently flat BEFORE any regrouping — their
        worker side downgraded permanently, so a re-formed group would
        stall on them forever.  Pass 2: new groups form only from live,
        never-grouped workers."""
        changed = False
        survivors: list[tmsg.TierGroupEntry] = []
        for entry in self._tier_groups:
            if (entry.leaf_address in self._tier_dissolved
                    or any(int(w) not in self._tier_workers
                           or int(w) in self._tier_flat
                           for w in entry.member_ids)):
                self._tier_flat.update(int(w) for w in entry.member_ids)
                self._tier_confirmed.discard(entry.leaf_address)
                changed = True
            else:
                survivors.append(entry)
        live = {wid: info for wid, info in self._tier_workers.items()
                if wid not in self._tier_flat}
        before = {g.leaf_address for g in survivors}
        groups, formed = tier_topology.form_groups(
            live, survivors, self._tier_dissolved, min_group)
        if not (changed or formed):
            return
        self._tier_groups = groups
        self._tier_epoch += 1
        for entry in groups:
            if entry.leaf_address not in before:
                # the coordinator-edge election record: which leader,
                # which leaf, at which topology epoch
                flight.record("tier.elect",
                              worker=int(entry.leader_worker_id),
                              a=len(entry.member_ids), b=self._tier_epoch,
                              note=entry.leaf_address)

    def remove_stale_workers(self, timeout_s: float = 30.0) -> list[int]:
        """Evict workers silent for > timeout_s
        (reference: src/coordinator.cpp:52-67).  Returns evicted ids."""
        now = self._time()
        evicted: list[int] = []
        with self._lock:
            for wid in list(self._workers):
                if now - self._workers[wid].last_heartbeat > timeout_s:
                    del self._workers[wid]
                    evicted.append(wid)
            if evicted:
                # the live set shrank: generation-aware width providers
                # (elastic/, ISSUE 13) see the narrowed barrier at their
                # next width read instead of a TTL lapse, and the
                # membership table marks the member GONE (epoch bump)
                self._registry_generation += 1
                for wid in evicted:
                    if self._member_transition_locked(wid,
                                                      emsg.MEMBER_GONE):
                        flight.record("elastic.evict", worker=wid,
                                      a=self._membership_epoch)
            if evicted and self._tier_workers:
                for wid in evicted:
                    self._tier_workers.pop(wid, None)
                self._tier_regroup_locked(tier_topology.min_group_size())
        return evicted
