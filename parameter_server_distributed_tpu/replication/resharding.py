"""Coordinator-orchestrated live resharding (split/merge) of the PS tier.

The worker-side partition is pure arithmetic — ``shard_owner(name, N)``
(crc32 % N, worker/ps_shards.py) — so changing the shard COUNT moves a
deterministic subset of tensor names to new owners.  The controller
performs the move live, with training running:

1. **census** — each current shard lists its tensor names
   (``ReplicaStatus``; names only, no values).
2. **fence + copy** — for every shard losing names, ``RetireTensors``
   atomically removes the moving tensors from its store, tombstones them
   at the upcoming map epoch, and returns their values — all under one
   lock hold, so the copied stripe is exactly the last state that shard
   applied to it (the "version fence").  From this instant a push
   touching a moved name is rejected with the ``stale shard map`` marker
   and the pushing worker parks in
   ``ShardMapClient.wait_for_epoch_above`` — zero failed steps, just a
   bounded pause for the handoff.
3. **install** — the values land on their new owners via
   ``PushReplicaDelta`` (kind=DELTA_INSTALL: merge, don't replace), each
   marked with the source's iteration so the new owner's aggregated
   watermark makes retried pushes idempotent.
4. **publish** — ``CoordinatorCore.set_shard_map`` replaces the layout
   and bumps the epoch; parked workers see it, rebuild their shard
   connections, repartition, and replay the rejected round (per-(worker,
   tensor) dedup on the unchanged shards absorbs the replay).

``ps.reshard.moved_bytes`` counts the handoff volume.
"""

from __future__ import annotations

import logging
from typing import Sequence

from ..core.coordinator_core import CoordinatorCore, ShardMapEntry
from ..core.tensor import TensorStore, from_wire, store_nbytes
from ..obs import stats as obs_stats
from ..worker.ps_shards import shard_owner
from . import messages as rmsg
from .replicator import (OPT_PREFIX, delta_chunks, replication_client,
                         split_replica_store)

log = logging.getLogger("pst.reshard")

_obs_moved = obs_stats.counter("ps.reshard.moved_bytes")


class ReshardError(RuntimeError):
    pass


def _as_entries(layout: Sequence) -> list[ShardMapEntry]:
    entries: list[ShardMapEntry] = []
    for item in layout:
        if isinstance(item, ShardMapEntry):
            entries.append(ShardMapEntry(primary=item.primary,
                                         backup=item.backup))
        elif isinstance(item, (tuple, list)):
            entries.append(ShardMapEntry(
                primary=item[0], backup=item[1] if len(item) > 1 else ""))
        else:
            entries.append(ShardMapEntry(primary=str(item)))
    return entries


class ReshardController:
    """One-shot orchestration of a shard-count change.  Runs wherever the
    coordinator core is reachable in-process (the coordinator itself, an
    admin CLI, a test)."""

    def __init__(self, coordinator_core: CoordinatorCore,
                 timeout_s: float = 60.0):
        self._core = coordinator_core
        self._timeout_s = float(timeout_s)

    def reshard(self, new_layout: Sequence) -> dict:
        """Move to ``new_layout`` (addresses or (primary, backup) pairs).
        Returns a stats dict: moved_bytes, moved_tensors, epoch.  The new
        shards' PS processes must already be running and reachable; a
        shard present in both layouts keeps its non-moving tensors in
        place (only ownership DIFFS travel)."""
        new_entries = _as_entries(new_layout)
        if not new_entries:
            raise ReshardError("new layout must have at least one shard")
        old_epoch, old_entries = self._core.get_shard_map()
        old_primaries = [e.primary for e in old_entries]
        new_primaries = [e.primary for e in new_entries]
        n_new = len(new_primaries)
        fence_epoch = old_epoch + 1  # the epoch set_shard_map will publish

        clients = {addr: replication_client(addr)
                   for addr in set(old_primaries) | set(new_primaries)}
        try:
            # 1. census: names per current shard, and the fence mark —
            # the highest iteration any shard has seen.  Every shard in
            # the new layout gets its aggregated watermark raised to it
            # (step 3), so an iteration that was mid-flight at the fence
            # can never strand a barrier on a shard the not-yet-
            # repartitioned workers will never push to (its gradients for
            # the transition iteration are simply skipped there — the
            # bounded handoff gap).
            names_by_shard: dict[int, list[str]] = {}
            fence_mark = 0
            fence_epoch_max = 0
            for i, addr in enumerate(old_primaries):
                status = clients[addr].call("ReplicaStatus",
                                            rmsg.ReplicaStatusRequest(),
                                            timeout=self._timeout_s)
                names_by_shard[i] = list(status.names)
                fence_mark = max(fence_mark, int(status.iteration))
                fence_epoch_max = max(fence_epoch_max, int(status.epoch))

            # which names leave which shard, and where they land
            transfers: dict[str, TensorStore] = {}  # new addr -> tensors
            moved_tensors = 0
            moved_bytes = 0
            for i, addr in enumerate(old_primaries):
                moving = [n for n in names_by_shard[i]
                          if new_primaries[shard_owner(n, n_new)] != addr]
                if not moving:
                    continue
                # 2. fence + copy (atomic on the source); the retired
                # payload carries the moved tensors AND their optimizer
                # slot entries (__opt__/<slot>/<name>), each routed to
                # its parameter's new owner so the optimization
                # trajectory survives the move
                retired: TensorStore = {}
                for chunk in clients[addr].call(
                        "RetireTensors",
                        rmsg.RetireTensorsRequest(names=moving,
                                                  map_epoch=fence_epoch),
                        timeout=self._timeout_s):
                    fence_epoch_max = max(fence_epoch_max, int(chunk.epoch))
                    fence_mark = max(fence_mark, int(chunk.iteration))
                    retired.update(from_wire(chunk.tensors))
                params, moved_opt = split_replica_store(retired)
                for name, value in params.items():
                    dest = new_primaries[shard_owner(name, n_new)]
                    transfers.setdefault(dest, {})[name] = value
                for slot, entries in (moved_opt or {}).items():
                    if not isinstance(entries, dict):
                        continue  # scalars (step counts) never move
                    for name, value in entries.items():
                        dest = new_primaries[shard_owner(name, n_new)]
                        transfers.setdefault(dest, {})[
                            f"{OPT_PREFIX}{slot}/{name}"] = value
                moved_tensors += len(params)
                moved_bytes += store_nbytes(params)
                log.info("reshard: %d tensors (%.1f MB) leave %s",
                         len(params), store_nbytes(params) / 1e6, addr)

            # 3. install on the new owners, then broadcast the fence mark
            # to EVERY shard of the new layout (an empty marker install
            # raises the aggregated watermark, see step 1) — shards with
            # transfers get it implicitly with their tensors
            for dest, tensors in transfers.items():
                ack = clients[dest].call(
                    "PushReplicaDelta",
                    delta_chunks(fence_epoch_max, fence_mark, 0,
                                 rmsg.DELTA_INSTALL, tensors),
                    timeout=self._timeout_s)
                if not ack.success:
                    raise ReshardError(
                        f"install on {dest} refused: {ack.message}")
            for dest in new_primaries:
                if dest in transfers:
                    continue
                ack = clients[dest].call(
                    "PushReplicaDelta",
                    delta_chunks(fence_epoch_max, fence_mark, 0,
                                 rmsg.DELTA_INSTALL, {}),
                    timeout=self._timeout_s)
                if not ack.success:
                    raise ReshardError(
                        f"fence mark on {dest} refused: {ack.message}")

            # 4. publish the new map (bumps the epoch; parked workers
            # repartition)
            epoch = self._core.set_shard_map(new_entries)
            _obs_moved.add(moved_bytes)
            log.info("reshard complete: %d -> %d shards at epoch %d "
                     "(%d tensors, %.1f MB moved)", len(old_primaries),
                     n_new, epoch, moved_tensors, moved_bytes / 1e6)
            return {"epoch": epoch, "moved_tensors": moved_tensors,
                    "moved_bytes": moved_bytes,
                    "old_shards": len(old_primaries), "new_shards": n_new}
        finally:
            for client in clients.values():
                client.close()
