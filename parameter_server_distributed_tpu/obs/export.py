"""Cluster metric export: heartbeat piggyback + coordinator aggregation.

Workers serialize their registry snapshot (:func:`snapshot_blob`) into the
``obs_snapshot`` extension field of every heartbeat (rpc/messages.py —
reference coordinators skip the unknown field).  The coordinator keeps the
latest snapshot per worker (:class:`ClusterAggregator`) and serves the
rollup over the ``GetClusterMetrics`` extension RPC, which
``pst-status --metrics`` renders: per-worker RPC p50/p95 latency, wire-byte
totals, step-phase breakdown, and the cluster straggler spread — the
telemetry elastic-membership and quantized-transport tuning need
(PAPERS.md: arXiv:2204.03211, arXiv:2506.17615).
"""

from __future__ import annotations

import json
import time
from typing import Any

from ..analysis.lock_order import checked_lock
from .stats import REGISTRY, percentile_from

# step-phase histograms recorded by worker/worker.py, in display order
# ("fused" is the single push→barrier→pull round of the pipelined data
# plane; the serial pull/push/barrier_wait phases appear when it is off
# or degraded)
_PHASES = ("data", "pull", "compute", "push", "fused", "barrier_wait")


def snapshot_blob(**extra: Any) -> bytes:
    """The process registry as JSON bytes, ready for the heartbeat
    extension field.  ``extra`` rides alongside (worker_id etc.)."""
    snap = REGISTRY.snapshot()
    snap["t"] = time.time()
    snap.update(extra)
    return json.dumps(snap, default=float).encode("utf-8")


def _hist_stats(snap: dict, name: str) -> dict | None:
    h = snap.get("histograms", {}).get(name)
    if not h or not h.get("count"):
        return None
    return {"count": h["count"],
            "mean": h["sum"] / h["count"],
            "p50": percentile_from(h, 50),
            "p95": percentile_from(h, 95)}


def _sum_counters(snap: dict, suffix: str, prefix: str = "") -> int:
    return sum(v for k, v in snap.get("counters", {}).items()
               if k.endswith(suffix) and k.startswith(prefix))


def _ps_rollup(snap: dict) -> dict:
    """PS-side hot-path metrics present in a snapshot (a colocated PS —
    tests, bench, single-process demos — shares the process registry, so
    its instruments ride the worker's heartbeat snapshot): the serve
    encode-once cache hit/miss counters, the barrier-close latency, and
    the peak resident gradient-buffer gauge (server/ps_service.py,
    core/ps_core.py)."""
    out: dict = {}
    counters = snap.get("counters", {})
    hits = counters.get("ps.serve.cache_hit", 0)
    misses = counters.get("ps.serve.cache_miss", 0)
    if hits or misses:
        out["serve_cache"] = {"hits": hits, "misses": misses}
    # versioned delta serving (delta/, ISSUE 10): chain-hit vs full-serve
    # fallbacks plus the actual delta wire volume served
    delta: dict = {}
    for key, name in (("hits", "ps.serve.delta_hit"),
                      ("misses", "ps.serve.delta_miss"),
                      ("bytes", "ps.serve.delta_bytes")):
        value = counters.get(name, 0)
        if value:
            delta[key] = value
    if delta:
        out["delta"] = delta
    # accelerator-resident apply (core/device_apply.py, ISSUE 11):
    # device-resident barrier closes
    if counters.get("ps.apply.device", 0):
        out["device_apply"] = {"applies": counters["ps.apply.device"]}
    # flat arena apply (core/arena.py, ISSUE 15): mega-array closes,
    # per-close downgrades to the per-tensor path, and the packing
    # padding overhead (the PSDT_ARENA_ALIGN cost)
    arena: dict = {}
    for key, name in (("applies", "ps.apply.arena"),
                      ("fallbacks", "ps.apply.arena_fallback")):
        value = counters.get(name, 0)
        if value:
            arena[key] = value
    pad = snap.get("gauges", {}).get("ps.apply.arena_pad")
    if arena and pad is not None:
        arena["pad"] = pad
    if arena:
        out["arena"] = arena
    # free-running barrier-free training (freerun/, ISSUE 16):
    # apply-on-arrival volume, version-vector dedups, floor drops,
    # coalesced publications, the live staleness distribution, and the
    # per-unit-staleness damp the schedule currently applies
    freerun: dict = {}
    for key, name in (("applies", "ps.freerun.applies"),
                      ("duplicates", "ps.freerun.duplicates"),
                      ("floor_drops", "ps.freerun.floor_drops"),
                      ("publishes", "ps.freerun.publishes")):
        value = counters.get(name, 0)
        if value:
            freerun[key] = value
    staleness = _hist_stats(snap, "ps.freerun.staleness")
    if staleness:
        freerun["staleness"] = staleness
    beta = snap.get("gauges", {}).get("ps.freerun.effective_beta")
    if freerun and beta is not None:
        freerun["effective_beta"] = beta
    if freerun:
        out["freerun"] = freerun
    # elastic quorum barriers (elastic/, ISSUE 13): K-of-N closes and
    # straggler gradients folded forward damped
    quorum = counters.get("ps.barrier.quorum_closes", 0)
    if quorum:
        out["quorum_closes"] = quorum
    stale = counters.get("ps.stale.folds", 0)
    if stale:
        out["stale_folds"] = stale
    close = _hist_stats(snap, "ps.barrier_close_s")
    if close:
        out["barrier_close"] = close
    peak = snap.get("gauges", {}).get("ps.peak_grad_buffer_bytes", 0)
    if peak:
        out["peak_grad_buffer_bytes"] = peak
    # striped hot path (core/ps_core.py, PSDT_STRIPES): per-stripe apply
    # wall time + the achieved parallelism of the last striped apply
    stripe = _hist_stats(snap, "ps.apply.stripe_ms")
    if stripe:
        out["apply_stripe_ms"] = stripe
    par = snap.get("gauges", {}).get("ps.apply.parallelism", 0)
    if par:
        out["apply_parallelism"] = par
    # replication / failover / resharding (replication/, ISSUE 7)
    replica: dict = {}
    shipped = counters.get("ps.replica.shipped_bytes", 0)
    if shipped:
        replica["shipped_bytes"] = shipped
    lag = snap.get("gauges", {}).get("ps.replica.lag_bytes", 0)
    if lag:
        replica["lag_bytes"] = lag
    ship = _hist_stats(snap, "ps.replica.ship_s")
    if ship:
        replica["ship_s"] = ship
    for key, name in (("promotions", "ps.replica.promotions"),
                      ("failovers", "ps.replica.failovers"),
                      ("fallbacks", "ps.replica.fallback"),
                      ("installed_bytes", "ps.replica.installed_bytes"),
                      ("reshard_moved_bytes", "ps.reshard.moved_bytes")):
        value = counters.get(name, 0)
        if value:
            replica[key] = value
    # cross-replica sharded update (replication/sharded_update.py,
    # ISSUE 18): sharded closes vs local fallbacks on the primary, the
    # exchange payload volume, and the backup-side slice applies
    for key, name in (("sharded_closes", "ps.apply.sharded"),
                      ("sharded_fallbacks", "ps.apply.sharded_fallback"),
                      ("sharded_bytes", "ps.replica.sharded_bytes"),
                      ("sharded_applies", "ps.replica.sharded_applies")):
        value = counters.get(name, 0)
        if value:
            replica[key] = value
    # 1 while this backup replicates by flat SHIPPING only (its
    # accelerator idle through every close), cleared by the first
    # sharded slice apply
    if snap.get("gauges", {}).get("ps.replica.idle_accelerator"):
        replica["idle_accelerator"] = True
    # a promoted primary serving with NO backup (ISSUE 9 satellite):
    # the unreplicated window the standby re-arm closes
    if snap.get("gauges", {}).get("ps.replica.unarmed"):
        replica["unarmed"] = True
    if replica:
        out["replica"] = replica
    # hierarchical aggregation (tiers/, ISSUE 9): leaf relay volume +
    # downgrade count, recorded wherever the leaf/worker runtime lives
    tier: dict = {}
    for key, name in (("upstream_bytes", "tier.upstream_bytes"),
                      ("relays", "tier.relays"),
                      ("rounds", "tier.rounds"),
                      ("downgrades", "tier.downgrades")):
        value = counters.get(name, 0)
        if value:
            tier[key] = value
    upstream = _hist_stats(snap, "tier.upstream_s")
    if upstream:
        tier["upstream_s"] = upstream
    size = snap.get("gauges", {}).get("tier.group_size", 0)
    if size:
        tier["group_size"] = size
    if tier:
        out["tier"] = tier
    return out


def worker_rollup(snap: dict) -> dict:
    """Derived per-worker view of one snapshot: per-method RPC latency
    percentiles, wire-byte totals, and the step-phase breakdown."""
    rpc: dict[str, dict] = {}
    for name in snap.get("histograms", {}):
        if name.startswith("rpc.client.") and name.endswith(".latency_s"):
            method = name[len("rpc.client."):-len(".latency_s")]
            stats = _hist_stats(snap, name)
            if stats:
                rpc[method] = stats
    phases = {}
    for phase in _PHASES:
        stats = _hist_stats(snap, f"worker.{phase}_s")
        if stats:
            phases[phase] = stats
    out = {
        "rpc": rpc,
        "phases": phases,
        "step": _hist_stats(snap, "worker.step_s"),
        "bytes_sent": _sum_counters(snap, ".request_bytes", "rpc.client."),
        "bytes_received": _sum_counters(snap, ".response_bytes",
                                        "rpc.client."),
        "retries": snap.get("counters", {}).get("rpc.client.retries", 0),
        "t": snap.get("t"),
    }
    ps = _ps_rollup(snap)
    if ps:
        out["ps"] = ps
    # native data plane (ISSUE 6): which codec this process resolved
    # (rpc.codec.native gauge) and how much of its fused traffic rode the
    # same-host shared-memory rings vs downgraded to TCP
    shm_bytes = snap.get("counters", {}).get("rpc.shm.bytes", 0)
    shm_fallback = snap.get("counters", {}).get("rpc.shm.fallback", 0)
    codec_native = snap.get("gauges", {}).get("rpc.codec.native")
    if shm_bytes or shm_fallback or codec_native is not None:
        out["native_plane"] = {
            "codec_native": codec_native,
            "shm_bytes": shm_bytes,
            "shm_fallbacks": shm_fallback,
        }
    payload = _sum_counters(snap, ".payload_bytes", "rpc.client.")
    if payload:
        # uncompressed (f32) size of the tensors that rode those wire
        # bytes — the with/without-compression comparison in one view
        out["payload_bytes_f32"] = payload
        # The matching denominator, preferring the worker's exact
        # wire-encoded tensor byte counter (rpc.client.push.wire_bytes —
        # uniform across the unary/stream/fused push paths); older
        # snapshots fall back to the push methods' request_bytes
        # (bytes_sent alone also counts heartbeat snapshots, sync polls,
        # and registration, which would understate the ratio).
        push = _sum_counters(snap, "push.wire_bytes", "rpc.client.")
        if not push:
            push = sum(_sum_counters(snap, ".request_bytes",
                                     f"rpc.client.{method}")
                       for method in ("ReceiveGradients",
                                      "PushGradientsStream",
                                      "PushPullStream"))
        if push:
            out["push_bytes"] = push
    return out


class ClusterAggregator:
    """Latest snapshot per worker + the cluster rollup.

    Entries expire after ``ttl_s`` without a heartbeat so an evicted
    worker's stale numbers do not skew the straggler spread forever."""

    def __init__(self, ttl_s: float = 120.0):
        # leaf rank: held only around snapshot-dict ops
        # (analysis/lock_order.py; order-asserted under PSDT_LOCK_CHECK=1)
        self._lock = checked_lock("ClusterAggregator._lock")
        self._snaps: dict[int, dict] = {}
        self._ttl_s = ttl_s

    def ingest(self, worker_id: int, blob: bytes | str) -> bool:
        if not blob:
            return False
        try:
            snap = json.loads(bytes(blob).decode("utf-8")
                              if not isinstance(blob, str) else blob)
        except (ValueError, UnicodeDecodeError):
            return False
        snap["received_t"] = time.time()
        with self._lock:
            self._snaps[int(worker_id)] = snap
        return True

    def snapshots(self) -> dict[int, dict]:
        now = time.time()
        with self._lock:
            for wid in [w for w, s in self._snaps.items()
                        if now - s.get("received_t", now) > self._ttl_s]:
                del self._snaps[wid]
            return {wid: dict(snap) for wid, snap in self._snaps.items()}

    def rollup(self) -> dict:
        """Cluster view: per-worker derived metrics plus cross-worker
        aggregates (straggler spread, slowest RPC p95, byte totals)."""
        per_worker = {wid: worker_rollup(snap)
                      for wid, snap in self.snapshots().items()}
        step_p50s = {wid: w["step"]["p50"] for wid, w in per_worker.items()
                     if w.get("step")}
        rpc_worst: dict[str, dict] = {}
        for wid, w in per_worker.items():
            for method, stats in w["rpc"].items():
                worst = rpc_worst.get(method)
                if worst is None or stats["p95"] > worst["p95"]:
                    rpc_worst[method] = {**stats, "worker": wid}
        cluster = {
            "workers": len(per_worker),
            "bytes_sent": sum(w["bytes_sent"]
                              for w in per_worker.values()),
            "bytes_received": sum(w["bytes_received"]
                                  for w in per_worker.values()),
            "slowest_rpc": rpc_worst,
        }
        if step_p50s:
            fastest, slowest = min(step_p50s.values()), max(step_p50s.values())
            cluster["straggler"] = {
                "fastest_p50_s": fastest, "slowest_p50_s": slowest,
                "spread": slowest / fastest if fastest > 0 else float("inf"),
                "slowest_worker": max(step_p50s, key=step_p50s.get),
            }
        return {"per_worker": per_worker, "cluster": cluster}


def _fmt_s(v: float | None) -> str:
    if v is None:
        return "-"
    return f"{v * 1e3:.2f}ms" if v < 1.0 else f"{v:.2f}s"


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def render_membership(membership: dict) -> str:
    """One-line view of the coordinator's membership rollup (elastic/,
    ISSUE 13): ``"3 active, 1 draining, 2 gone (epoch 7)"``."""
    states = membership.get("states", {})
    order = ("active", "joining", "draining", "gone")
    parts = [f"{states[k]} {k}" for k in order if states.get(k)]
    parts += [f"{v} {k}" for k, v in sorted(states.items())
              if k not in order and v]
    return (", ".join(parts) if parts else "no members") + \
        f" (epoch {membership.get('epoch', 0)})"


def render_fleet(fleet: dict) -> str:
    """One-line view of the coordinator's decode-fleet rollup (fleet/,
    ISSUE 14): ``"4 active (27/32 slots free, queue 3), versions
    v3..v4, target 4 (epoch 9)"``."""
    states = fleet.get("states", {})
    order = ("active", "joining", "draining", "gone")
    parts = [f"{states[k]} {k}" for k in order if states.get(k)]
    parts += [f"{v} {k}" for k, v in sorted(states.items())
              if k not in order and v]
    line = ", ".join(parts) if parts else "no servers"
    line += (f" ({fleet.get('free_slots', 0)}/{fleet.get('slots', 0)} "
             f"slots free, queue {fleet.get('queue_depth', 0)})")
    versions = fleet.get("versions") or []
    if versions:
        line += (f", version v{versions[0]}" if len(versions) == 1 else
                 f", versions v{versions[0]}..v{versions[-1]}")
    target = fleet.get("target", 0)
    line += f", target {target}" if target else ", autoscale"
    return line + f" (epoch {fleet.get('epoch', 0)})"


def render_rollup(rollup: dict) -> str:
    """Human view of :meth:`ClusterAggregator.rollup` for pst-status."""
    lines: list[str] = []
    cluster = rollup.get("cluster", {})
    lines.append(f"cluster metrics ({cluster.get('workers', 0)} workers "
                 f"reporting)")
    straggler = cluster.get("straggler")
    if straggler:
        lines.append(
            f"  step p50 spread: {_fmt_s(straggler['fastest_p50_s'])} .. "
            f"{_fmt_s(straggler['slowest_p50_s'])} "
            f"({straggler['spread']:.2f}x, slowest worker "
            f"{straggler['slowest_worker']})")
    lines.append(f"  wire bytes: {_fmt_bytes(cluster.get('bytes_sent', 0))} "
                 f"sent / {_fmt_bytes(cluster.get('bytes_received', 0))} "
                 f"received (client-side totals)")
    membership = rollup.get("membership")
    if membership:
        lines.append("  membership: "
                     + render_membership(membership))
    fleet = rollup.get("fleet")
    if fleet:
        lines.append("  fleet: " + render_fleet(fleet))
    for method, stats in sorted(cluster.get("slowest_rpc", {}).items()):
        lines.append(f"  slowest {method}: p95 {_fmt_s(stats['p95'])} "
                     f"(worker {stats['worker']})")
    for wid, w in sorted(rollup.get("per_worker", {}).items()):
        lines.append(f"  worker {wid}:")
        for method, stats in sorted(w["rpc"].items()):
            lines.append(
                f"    rpc {method}: n={stats['count']} "
                f"p50={_fmt_s(stats['p50'])} p95={_fmt_s(stats['p95'])}")
        if w.get("phases"):
            parts = " ".join(
                f"{phase}={_fmt_s(stats['p50'])}"
                for phase, stats in w["phases"].items())
            lines.append(f"    step phases (p50): {parts}")
        ps = w.get("ps")
        if ps:
            parts = []
            cache = ps.get("serve_cache")
            if cache:
                total = cache["hits"] + cache["misses"]
                parts.append(f"serve cache {cache['hits']}/{total} hits "
                             f"({cache['misses']} encodes)")
            dserve = ps.get("delta")
            if dserve:
                total = dserve.get("hits", 0) + dserve.get("misses", 0)
                parts.append(
                    f"delta serve {dserve.get('hits', 0)}/{total} hits "
                    f"({_fmt_bytes(dserve.get('bytes', 0))} delta)")
            dapply = ps.get("device_apply")
            if dapply:
                parts.append(
                    f"device apply {dapply.get('applies', 0)} closes")
            arena = ps.get("arena")
            if arena:
                note = f"arena {arena.get('applies', 0)} flat closes"
                extras = []
                if arena.get("fallbacks"):
                    extras.append(f"{arena['fallbacks']} fallbacks")
                if arena.get("pad"):
                    extras.append(f"pad {100 * arena['pad']:.1f}%")
                if extras:
                    note += f" ({', '.join(extras)})"
                parts.append(note)
            fr = ps.get("freerun")
            if fr:
                note = f"freerun {fr.get('applies', 0)} applies"
                extras = []
                if fr.get("duplicates"):
                    extras.append(f"{fr['duplicates']} dups")
                if fr.get("floor_drops"):
                    extras.append(f"{fr['floor_drops']} floor drops")
                if fr.get("publishes"):
                    extras.append(f"{fr['publishes']} publishes")
                if extras:
                    note += f" ({', '.join(extras)})"
                stl = fr.get("staleness")
                if stl:
                    note += (f", staleness p50={stl['p50']:.1f} "
                             f"p95={stl['p95']:.1f}")
                if fr.get("effective_beta") is not None:
                    note += f", eff beta {fr['effective_beta']:.4f}"
                parts.append(note)
            if ps.get("quorum_closes"):
                parts.append(f"{ps['quorum_closes']} quorum closes")
            if ps.get("stale_folds"):
                parts.append(f"{ps['stale_folds']} stale folds")
            close = ps.get("barrier_close")
            if close:
                parts.append(f"barrier close p50={_fmt_s(close['p50'])}")
            stripe = ps.get("apply_stripe_ms")
            if stripe:
                note = (f"apply stripes p50={stripe['p50']:.2f}ms")
                par = ps.get("apply_parallelism")
                if par:
                    note += f" ({par:g}x parallel)"
                parts.append(note)
            peak = ps.get("peak_grad_buffer_bytes")
            if peak:
                parts.append(f"peak grad buffer {_fmt_bytes(peak)}")
            lines.append(f"    ps: {', '.join(parts)}")
            replica = ps.get("replica")
            if replica:
                rparts = []
                if replica.get("shipped_bytes"):
                    note = f"shipped {_fmt_bytes(replica['shipped_bytes'])}"
                    ship = replica.get("ship_s")
                    if ship:
                        note += f" (ship p50={_fmt_s(ship['p50'])})"
                    rparts.append(note)
                if replica.get("lag_bytes"):
                    rparts.append(f"lag {_fmt_bytes(replica['lag_bytes'])}")
                if replica.get("installed_bytes"):
                    rparts.append(
                        f"installed {_fmt_bytes(replica['installed_bytes'])}")
                if replica.get("promotions"):
                    rparts.append(f"{replica['promotions']} promotions")
                if replica.get("failovers"):
                    rparts.append(f"{replica['failovers']} failovers")
                if replica.get("fallbacks"):
                    rparts.append(f"{replica['fallbacks']} fallbacks")
                if replica.get("reshard_moved_bytes"):
                    rparts.append(
                        "reshard moved "
                        + _fmt_bytes(replica["reshard_moved_bytes"]))
                if replica.get("sharded_closes"):
                    rparts.append(
                        f"{replica['sharded_closes']} sharded closes "
                        f"({_fmt_bytes(replica.get('sharded_bytes', 0))} "
                        f"exchanged)")
                if replica.get("sharded_fallbacks"):
                    rparts.append(f"{replica['sharded_fallbacks']} "
                                  f"sharded fallbacks")
                if replica.get("sharded_applies"):
                    rparts.append(f"{replica['sharded_applies']} "
                                  f"sharded slice applies")
                if replica.get("idle_accelerator"):
                    rparts.append("idle accelerator (flat-ship replica)")
                if replica.get("unarmed"):
                    rparts.append("UNARMED (promoted primary, no backup)")
                lines.append(f"    replication: {', '.join(rparts)}")
            tier = ps.get("tier")
            if tier:
                tparts = []
                if tier.get("relays"):
                    note = (f"{tier['relays']} relays "
                            f"({_fmt_bytes(tier.get('upstream_bytes', 0))} "
                            f"quantized upstream)")
                    up = tier.get("upstream_s")
                    if up:
                        note += f" p50={_fmt_s(up['p50'])}"
                    tparts.append(note)
                if tier.get("group_size"):
                    tparts.append(f"group of {tier['group_size']:g}")
                if tier.get("rounds"):
                    tparts.append(f"{tier['rounds']} tiered rounds")
                if tier.get("downgrades"):
                    tparts.append(f"{tier['downgrades']} downgrades")
                lines.append(f"    tiers: {', '.join(tparts)}")
        native_plane = w.get("native_plane")
        if native_plane:
            parts = []
            if native_plane.get("codec_native") is not None:
                parts.append("codec="
                             + ("native" if native_plane["codec_native"]
                                else "python"))
            if native_plane.get("shm_bytes"):
                parts.append(
                    f"shm {_fmt_bytes(native_plane['shm_bytes'])}")
            if native_plane.get("shm_fallbacks"):
                parts.append(
                    f"{native_plane['shm_fallbacks']} shm fallbacks")
            if parts:
                lines.append(f"    data plane: {', '.join(parts)}")
        extra = (f"    bytes: {_fmt_bytes(w['bytes_sent'])} sent / "
                 f"{_fmt_bytes(w['bytes_received'])} received")
        if w.get("payload_bytes_f32"):
            ratio = (w["payload_bytes_f32"]
                     / max(1, w.get("push_bytes") or w["bytes_sent"]))
            extra += (f" (f32 payload {_fmt_bytes(w['payload_bytes_f32'])}"
                      f", {ratio:.1f}x compression)")
        if w.get("retries"):
            extra += f", {w['retries']} retries"
        lines.append(extra)
    return "\n".join(lines)
