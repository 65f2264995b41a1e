"""Cross-process iteration postmortems over flight rings (``pst-trace``).

The flight recorder (:mod:`obs.flight`) leaves one mmap-backed ring per
process under ``PSDT_FLIGHT_DIR`` — including for processes that died by
``kill -9`` or SIGSEGV.  This module merges them (plus any Chrome-trace
dumps the span layer wrote via ``PSDT_TRACE_FILE``) and reconstructs what
actually happened:

- **process listing** — every ring's role/pid, whether it shut down clean
  or DIED (header ``clean`` flag), how much history the ring wrapped
  away, and any faulthandler crash sidecar.
- **iteration timeline** — all events of iteration N keyed by
  ``(iteration, worker)``, time-ordered across processes: worker step
  legs, per-worker push commits, the PS barrier phases
  (seal → drain → apply → publish), replication ships/installs, failover
  reports/promotions, reshard fences.
- **critical path + straggler attribution** — the barrier closes when the
  LAST worker commits; the path from that worker's step start through
  seal/drain/apply to publish is the iteration's critical path, and the
  commit spread across workers is the straggler attribution the elastic
  K-of-N policy (ROADMAP item 1) needs per-worker, per-phase.
- **failure narrative** — dead processes, failover promotions (which
  shard, which new primary, at which epoch) and the worker-side retries
  of the same iteration that made the failover invisible to training.

Renders: text (:func:`render_report`), JSON (:func:`report`), and a
merged Chrome trace (:func:`chrome_events` — paired ``*.start``/``*.end``
events become duration slices, everything else instants) that loads in
Perfetto next to the span layer's own dumps.

Wall clocks: rings merge on ``time.time()`` stamps, which is exact for
same-host postmortems (the chaos drives and tests) and as good as NTP
across hosts — good enough to order millisecond-scale barrier phases in
practice; the per-process ``seq`` breaks ties.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Any, Iterable

from . import flight

# Mirrors tiers/messages.py TIER_AGGREGATE_ID_BASE (asserted equal by
# tests/test_tiers.py): a push-commit worker id at or above this base is
# a leaf aggregator's GROUP contribution, and the postmortem names the
# group — not a phantom worker — in timelines and the critical path.
_TIER_ID_BASE = 1 << 20


def _is_group(worker_id: int) -> bool:
    return worker_id >= _TIER_ID_BASE


def _group_label(worker_id: int) -> str:
    return f"group[leader {worker_id - _TIER_ID_BASE}]"


# One human-readable row per registered flight event: what it marks and
# what the a/b/note payload fields carry.  Keys must cover flight.EVENTS
# exactly — pst-analyze's flight-event pass diffs the two tables, so a
# new event without a decode row (or a stale row) fails the analyzer.
EVENT_DECODE: dict[str, str] = {
    "proc.start": "process came up (role in note)",
    "proc.exit": "clean shutdown recorded",
    "proc.sigterm": "SIGTERM received",
    "rpc.cli.start": "client RPC issued (note = method)",
    "rpc.cli.end": "client RPC done; a=duration_us b=1 ok/0 error",
    "rpc.srv.start": "server handler entered (note = method)",
    "rpc.srv.end": "server handler done; a=duration_us",
    "step.start": "worker step began",
    "step.end": "worker step done; a=duration_us",
    "fused.start": "fused push+pull issued",
    "fused.end": "fused push+pull done; a=duration_us b=1 ok/0 degraded",
    "boot.seed": "worker seeded an empty store",
    "fold.reserve": "gradient chunk fold reserved (sampled); a=tensors",
    "push.commit": "worker push committed; a=contributors b=width",
    "barrier.seal": "barrier sealed; a=contributors",
    "barrier.drain": "in-flight folds drained; a=folds",
    "apply.start": "optimizer apply began",
    "apply.end": "optimizer apply done; a=duration_us",
    "barrier.publish": "new params published; a=contributors b=width",
    "barrier.retry": "failed close left the barrier retryable",
    "repl.ship.start": "replica snapshot ship began; a=bytes b=version",
    "repl.ship.end": "replica ship done; a=duration_us b=version",
    "repl.ack": "replica acked a ship; a=1 ok/0 refused b=version",
    "repl.install": "replica installed a shipped store; a=bytes "
                    "b=version",
    "repl.refuse": "replica refused a ship (note = reason)",
    "repl.degrade": "replication permanently degraded",
    "failover.report": "dead primary reported; a=shard (note = address)",
    "failover.promote": "replica promoted; a=shard b=new epoch",
    "failover.retry": "worker retried onto replacement; a=shard",
    "reshard.fence": "reshard fence; a=tensors retired b=map epoch",
    "reshard.install": "resharded store installed; a=bytes b=epoch",
    "reshard.epoch": "shard map advanced; a=new epoch b=shard count",
    "shm.negotiate": "shm ring negotiated; a=connection b=ring bytes",
    "shm.refuse": "shm refused (note = reason)",
    "shm.attach": "client attached shm ring; b=ring bytes",
    "shm.downgrade": "shm downgraded to TCP (note = reason)",
    "shm.reap": "shm connection reaped; a=connection",
    "shm.reap.dup": "second shm release attempt hit the latch",
    "codec.select": "wire codec chosen; a=1 native/0 python",
    "ckpt.restore": "checkpoint restored",
    "tier.elect": "tier topology elected; a=group size b=epoch/agg id",
    "tier.fold": "member push folded at leaf (sampled); a=tensors "
                 "b=aggregate id",
    "tier.seal": "leaf group sealed; a=contributors b=group size",
    "tier.upstream": "group aggregate shipped upstream; a=duration_us "
                     "b=wire bytes",
    "tier.downgrade": "permanent flat downgrade (note = reason)",
    "serve.delta.build": "serve delta built; a=bytes b=to_version",
    "serve.delta.hit": "delta chain served; a=wire bytes b=pairs",
    "serve.delta.miss": "delta miss, full store served; a=held "
                        "b=current (note = reason)",
    "serve.delta.downgrade": "client permanently downgraded deltas "
                             "(note = reason)",
    "publish.subscribe": "weight subscriber joined; a=held version "
                         "b=subscriber id",
    "publish.swap": "subscriber swapped weights; a=version "
                    "b=duration_us",
    "publish.lag": "subscriber lag sample; a=versions behind",
    "apply.device": "device-resident apply; a=duration_us b=stripes",
    "apply.readback": "async D2H readback started; a=tensors",
    "elastic.join": "member ACTIVE; a=membership epoch",
    "elastic.drain": "member DRAINING; a=epoch (note = reason)",
    "elastic.evict": "coordinator reap marked member GONE; a=epoch",
    "quorum.seal": "barrier closed at K of N; a=contributors b=width",
    "stale.fold": "straggler folded forward; a=staleness b=tensors",
    "fleet.register": "decode server ACTIVE; a=slots b=fleet epoch",
    "fleet.drain": "decode server DRAINING; a=fleet epoch",
    "fleet.evict": "coordinator reap marked server GONE; a=fleet epoch",
    "fleet.route": "router pinned a stream; a=request b=server",
    "fleet.scale": "scale decision; a=target b=epoch/current size",
    "fleet.rollout": "rolling update step; a=version b=server",
    "fleet.swap": "decode server swapped serving version; a=version "
                  "b=server",
    "apply.arena.pack": "arena packing table built; a=duration_us "
                        "b=stripes",
    "apply.arena.repack": "arena table rebuilt on shape change; "
                          "a=duration_us",
    "apply.arena.fallback": "arena close downgraded to per-tensor "
                            "(note = reason)",
    "apply.arena": "flat arena close published; a=dispatch_us "
                   "b=readback_us",
    "freerun.apply": "apply-on-arrival landed; a=staleness b=damp ppm",
    "freerun.dup": "version-vector dedup dropped a replay; a=last step",
    "freerun.publish": "coalesced publication; a=version b=applies",
    "damp.floor": "contribution damped below the floor; a=staleness "
                  "b=scale ppb",
    "shard.install": "partition shard installed; a=bytes b=version",
    "shard.update.degrade": "sharded close degraded to replicated path "
                            "(note = reason)",
    "apply.sharded": "sharded close published; a=replicas b=wire bytes",
    "serve.prefix.hit": "radix prefix reuse; a=prefix tokens reused "
                        "b=suffix tokens forwarded",
    "serve.prefix.evict": "prefix-cache LRU pass; a=nodes evicted "
                          "b=bytes pinned after",
    "serve.prefix.split": "radix edge split; a=split depth b=tree nodes",
    "serve.slow_leg": "serving leg over 0.1 s; a=wall_us b=thread cpu_us "
                      "(note = leg gc=ms cs=involuntary/voluntary "
                      "switches mf=major faults ev=nodes evicted)",
}


def describe_event(name: str) -> str:
    """One-line decode of a flight event name (the name itself when the
    table has no row — old rings can carry codes newer than this build)."""
    return EVENT_DECODE.get(name, name)


def decode_slow_leg(event: dict) -> dict:
    """A ``serve.slow_leg`` flight event back into the fields of the
    record ``obs/legs.py`` kept (the inverse of ``legs.slow_leg_note``;
    this module imports nothing of the package, so the note's grammar is
    mirrored here).  A field the 48-byte note cut short is left out."""
    leg, *pairs = event["note"].split()
    out = {"leg": f"serve/{leg}", "wall_s": event["a"] / 1e6,
           "cpu_s": event["b"] / 1e6, "ended_at": event["ts"]}
    names = {"gc": ("gc_s",), "mf": ("major_faults",), "ev": ("evicted",),
             "cs": ("involuntary_switches", "voluntary_switches")}
    for pair in pairs:
        key, _, value = pair.partition("=")
        try:
            numbers = [int(v) for v in value.split("/")]
        except ValueError:
            continue
        if len(numbers) == len(names.get(key, ())):
            out.update(zip(names[key], numbers))
    if "gc_s" in out:
        out["gc_s"] /= 1e3
    return out


# ------------------------------------------------------------------- loading


def load_rings(directory: str) -> list[dict]:
    """Decode every ``flight-*.ring`` under ``directory`` (skipping
    unreadable/foreign files with a note instead of dying — a postmortem
    tool must not crash on a half-written artifact) and attach any
    ``crash-<pid>.txt`` faulthandler sidecar."""
    rings: list[dict] = []
    for path in sorted(glob.glob(os.path.join(directory, "flight-*.ring"))):
        try:
            ring = flight.decode_ring(path)
        except (OSError, ValueError) as exc:
            rings.append({"path": path, "error": str(exc), "events": [],
                          "pid": 0, "role": "?", "clean": False,
                          "dropped": 0})
            continue
        # clean=0 means "no clean shutdown RECORDED" — which is also the
        # steady state of a process still running.  A same-host liveness
        # probe (signal 0) separates "still running" from "DIED"; rings
        # merge same-host by design (module docstring), and a recycled
        # pid at worst reports a dead process as running, never the
        # reverse.
        ring["alive"] = False
        if not ring["clean"] and ring["pid"]:
            try:
                os.kill(int(ring["pid"]), 0)
                ring["alive"] = True
            except ProcessLookupError:
                pass
            except (PermissionError, OSError):
                ring["alive"] = True  # exists, not ours
        crash = os.path.join(directory, f"crash-{ring['pid']}.txt")
        try:
            if os.path.getsize(crash) > 0:
                with open(crash, errors="replace") as fh:
                    ring["crash"] = fh.read()
        except OSError:
            pass
        rings.append(ring)
    return rings


def merge_events(rings: Iterable[dict]) -> list[dict]:
    """All rings' events in one wall-clock-ordered list, each stamped
    with its source pid/role (per-process seq breaks same-stamp ties)."""
    merged: list[dict] = []
    for ring in rings:
        for ev in ring.get("events", ()):
            ev = dict(ev)
            ev["pid"] = ring.get("pid", 0)
            ev["role"] = ring.get("role", "?")
            merged.append(ev)
    merged.sort(key=lambda e: (e["ts"], e["pid"], e["seq"]))
    return merged


# ------------------------------------------------------------ reconstruction


def iterations_seen(events: Iterable[dict]) -> list[int]:
    return sorted({e["iteration"] for e in events if e["iteration"] >= 0})


def _pairs(events: list[dict], start: str, end: str,
           key=lambda e: (e["pid"], e["tid"], e["iteration"],
                          e["worker"]),
           return_open: bool = False):
    """Match ``start``/``end`` events into intervals per (process,
    thread, iteration, worker) — nearest-start wins, unmatched ends
    dropped.  A crash between start and end leaves an OPEN interval;
    ``return_open=True`` additionally returns those unmatched starts —
    the "in flight at death" evidence the Chrome export must not lose."""
    open_by_key: dict[tuple, list[dict]] = {}
    out: list[tuple[dict, dict]] = []
    for ev in events:
        if ev["event"] == start:
            open_by_key.setdefault(key(ev), []).append(ev)
        elif ev["event"] == end:
            stack = open_by_key.get(key(ev))
            if stack:
                out.append((stack.pop(), ev))
    if return_open:
        opens = [ev for stack in open_by_key.values() for ev in stack]
        return out, opens
    return out


def iteration_timeline(events: list[dict], iteration: int) -> dict:
    """Everything that happened to ``iteration``, reconstructed across
    processes.  Returns a JSON-able dict; see :func:`render_report` for
    the human view."""
    evs = [e for e in events if e["iteration"] == iteration]
    commits = [e for e in evs if e["event"] == "push.commit"]
    publishes = [e for e in evs if e["event"] == "barrier.publish"]
    seals = [e for e in evs if e["event"] == "barrier.seal"]
    drains = [e for e in evs if e["event"] == "barrier.drain"]
    applies = _pairs(evs, "apply.start", "apply.end",
                     key=lambda e: (e["pid"], e["iteration"]))
    retries = [e for e in evs if e["event"] == "failover.retry"]
    # per-worker legs: step + fused/push spans and this worker's commit.
    # Commits are counted PER SOURCE PID: under the sharded topology a
    # worker legitimately commits once on every shard's barrier, so
    # "retried" means >1 commit on the SAME shard process (a replay the
    # dedup absorbed), never the normal per-shard fan-out.
    workers: dict[int, dict] = {}
    groups: dict[int, dict] = {}
    commits_by_pid: dict[tuple[int, int], int] = {}
    for ev in evs:
        wid = ev["worker"]
        if wid < 0:
            continue
        if _is_group(wid):
            # a leaf aggregator's group lane (tiers/): seal → upstream →
            # PS commit, keyed by the synthetic aggregate id
            g = groups.setdefault(wid, {"events": 0})
            g["events"] += 1
            if ev["event"] == "tier.seal":
                g["seal_ts"] = ev["ts"]
                g["sealed_members"] = ev["a"]
                g["group_size"] = ev["b"]
            elif ev["event"] == "tier.upstream":
                g["upstream_ts"] = ev["ts"]
                g["upstream_s"] = ev["a"] / 1e6
                g["upstream_bytes"] = ev["b"]
            elif ev["event"] == "push.commit":
                g["commit"] = ev["ts"]
            continue
        w = workers.setdefault(wid, {"events": 0})
        w["events"] += 1
        if ev["event"] == "step.start":
            w["step_start"] = ev["ts"]
        elif ev["event"] == "step.end":
            w["step_end"] = ev["ts"]
        elif ev["event"] == "push.commit":
            # the LAST commit wins: a failover retry of the same
            # iteration commits again (dedup makes it idempotent)
            w["commit"] = ev["ts"]
            key = (wid, ev["pid"])
            commits_by_pid[key] = commits_by_pid.get(key, 0) + 1
        elif ev["event"] == "failover.retry":
            w["failover_retry"] = ev["note"]
        elif ev["event"] == "tier.fold":
            w["tier_folds"] = w.get("tier_folds", 0) + 1
    for (wid, _pid), n in commits_by_pid.items():
        w = workers[wid]
        w["commits"] = max(w.get("commits", 0), n)
    out: dict[str, Any] = {"iteration": iteration, "workers": workers,
                           "events": len(evs)}
    if groups:
        out["groups"] = groups
    # K-of-N quorum close (elastic/, ISSUE 13): name the workers left
    # OUTSIDE the close — every worker that actually RAN this iteration
    # (a step/fused start or commit FOR it) but had no commit before the
    # quorum seal.  Scoped to this iteration's events deliberately: a
    # gracefully drained member has no step here and must not be named
    # a straggler of closes it was legitimately not part of.  The seal
    # note carries the contributor ids too (belt and braces for wrapped
    # rings).
    quorum_seals = [e for e in evs if e["event"] == "quorum.seal"]
    if quorum_seals:
        q = quorum_seals[0]
        inside = {e["worker"] for e in commits
                  if e["ts"] <= q["ts"] and not _is_group(e["worker"])}
        for tok in (q.get("note") or "").split(","):
            if tok.strip().lstrip("-").isdigit():
                inside.add(int(tok))
        ran_here = {e["worker"] for e in evs
                    if 0 <= e["worker"] < _TIER_ID_BASE
                    and e["event"] in ("push.commit", "step.start",
                                       "fused.start")}
        out["quorum"] = {
            "contributors": q["a"], "width": q["b"],
            "outside": sorted(ran_here - inside),
        }
    stale_folds = [e for e in evs if e["event"] == "stale.fold"]
    if stale_folds:
        # folds INTO this iteration: a straggler's carried gradient
        out["stale_folds"] = [{"worker": e["worker"], "staleness": e["a"],
                               "tensors": e["b"]} for e in stale_folds]
    if commits:
        first, last = commits[0], commits[-1]
        out["first_commit"] = {"worker": first["worker"], "ts": first["ts"]}
        out["last_commit"] = {"worker": last["worker"], "ts": last["ts"]}
        out["commit_spread_s"] = last["ts"] - first["ts"]
        out["straggler"] = last["worker"]
        if _is_group(last["worker"]):
            # attribution by NAME: the barrier-close critical path ran
            # through this group's leaf hop, not a phantom worker
            out["straggler_group"] = _group_label(last["worker"])
    if seals:
        out["seal_ts"] = seals[0]["ts"]
    if drains:
        out["drained_folds"] = drains[0]["a"]
    if applies:
        start, end = applies[0]
        out["apply_s"] = end["a"] / 1e6
        out["apply_ts"] = start["ts"]
    # flat arena apply (core/arena.py, ISSUE 15): the close's arena
    # phases — slab pack(s) attributed to this iteration, the fused
    # stage dispatch, and the contiguous per-stripe readback — rendered
    # as an "arena:" line next to the apply phases
    arena_closes = [e for e in evs if e["event"] == "apply.arena"]
    if arena_closes:
        a = arena_closes[-1]
        arena: dict[str, Any] = {"dispatch_s": a["a"] / 1e6,
                                 "readback_s": a["b"] / 1e6}
        packs = [e for e in evs
                 if e["event"] in ("apply.arena.pack",
                                   "apply.arena.repack")]
        if packs:
            arena["pack_s"] = sum(e["a"] for e in packs) / 1e6
            arena["repacked"] = any(e["event"] == "apply.arena.repack"
                                    for e in packs)
        out["arena"] = arena
    arena_fallbacks = [e for e in evs
                       if e["event"] == "apply.arena.fallback"]
    if arena_fallbacks:
        out["arena_fallback"] = arena_fallbacks[-1].get("note", "")
    if publishes:
        pub = publishes[-1]
        out["publish_ts"] = pub["ts"]
        out["contributors"] = pub["a"]
        out["barrier_width"] = pub["b"]
    if retries:
        out["failover_retries"] = [
            {"worker": e["worker"], "shard": e["a"], "to": e["note"]}
            for e in retries]
    # replication/reshard activity attributed to this iteration
    ships = [e for e in evs if e["event"] == "repl.ship.end"]
    if ships:
        out["replica_ships"] = len(ships)
    installs = [e for e in evs if e["event"] == "repl.install"]
    if installs:
        out["replica_installs"] = [
            {"role": e["role"], "bytes": e["a"], "version": e["b"]}
            for e in installs]
    # versioned delta serving (delta/, ISSUE 10): how this iteration's
    # serve fan-out rode the delta chain vs fell back to full encodes
    dhits = [e for e in evs if e["event"] == "serve.delta.hit"]
    dmisses = [e for e in evs if e["event"] == "serve.delta.miss"]
    if dhits or dmisses:
        out["delta_serve"] = {
            "hits": len(dhits), "misses": len(dmisses),
            "delta_bytes": sum(e["a"] for e in dhits),
            "miss_reasons": sorted({e["note"] for e in dmisses
                                    if e["note"]}),
        }
    return out


def critical_path(events: list[dict], iteration: int,
                  timeline: dict | None = None) -> list[dict]:
    """The ordered chain of events that gated ``iteration``'s barrier
    close: the straggler's step start → its push commit → seal → drain →
    apply → publish, each with its delta to the previous link.  Empty
    when the iteration never published.  ``timeline`` (an
    :func:`iteration_timeline` result) avoids recomputing it."""
    tl = timeline if timeline is not None \
        else iteration_timeline(events, iteration)
    if "publish_ts" not in tl or "last_commit" not in tl:
        return []
    straggler = tl["last_commit"]["worker"]
    chain: list[tuple[str, float]] = []
    if _is_group(straggler):
        # the close gated on a GROUP's leaf hop (tiers/): name it, and
        # chart the intra-group legs — seal (last member arrived at the
        # leaf) and the quantized upstream push — so a slow group is
        # attributable to its own phases, not just "slow"
        label = tl.get("straggler_group") or _group_label(straggler)
        g = tl.get("groups", {}).get(straggler, {})
        if "seal_ts" in g:
            chain.append((f"{label} sealed at its leaf "
                          f"({g.get('sealed_members', '?')} members)",
                          g["seal_ts"]))
        if "upstream_ts" in g:
            chain.append((f"{label} quantized upstream push "
                          f"({g.get('upstream_bytes', 0)} B)",
                          g["upstream_ts"]))
        chain.append((f"{label} upstream commit (closes barrier)",
                      tl["last_commit"]["ts"]))
    else:
        w = tl["workers"].get(straggler, {})
        if "step_start" in w:
            chain.append((f"worker {straggler} step start",
                          w["step_start"]))
        chain.append((f"worker {straggler} push commit (closes barrier)",
                      tl["last_commit"]["ts"]))
    if "seal_ts" in tl:
        chain.append(("barrier seal", tl["seal_ts"]))
    if "apply_ts" in tl:
        chain.append(("optimizer apply", tl["apply_ts"]))
    chain.append(("barrier publish", tl["publish_ts"]))
    chain.sort(key=lambda c: c[1])
    out = []
    prev_ts = chain[0][1]
    for name, ts in chain:
        out.append({"what": name, "ts": ts, "dt_s": ts - prev_ts})
        prev_ts = ts
    return out


def stalled_iterations(events: list[dict], stall_s: float) -> list[dict]:
    """Iterations whose barrier STALLED (elastic/, ISSUE 13 acceptance:
    under an armed quorum no barrier may wait past grace on a gone or
    slow worker).  An iteration counts as stalled when a worker actually
    ran it (a step/fused start exists — pure forward-fold target
    iterations have no step of their own) and either

    - it never published a barrier, or
    - its seal came more than ``stall_s`` after the last pre-seal commit
      (the barrier sat waiting on someone who never arrived).

    Returns ``[{iteration, reason, waited_s?}]`` — empty is the
    acceptance condition pst-trace verifies for the preemption-chaos
    drives."""
    out: list[dict] = []
    for it in iterations_seen(events):
        evs = [e for e in events if e["iteration"] == it]
        if not any(e["event"] in ("step.start", "fused.start")
                   for e in evs):
            continue
        pubs = [e for e in evs if e["event"] == "barrier.publish"]
        if not pubs:
            out.append({"iteration": it, "reason": "never published"})
            continue
        seals = [e for e in evs if e["event"] == "barrier.seal"]
        commits = [e["ts"] for e in evs if e["event"] == "push.commit"]
        if seals and commits:
            pre = [ts for ts in commits if ts <= seals[0]["ts"]]
            if pre:
                waited = seals[0]["ts"] - max(pre)
                if waited > stall_s:
                    out.append({"iteration": it,
                                "reason": f"seal waited {waited:.3f}s "
                                          f"after the last commit",
                                "waited_s": waited})
    return out


def failure_narrative(rings: list[dict], events: list[dict]) -> dict:
    """Dead processes, promotions, and same-iteration failover retries —
    the across-iterations story pst-trace leads with."""
    dead = [{"role": r.get("role", "?"), "pid": r.get("pid", 0),
             "path": r.get("path", ""),
             "crash_traceback": bool(r.get("crash"))}
            for r in rings if not r.get("clean") and not r.get("alive")
            and not r.get("error")]
    promotions = [{"shard": e["a"], "epoch": e["b"], "new_primary": e["note"],
                   "ts": e["ts"], "role": e["role"]}
                  for e in events if e["event"] == "failover.promote"]
    reports = [{"worker": e["worker"], "shard": e["a"], "dead": e["note"]}
               for e in events if e["event"] == "failover.report"]
    retries = [{"worker": e["worker"], "iteration": e["iteration"],
                "shard": e["a"], "to": e["note"]}
               for e in events if e["event"] == "failover.retry"]
    degrades = [{"role": e["role"], "what": e["event"], "note": e["note"]}
                for e in events
                if e["event"] in ("repl.degrade", "shm.downgrade",
                                  "tier.downgrade", "serve.delta.downgrade")]
    # live weight publication (delta/, ISSUE 10): subscriptions opened,
    # decode-side hot swaps (last version swapped in), worst version lag
    subs = [e for e in events if e["event"] == "publish.subscribe"]
    swaps = [e for e in events if e["event"] == "publish.swap"]
    lags = [e["a"] for e in events if e["event"] == "publish.lag"]
    publish: dict[str, Any] = {}
    if subs:
        publish["subscriptions"] = len(subs)
    if swaps:
        publish["swaps"] = len(swaps)
        publish["last_version"] = swaps[-1]["a"]
    if lags:
        publish["max_lag"] = max(lags)
    # elastic membership transitions (elastic/, ISSUE 13): who drained
    # (ctl/SIGTERM/leave), who the reaper marked GONE, and how many
    # quorum closes / forward folds the run saw
    drains = [{"worker": e["worker"], "note": e["note"], "role": e["role"]}
              for e in events if e["event"] == "elastic.drain"]
    evicts = [{"worker": e["worker"]}
              for e in events if e["event"] == "elastic.evict"]
    quorum_closes = sum(1 for e in events if e["event"] == "quorum.seal")
    stale_count = sum(1 for e in events if e["event"] == "stale.fold")
    elastic: dict[str, Any] = {}
    if drains:
        elastic["drains"] = drains
    if evicts:
        elastic["evictions"] = evicts
    if quorum_closes:
        elastic["quorum_closes"] = quorum_closes
    if stale_count:
        elastic["stale_folds"] = stale_count
    slow_legs = [dict(decode_slow_leg(e), role=e["role"])
                 for e in events if e["event"] == "serve.slow_leg"]
    out: dict[str, Any] = {}
    if slow_legs:
        out["slow_legs"] = slow_legs
    if elastic:
        out["membership"] = elastic
    if publish:
        out["publication"] = publish
    if dead:
        out["dead_processes"] = dead
    if promotions:
        out["promotions"] = promotions
    if reports:
        out["failure_reports"] = reports
    if retries:
        out["failover_retries"] = retries
    if degrades:
        out["degrades"] = degrades
    return out


def report(directory: str, iteration: int | None = None) -> dict:
    """The full postmortem as JSON-able data: process listing, failure
    narrative, and the timeline + critical path of ``iteration``
    (default: the last iteration that published a barrier, else the last
    seen)."""
    rings = load_rings(directory)
    events = merge_events(rings)
    published = sorted({e["iteration"] for e in events
                        if e["event"] == "barrier.publish"})
    seen = iterations_seen(events)
    if iteration is None:
        iteration = (published[-1] if published
                     else (seen[-1] if seen else -1))
    out = {
        "directory": directory,
        "processes": [{
            "role": r.get("role", "?"), "pid": r.get("pid", 0),
            "clean": r.get("clean", False),
            "alive": r.get("alive", False),
            "events": len(r.get("events", ())),
            "dropped": r.get("dropped", 0),
            **({"error": r["error"]} if r.get("error") else {}),
            **({"crash": True} if r.get("crash") else {}),
        } for r in rings],
        "iterations": {"seen": seen[:200], "published": published[:200]},
        "narrative": failure_narrative(rings, events),
    }
    if iteration >= 0:
        out["iteration"] = iteration
        tl = iteration_timeline(events, iteration)
        out["timeline"] = tl
        out["critical_path"] = critical_path(events, iteration,
                                             timeline=tl)
    return out


# ------------------------------------------------------------------- renders


def _fmt_dt(s: float) -> str:
    return f"{s * 1e3:.2f}ms" if abs(s) < 1.0 else f"{s:.3f}s"


def render_report(rep: dict) -> str:
    """Human text view of :func:`report` — what pst-trace prints."""
    lines = [f"flight postmortem: {rep['directory']}"]
    for p in rep["processes"]:
        if p["clean"]:
            status = "clean exit"
        elif p.get("alive"):
            status = "still running"
        else:
            status = "DIED (no clean shutdown)"
        extra = ""
        if p.get("crash"):
            extra += ", fatal-signal traceback captured"
        if p.get("dropped"):
            extra += f", ring wrapped ({p['dropped']} events lost)"
        if p.get("error"):
            status, extra = f"unreadable: {p['error']}", ""
        lines.append(f"  {p['role']} (pid {p['pid']}): {status}, "
                     f"{p['events']} events{extra}")
    seen = rep["iterations"]["seen"]
    published = rep["iterations"]["published"]
    lines.append(f"  iterations: {len(seen)} seen, "
                 f"{len(published)} published barriers")
    narrative = rep.get("narrative", {})
    for promo in narrative.get("promotions", ()):
        lines.append(f"  PROMOTION: shard {promo['shard']} -> "
                     f"{promo['new_primary']} at map epoch {promo['epoch']} "
                     f"({promo['role']})")
    for retry in narrative.get("failover_retries", ()):
        lines.append(f"  RETRIED ITERATION: worker {retry['worker']} "
                     f"retried iteration {retry['iteration']} against "
                     f"{retry['to']} (shard {retry['shard']})")
    for d in narrative.get("degrades", ()):
        lines.append(f"  degrade: {d['what']} at {d['role']} ({d['note']})")
    elastic = narrative.get("membership")
    if elastic:
        parts = []
        for d in elastic.get("drains", ()):
            parts.append(f"worker {d['worker']} drained"
                         + (f" ({d['note']})" if d.get("note") else ""))
        for e in elastic.get("evictions", ()):
            parts.append(f"worker {e['worker']} evicted (reap)")
        if elastic.get("quorum_closes"):
            parts.append(f"{elastic['quorum_closes']} quorum closes")
        if elastic.get("stale_folds"):
            parts.append(f"{elastic['stale_folds']} stale folds")
        lines.append(f"  membership: {', '.join(parts)}")
    for leg in narrative.get("slow_legs", ()):
        evidence = ", ".join(
            f"{label} {leg[key]}" for key, label in (
                ("involuntary_switches", "involuntary switches"),
                ("voluntary_switches", "voluntary switches"),
                ("major_faults", "major faults"),
                ("evicted", "nodes evicted")) if leg.get(key))
        lines.append(
            f"  SLOW LEG: {leg['leg']} {_fmt_dt(leg['wall_s'])} "
            f"(thread cpu {_fmt_dt(leg['cpu_s'])}, collector "
            f"{_fmt_dt(leg.get('gc_s', 0.0))}"
            + (f"; {evidence}" if evidence else "")
            + f") at {leg['role']}, ended {leg['ended_at']:.3f}")
    publish = narrative.get("publication")
    if publish:
        parts = []
        if publish.get("subscriptions"):
            parts.append(f"{publish['subscriptions']} subscriptions")
        if publish.get("swaps"):
            parts.append(f"{publish['swaps']} weight swaps "
                         f"(last version {publish.get('last_version', '?')})")
        if publish.get("max_lag"):
            parts.append(f"max lag {publish['max_lag']} versions")
        lines.append(f"  weight publication: {', '.join(parts)}")
    tl = rep.get("timeline")
    if tl:
        lines.append(f"iteration {rep['iteration']}:")
        if "barrier_width" in tl:
            straggler = ""
            if "straggler" in tl:
                straggler = (f", straggler {tl['straggler_group']}"
                             if "straggler_group" in tl
                             else f", straggler worker {tl['straggler']}")
            lines.append(f"  barrier: {tl.get('contributors', '?')}/"
                         f"{tl['barrier_width']} contributors, "
                         f"commit spread "
                         f"{_fmt_dt(tl.get('commit_spread_s', 0.0))}"
                         + straggler)
        for gid in sorted(tl.get("groups", {})):
            g = tl["groups"][gid]
            parts = [f"{g.get('sealed_members', '?')}/"
                     f"{g.get('group_size', '?')} members sealed"]
            if "upstream_s" in g:
                parts.append(f"upstream {_fmt_dt(g['upstream_s'])} "
                             f"({g.get('upstream_bytes', 0)} B quantized)")
            lines.append(f"  {_group_label(gid)}: {', '.join(parts)}")
        quorum = tl.get("quorum")
        if quorum:
            outside = quorum.get("outside")
            lines.append(
                f"  QUORUM close: {quorum['contributors']}/"
                f"{quorum['width']} contributors"
                + (", left outside: "
                   + ", ".join(f"worker {w}" for w in outside)
                   if outside else ""))
        for fold in tl.get("stale_folds", ()):
            lines.append(f"  stale fold: worker {fold['worker']} carried "
                         f"in at staleness {fold['staleness']} "
                         f"({fold['tensors']} tensors, lr damped)")
        if "apply_s" in tl:
            lines.append(f"  optimizer apply: {_fmt_dt(tl['apply_s'])}")
        arena = tl.get("arena")
        if arena:
            parts = []
            if "pack_s" in arena:
                parts.append(
                    ("repack " if arena.get("repacked") else "pack ")
                    + _fmt_dt(arena["pack_s"]))
            parts.append(f"dispatch {_fmt_dt(arena['dispatch_s'])}")
            parts.append(f"readback {_fmt_dt(arena['readback_s'])}")
            lines.append("  arena: " + " + ".join(parts))
        if tl.get("arena_fallback") is not None and "arena" not in tl:
            lines.append("  arena: FELL BACK to per-tensor "
                         f"({tl['arena_fallback'] or 'unknown'})")
        dserve = tl.get("delta_serve")
        if dserve:
            note = (f"  delta serve: {dserve['hits']} chain hits "
                    f"({dserve['delta_bytes']} B), "
                    f"{dserve['misses']} full serves")
            if dserve.get("miss_reasons"):
                note += f" ({', '.join(dserve['miss_reasons'])})"
            lines.append(note)
        for wid in sorted(tl.get("workers", {})):
            w = tl["workers"][wid]
            parts = []
            if "step_start" in w and "step_end" in w:
                parts.append(
                    f"step {_fmt_dt(w['step_end'] - w['step_start'])}")
            elif "step_start" in w:
                parts.append("step OPEN (in flight at death?)")
            if w.get("commits", 0) > 1:
                parts.append(f"{w['commits']} commits (retried)")
            if "failover_retry" in w:
                parts.append(f"failed over to {w['failover_retry']}")
            if w.get("tier_folds"):
                parts.append(f"{w['tier_folds']} leaf folds (tiered)")
            lines.append(f"  worker {wid}: "
                         + (", ".join(parts) if parts
                            else f"{w['events']} events"))
        path = rep.get("critical_path") or []
        if path:
            lines.append("  critical path to barrier close:")
            for link in path:
                lines.append(f"    +{_fmt_dt(link['dt_s'])} {link['what']}")
    return "\n".join(lines)


def chrome_events(events: list[dict]) -> list[dict]:
    """Flight events as Chrome-trace events: paired ``*.start``/``*.end``
    become ``ph="X"`` duration slices, everything else ``ph="i"``
    instants.  pid/tid lanes match the span layer's own dumps, so the
    merged file lines flight evidence up under the spans in Perfetto."""
    out: list[dict] = []
    starts = {name[:-6] for name in flight.EVENTS if name.endswith(".start")}
    paired = {base for base in starts if f"{base}.end" in flight.EVENTS}
    for base in paired:
        matched, opens = _pairs(events, f"{base}.start", f"{base}.end",
                                return_open=True)
        for start, end in matched:
            out.append({
                "name": base, "ph": "X", "cat": "flight",
                "ts": start["ts"] * 1e6,
                "dur": max(end["ts"] - start["ts"], 1e-7) * 1e6,
                "pid": start["pid"], "tid": start["tid"],
                "args": {k: start[k] for k in
                         ("iteration", "worker", "a", "b", "note")
                         if start.get(k) not in (None, "", -1)},
            })
        for start in opens:
            # an operation in flight when the process died (or when the
            # ring was snapshotted): exactly the crash-point evidence —
            # render as a marked instant, never drop it
            out.append({
                "name": f"{base} (open)", "ph": "i", "cat": "flight",
                "s": "p", "ts": start["ts"] * 1e6,
                "pid": start["pid"], "tid": start["tid"],
                "args": {k: start[k] for k in
                         ("iteration", "worker", "a", "b", "note")
                         if start.get(k) not in (None, "", -1)},
            })
    instant = {f"{b}.start" for b in paired} | {f"{b}.end" for b in paired}
    for ev in events:
        if ev["event"] in instant:
            continue
        args = {k: ev[k] for k in
                ("iteration", "worker", "a", "b", "note")
                if ev.get(k) not in (None, "", -1)}
        args["decode"] = describe_event(ev["event"])
        out.append({
            "name": ev["event"], "ph": "i", "cat": "flight", "s": "p",
            "ts": ev["ts"] * 1e6, "pid": ev["pid"], "tid": ev["tid"],
            "args": args,
        })
    out.sort(key=lambda e: e["ts"])
    return out


def export_chrome_trace(directory: str, out_path: str) -> str:
    """Merged Chrome trace of the directory's flight rings PLUS any span
    dumps (``*.json`` written by ``PSDT_TRACE_FILE``) in the same
    directory — the one-file Perfetto view of a postmortem."""
    events = chrome_events(merge_events(load_rings(directory)))
    for path in sorted(glob.glob(os.path.join(directory, "*.json"))):
        if os.path.abspath(path) == os.path.abspath(out_path):
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
            events.extend(doc["traceEvents"] if isinstance(doc, dict)
                          else doc)
        except (OSError, ValueError, KeyError):
            continue  # not a chrome trace: skip, don't die
    events.sort(key=lambda e: e.get("ts", 0.0))
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return out_path
