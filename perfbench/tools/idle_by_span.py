"""Name a kept trace's idle time by the program's own spans.

    PERFBENCH_KEEP_TRACE=1 python3 perfbench/run.py --workload W --seed N --trace 1
    python3 perfbench/tools/idle_by_span.py .perfbench_trace/W

The PS job records spans in its traced run, so its trace holds the
``psdt/*`` mirror of every span (``obs/trace.py``); the serving and SPMD
jobs do not, so run those with ``PSDT_TRACE=1`` in the environment too.

Two readings of the same trace, both printed as one JSON object:

- ``idle_gaps``: ``reduce.reduce_trace`` unchanged, fed the ``psdt/*`` host
  events beside the ``bench/*`` ones, so that each gap between device
  operations is named by the innermost span open when the gap STARTS
  (``psdt/worker/d2h_after_fusion.7``).  A gap of 20 s gets one name.
- ``idle_by_thread``: every idle interval cut at the span boundaries of
  each host thread and added up under the innermost span open at each
  instant on that thread (``none`` where the thread has no span open): which
  leg the host was in while the chip waited, leg by leg.

``device_ops`` lists the operations that took most time with what the
trace's metadata says of each: the ``jax.named_scope`` path of the block it
came from (``tf_op``), its kind, the compiler's operations and bytes.
Only :func:`load` touches JAX (and TensorFlow's proto bindings, for the
metadata); the rest works on plain data.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import reduce  # noqa: E402  (imports no JAX)

PREFIXES = ("psdt/", "bench/")
# what a device operation's metadata in the trace says about it: the path of
# the program's blocks it was lowered from (``jax.named_scope``), its kind,
# and the compiler's count of its operations and bytes
PROVENANCE = ("tf_op", "hlo_category", "flops", "bytes_accessed")


def provenance(path: str) -> dict[str, dict]:
    """{device op: {statistic: value}} from the trace's event metadata, which
    ``jax.profiler.ProfileData`` does not show: read from the raw proto with
    the bindings TensorFlow ships, and empty where they cannot be imported.
    A program loaded from a compile cache carries the names it was COMPILED
    with: names added since show only after a compilation."""
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        return {}
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops: dict[str, dict] = {}
    for plane in space.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            found = {}
            for stat in meta.stats:
                key = names.get(stat.metadata_id)
                if key in PROVENANCE:
                    found[key] = (stat.str_value
                                  or names.get(stat.ref_value)
                                  or stat.uint64_value or stat.int64_value
                                  or stat.double_value)
            if found:
                ops.setdefault(reduce.op_name(meta.name), found)
    return ops


def load(path: str) -> dict:
    """An ``.xplane.pb`` as :func:`reduce.load_xplane` gives it, with the
    ``psdt/*`` host events kept too, each host event's thread as a fourth
    field, and ``ops`` from :func:`provenance`."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [
                        (reduce.op_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for index, line in enumerate(plane.lines):
                thread = f"{line.name}#{index}"
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9,
                                     thread))
    return {"device": device, "host": sorted(host, key=lambda e: e[1]),
            "ops": provenance(path)}


def innermost(events) -> list[tuple[float, float, str]]:
    """One thread's nested (name, start, end) events as disjoint
    [(start, end, name)] pieces, each named by the innermost event open."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []   # (end, name), outermost first
    cursor = 0.0

    def close_until(t: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close_until(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start) if stack else start
        stack.append((end, name))
    close_until(float("inf"))
    return out


def idle_intervals(trace: dict) -> list[tuple[float, float]]:
    """Where the first device ran no operation, inside the traced window
    (first to last thing in the trace, as ``reduce.reduce_trace`` has it)."""
    planes = [events for events in trace["device"].values() if events]
    if not planes:
        return []
    edges = [(s, e) for events in planes for _, s, e in events]
    edges += [(e[1], e[2]) for e in trace["host"]]
    start, end = min(s for s, _ in edges), max(e for _, e in edges)
    busy = reduce.merged((s, e) for _, s, e in planes[0])
    marks = [start] + [t for pair in busy for t in pair] + [end]
    return [(a, b) for a, b in zip(marks[::2], marks[1::2]) if b > a]


def pieces_by_thread(trace: dict) -> dict[str, list]:
    """{thread: innermost pieces} for every host thread that holds a
    ``psdt/*`` or ``bench/*`` event."""
    threads: dict[str, list] = {}
    for name, start, end, thread in trace["host"]:
        threads.setdefault(thread, []).append((name, start, end))
    return {thread: innermost(events) for thread, events in threads.items()}


def idle_under(pieces, idle) -> dict[str, float]:
    """{span or "none": idle seconds} of one thread's pieces."""
    shares: dict[str, float] = {}
    i = 0
    for a, b in idle:
        covered = 0.0
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            start, end, name = pieces[j]
            part = min(b, end) - max(a, start)
            shares[name] = shares.get(name, 0.0) + part
            covered += part
            j += 1
        shares["none"] = shares.get("none", 0.0) + (b - a) - covered
    return dict(sorted(shares.items(), key=lambda kv: -kv[1]))


def report(trace: dict, top: int = 12) -> dict | None:
    idle_at = idle_intervals(trace)
    if not idle_at and not any(trace["device"].values()):
        return None

    def named(shares) -> float:
        return sum(v for k, v in shares.items() if k.startswith("psdt/"))

    pieces = pieces_by_thread(trace)
    by_thread = dict(sorted(
        ((thread, idle_under(p, idle_at)) for thread, p in pieces.items()),
        key=lambda kv: -named(kv[1])))
    # reduce_trace looks a few events back for the span open at a gap's
    # start, which is enough for one thread's disjoint pieces and not for
    # the nested events of several threads: hand it the pieces of the
    # thread that was inside the program's spans for most of the idle time
    main = next(iter(by_thread), None)
    reduced = reduce.reduce_trace(
        {"device": trace["device"],
         "host": [(name, a, b) for a, b, name in pieces.get(main, [])]},
        top=top)
    idle = sum(b - a for a, b in idle_at)
    gaps = reduced["idle_gaps"]

    def pct(seconds: float) -> float | None:
        return 100.0 * seconds / idle if idle > 0 else None

    return {
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "idle_s": idle,
        "idle_gaps_thread": main,
        "idle_gaps": gaps,
        "gaps_named_by_psdt_pct": pct(sum(
            v for k, v in gaps if k.startswith("psdt/"))),
        "idle_by_thread": {
            thread: {"named_by_psdt_pct": pct(named(shares)),
                     "idle_s": dict(list(shares.items())[:top])}
            for thread, shares in by_thread.items()},
        "device_ops": [[name, seconds, trace.get("ops", {}).get(name, {})]
                       for name, seconds in reduced["device_ops"]],
        # how many annotations each span left in the traced window (a leg
        # that is carved out of a block, rpc/shm/wait, leaves one for
        # every entry and ONE span in the program's buffer)
        "psdt_events": dict(collections.Counter(
            e[0] for e in trace["host"] if e[0].startswith("psdt/"))),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", help="an .xplane.pb, or the directory a "
                        "kept trace was written to")
    parser.add_argument("--top", type=int, default=12)
    args = parser.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") \
        else reduce.find_xplane(args.trace)
    if not path:
        print(f"idle_by_span: no .xplane.pb under {args.trace}",
              file=sys.stderr)
        return 1
    out = report(load(path), args.top)
    if out is None:
        print("idle_by_span: the trace holds no device operation",
              file=sys.stderr)
        return 1
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
