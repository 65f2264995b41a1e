"""Arithmetic from raw observations to metrics: percentiles, the throughput
window, histogram deltas, interval unions and the profiler-trace reduction.

Everything here works on plain Python data so that the tests can feed it
hand-made inputs; only :func:`load_xplane` touches JAX.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re
import statistics

# ------------------------------------------------------------ percentiles


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default method).  Raises on an empty sample: a
    latency with no sample is a failure, not a zero."""
    ordered = sorted(float(v) for v in values)
    if not ordered:
        raise ValueError("percentile of an empty sample")
    if len(ordered) == 1:
        return ordered[0]
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def iqr_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / statistics.median(values)


# ------------------------------------------------------ throughput window


def throughput_window(fences, opens_at_step: int, seconds: float):
    """Whole steps between two fences over their own elapsed time.

    ``fences`` is [(step, t)], one per fenced (fetched) step, in order.  The
    window opens at the fence of ``opens_at_step`` and closes at the first
    later fence that is at least ``seconds`` after it.  Returns
    (steps, elapsed_s, close_step), or None while no fence closes it."""
    opened = None
    for step, t in fences:
        if opened is None:
            if step == opens_at_step:
                opened = (step, t)
            continue
        if t - opened[1] >= seconds:
            return step - opened[0], t - opened[1], step
    return None


# -------------------------------------------------------- histogram delta

_BASE = 2.0 ** 0.25   # bucket ratio of the program's obs/stats.Histogram


def histogram_delta(before: dict | None, after: dict) -> dict:
    """Observations made between two snapshots of one log-bucket histogram
    (``obs/stats.Histogram.snapshot``): counts, sum and buckets subtract."""
    before = before or {"count": 0, "sum": 0.0, "zeros": 0, "buckets": {}}
    buckets = {}
    for key, n in after.get("buckets", {}).items():
        left = n - before.get("buckets", {}).get(key, 0)
        if left > 0:
            buckets[int(key)] = left
    return {"count": after.get("count", 0) - before.get("count", 0),
            "sum": after.get("sum", 0.0) - before.get("sum", 0.0),
            "zeros": after.get("zeros", 0) - before.get("zeros", 0),
            "buckets": buckets}


def histogram_percentile(delta: dict, q: float) -> float | None:
    """Percentile off the bucket midpoints (each bucket is 19% wide, so the
    value is within about 9% of the true one).  None without observations."""
    count = delta.get("count", 0)
    if count <= 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * count))
    seen = delta.get("zeros", 0)
    if rank <= seen:
        return 0.0
    for idx in sorted(delta["buckets"]):
        seen += delta["buckets"][idx]
        if rank <= seen:
            return _BASE ** (idx - 0.5)
    return None


# ------------------------------------------------------------- intervals


def merged(intervals) -> list[tuple[float, float]]:
    """[(start, end)] intervals with every overlap joined, in order."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def union_length(intervals) -> float:
    """Total length covered by [(start, end)] intervals."""
    return sum(end - start for start, end in merged(intervals))


def idle_share(busy_s: float, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError("idle share of an empty window")
    return 1.0 - busy_s / window_s


# ------------------------------------------------------- profiler traces

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv)")
_TINY_GAP_S = 10e-6


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def op_name(event_name: str) -> str:
    """The profiler names a device op by its whole HLO line
    (``%fusion.589 = (f32[...]) fusion(...)``): keep the op's own name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """An ``.xplane.pb`` as plain data:
    {"device": {plane: [(name, start_s, end_s)]}, "host": [(name, start_s,
    end_s)]}.  Device events are those of each device plane's op line;
    host events are the benchmark's own ``bench/*`` annotations."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name:
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            device[name] = [
                (op_name(ev.name), ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return {"device": device, "host": sorted(host, key=lambda e: e[1])}


def self_times(events) -> list[tuple[str, float, float, float]]:
    """[(name, start, end, self_s)] for the events of one in-order line,
    where an event that encloses later ones (a ``while`` around its body)
    keeps only the time its children do not cover."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    out: list[list] = []
    stack: list[int] = []
    for name, start, end in ordered:
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(end, out[stack[-1]][2]) - start
        out.append([name, start, end, end - start])
        stack.append(len(out) - 1)
    return [(n, s, e, max(0.0, t)) for n, s, e, t in out]


def _host_at(host, starts, t: float) -> str:
    """The innermost benchmark annotation open at time t (``host`` sorted
    by start, ``starts`` its start times; annotations nest a few deep)."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(-1, i - 8), -1):
        if host[j][2] >= t:
            return host[j][0]
    return "bench/none"


def reduce_trace(trace: dict, top: int = 10) -> dict | None:
    """Busy and idle time, the ops that took most time, the idle gaps by
    what the host was doing, and the collectives' exposed time.

    ``busy_s`` is the union of the device's op intervals, averaged over the
    devices.  ``window_s`` is the traced window: from the first to the last
    thing the trace holds, device op or ``bench/*`` host annotation (a PS
    round is mostly host work with no device op at either end).  Returns
    None when no device plane holds an op."""
    planes = {k: v for k, v in trace["device"].items() if v}
    if not planes:
        return None
    busy = collective = 0.0
    op_time: dict[str, float] = {}
    gaps: dict[str, float] = {}
    everything = [(s, e) for events in planes.values() for _, s, e in events]
    everything += [(s, e) for _, s, e in trace["host"]]
    window_start = min(s for s, _ in everything)
    window_end = max(e for _, e in everything)
    for events in planes.values():
        busy += union_length((s, e) for _, s, e in events)
        for name, _, _, self_s in self_times(events):
            op_time[name] = op_time.get(name, 0.0) + self_s
            if COLLECTIVE.match(name):
                collective += self_s
    n = len(planes)
    # gaps of the first device only: the host is one timeline
    first = next(iter(planes.values()))
    spans = ([(window_start, window_start)]
             + merged((s, e) for _, s, e in first)
             + [(window_end, window_end)])
    ends = sorted((e, name) for name, _, e in first)
    end_times = [e for e, _ in ends]
    host = trace["host"]
    host_starts = [s for _, s, _ in host]
    for (_, gap_start), (gap_end, _) in zip(spans, spans[1:]):
        length = gap_end - gap_start
        if length <= 0:
            continue
        if length < _TINY_GAP_S:
            key = "gaps_under_10_us"
        else:
            i = bisect.bisect_right(end_times, gap_start + 1e-12) - 1
            before = ends[i][1] if i >= 0 else "start"
            key = f"{_host_at(host, host_starts, gap_start)}_after_{before}"
        gaps[key] = gaps.get(key, 0.0) + length
    by_time = sorted(op_time.items(), key=lambda kv: -kv[1])
    by_gap = sorted(gaps.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy / n, "window_s": window_end - window_start,
            "devices": n,
            "collective_exposed_s": collective / n,
            "device_ops": [[k, v / n] for k, v in by_time[:top]],
            "idle_gaps": [[k, v] for k, v in by_gap[:top]]}
