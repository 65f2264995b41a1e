"""Process-wide metric instruments: counters, gauges, log-bucket histograms.

Design constraints, in order:

1. **Hot-path cheap.**  ``Histogram.observe`` is one ``log`` + one dict
   increment under a per-instrument lock — safe to leave unconditionally
   on every RPC and every step phase (the <2% bench-overhead budget).
2. **Mergeable.**  Everything snapshots to plain JSON (bucket maps, not
   percentiles), so worker snapshots can ride heartbeats and be aggregated
   or re-quantiled at the coordinator losslessly (obs/export.py).
3. **Bounded error.**  Buckets are geometric with ratio 2**(1/4) (~19%
   wide), so any percentile read off the bucket midpoints is within ~9%
   of the true value — plenty for p50/p95 latency and straggler spread.

Also home to the training loops' own instruments: StepTimer,
MetricsLogger, profile_trace, samples_per_sec.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import time
from typing import Any, Iterator

# Geometric bucket ratio: value v (>0) lands in bucket ceil(log(v, BASE));
# bucket i spans (BASE**(i-1), BASE**i].
_BASE = 2.0 ** 0.25
_LOG_BASE = math.log(_BASE)


class Counter:
    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0

    def add(self, n: int | float = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Log-bucketed distribution: O(1) memory in observations, bounded
    relative error on percentiles (see module docstring)."""

    __slots__ = ("_lock", "buckets", "count", "total", "zeros",
                 "vmin", "vmax")

    def __init__(self):
        self._lock = threading.Lock()
        self.buckets: dict[int, int] = {}
        self.count = 0
        self.total = 0.0
        self.zeros = 0  # observations <= 0 (kept out of the log buckets)
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            if v < self.vmin:
                self.vmin = v
            if v > self.vmax:
                self.vmax = v
            if v <= 0.0:
                self.zeros += 1
                return
            idx = math.ceil(math.log(v) / _LOG_BASE - 1e-9)
            self.buckets[idx] = self.buckets.get(idx, 0) + 1

    def percentile(self, q: float) -> float:
        with self._lock:
            return percentile_from(self._snapshot_locked(), q)

    def summary(self) -> dict[str, float]:
        with self._lock:
            snap = self._snapshot_locked()
        if not snap["count"]:
            return {"count": 0}
        return {"count": snap["count"],
                "mean": snap["sum"] / snap["count"],
                "p50": percentile_from(snap, 50),
                "p95": percentile_from(snap, 95),
                "min": snap["min"], "max": snap["max"]}

    def _snapshot_locked(self) -> dict:
        return {"count": self.count, "sum": self.total, "zeros": self.zeros,
                "min": self.vmin if self.count else 0.0,
                "max": self.vmax if self.count else 0.0,
                "buckets": dict(self.buckets)}

    def snapshot(self) -> dict:
        with self._lock:
            return self._snapshot_locked()


def percentile_from(snap: dict, q: float) -> float:
    """q-th percentile from a histogram SNAPSHOT (local or one that rode a
    heartbeat — bucket keys may have become strings in JSON).  Returns the
    geometric midpoint of the bucket holding the target rank, clamped to
    the observed [min, max]."""
    count = snap.get("count", 0)
    if not count:
        return float("nan")
    rank = max(1, math.ceil(q / 100.0 * count))
    seen = snap.get("zeros", 0)
    if rank <= seen:
        return min(0.0, snap["min"])
    items = sorted((int(k), v) for k, v in snap["buckets"].items())
    for idx, n in items:
        seen += n
        if rank <= seen:
            mid = _BASE ** (idx - 0.5)
            return min(max(mid, snap["min"]), snap["max"])
    return snap["max"]


class Registry:
    """Name -> instrument map; the process-wide default is ``REGISTRY``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: dict[str, Any] = {}

    def _get(self, name: str, cls):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = self._instruments[name] = cls()
            elif not isinstance(inst, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{type(inst).__name__}")
            return inst

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def clear(self) -> None:
        with self._lock:
            self._instruments.clear()

    def snapshot(self) -> dict:
        """JSON-able view of every instrument (histograms as bucket maps —
        see obs/export.py for percentile/rollup computation)."""
        with self._lock:
            items = list(self._instruments.items())
        out: dict[str, dict] = {"counters": {}, "gauges": {},
                                "histograms": {}}
        for name, inst in items:
            if isinstance(inst, Counter):
                out["counters"][name] = inst.value
            elif isinstance(inst, Gauge):
                out["gauges"][name] = inst.value
            else:
                out["histograms"][name] = inst.snapshot()
        return out


REGISTRY = Registry()


class TimeSeriesRing:
    """Bounded ring of timestamped registry snapshots, and the rate/delta
    math between them — what ``pst-status --watch`` renders (ISSUE 8).

    Snapshots are the same plain-JSON shape :meth:`Registry.snapshot`
    emits (and that heartbeats carry), so the ring works identically over
    a local registry or over rollup snapshots fetched from the
    coordinator.  ``push`` stamps ``t`` if absent; :meth:`rates` derives
    per-second counter rates, histogram observation rates, and gauge
    values between the two most recent snapshots (or any pair)."""

    def __init__(self, capacity: int = 64):
        from collections import deque

        from ..analysis.lock_order import checked_lock

        # leaf (analysis/lock_order.py): guards only the deque
        self._lock = checked_lock("TimeSeriesRing._lock")
        self._snaps: deque = deque(maxlen=max(2, int(capacity)))

    def push(self, snap: dict) -> dict:
        snap = dict(snap)
        snap.setdefault("t", time.time())
        with self._lock:
            self._snaps.append(snap)
        return snap

    def __len__(self) -> int:
        with self._lock:
            return len(self._snaps)

    def last(self, n: int = 1) -> list[dict]:
        with self._lock:
            return list(self._snaps)[-n:]

    def rates(self) -> dict | None:
        """Deltas between the two newest snapshots, or None until two
        exist."""
        pair = self.last(2)
        if len(pair) < 2:
            return None
        return snapshot_rates(pair[0], pair[1])


def snapshot_rates(prev: dict, cur: dict) -> dict:
    """Per-second rates between two registry snapshots: counters become
    ``delta/dt``, histograms become observation rates (count delta/dt)
    with the interval mean, gauges pass through at their current value.
    Counters that went BACKWARD (process restart) report the current
    value over dt — a restart reads as a burst, not a negative rate."""
    dt = max(1e-9, float(cur.get("t", 0.0)) - float(prev.get("t", 0.0)))
    counters = {}
    for name, value in cur.get("counters", {}).items():
        before = prev.get("counters", {}).get(name, 0)
        delta = value - before if value >= before else value
        # zero rates are kept, deliberately: a STALLED worker showing
        # 0.00/s is exactly the signal --watch exists to surface —
        # eliding it would be indistinguishable from the worker not
        # being part of the cluster at all
        counters[name] = delta / dt
    hists = {}
    for name, h in cur.get("histograms", {}).items():
        count = h.get("count", 0)
        ph = prev.get("histograms", {}).get(name, {})
        pcount = ph.get("count", 0)
        dcount = count - pcount if count >= pcount else count
        if not dcount:
            continue
        dsum = (h.get("sum", 0.0) - ph.get("sum", 0.0)
                if count >= pcount else h.get("sum", 0.0))
        hists[name] = {"per_s": dcount / dt, "mean": dsum / dcount}
    return {"dt_s": dt, "t": cur.get("t"), "counters": counters,
            "histograms": hists, "gauges": dict(cur.get("gauges", {}))}


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str) -> Histogram:
    return REGISTRY.histogram(name)


# --------------------------------------------------------------------------
# The training loops' instruments: step timer, JSONL metrics, profiler
# --------------------------------------------------------------------------

class StepTimer:
    def __init__(self, capacity: int = 1024):
        self._durations: list[float] = []
        self._capacity = capacity
        self._t0: float | None = None

    def __enter__(self) -> "StepTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        assert self._t0 is not None
        self.record(time.perf_counter() - self._t0)

    def record(self, duration_s: float) -> None:
        self._durations.append(duration_s)
        if len(self._durations) > self._capacity:
            del self._durations[:-self._capacity]

    @property
    def count(self) -> int:
        return len(self._durations)

    def percentile(self, q: float) -> float:
        if not self._durations:
            return float("nan")
        ordered = sorted(self._durations)
        idx = min(len(ordered) - 1, int(q / 100.0 * len(ordered)))
        return ordered[idx]

    def summary(self) -> dict[str, float]:
        if not self._durations:
            return {"count": 0}
        return {
            "count": len(self._durations),
            "mean_s": sum(self._durations) / len(self._durations),
            "p50_s": self.percentile(50),
            "p95_s": self.percentile(95),
            "last_s": self._durations[-1],
        }


class MetricsLogger:
    """Append-only JSONL metrics stream (path=None: in-memory only)."""

    def __init__(self, path: str | None = None):
        self._path = path
        self._records: list[dict] = []
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

    def log(self, **fields: Any) -> dict:
        record = {"t": time.time(), **fields}
        self._records.append(record)
        if self._path:
            with open(self._path, "a") as f:
                f.write(json.dumps(record, default=float) + "\n")
        return record

    @property
    def records(self) -> list[dict]:
        return list(self._records)

    def latest(self, metric: str) -> Any:
        for record in reversed(self._records):
            if metric in record:
                return record[metric]
        return None


@contextlib.contextmanager
def profile_trace(name: str = "train",
                  trace_dir: str | None = None) -> Iterator[None]:
    """TPU timeline capture via jax.profiler; no-op unless a directory is
    given or PSDT_TRACE_DIR is set."""
    trace_dir = trace_dir or os.environ.get("PSDT_TRACE_DIR")
    if not trace_dir:
        yield
        return
    import jax

    with jax.profiler.trace(os.path.join(trace_dir, name)):
        yield


def samples_per_sec(batch_size: int, step_time_s: float,
                    num_chips: int = 1) -> float:
    if step_time_s <= 0:
        return float("nan")
    return batch_size / step_time_s / max(1, num_chips)
