"""A slow leg keeps its evidence.

The serving thread is always inside one of a few *legs* while a request
waits (models/serving.py: the caller's time between two rounds, a round's
host and device legs, an admission's five).  A stall of the serving loop is
one leg that took far longer than it does as a rule; it comes in one run of
twenty, so nobody is watching when it does, and a histogram's far bucket
says that it happened and not why.  This module is the rule that keeps the
why: a leg whose own time is over :data:`SLOW_LEG_S` is a **slow leg**, and
at its end the program writes down what the thread and the process did
meanwhile (:meth:`SlowLegs._keep`), into four places that were there
already (a bounded list, the log, the flight ring, the span buffer) and
five counters that read 0 while all is well.

What is read at every leg's boundary is a :meth:`SlowLegs.mark`: two
clocks and four numbers the process counts anyway.  Everything dearer (the
thread's ``getrusage``, the device's ``memory_stats()``) is read on entry to
``submit`` / a round (:meth:`SlowLegs.enter`) or after a slow leg, which is
rare.  ``obs.trace.timed`` is used as it is, not changed: :meth:`SlowLegs.leg`
wraps one.

How the evidence reads (docs/observability.md, "Reading a slow leg"):

================================================  ==========================
device wait high, CPU low                         the runtime or the device:
                                                  lay ``at`` on a kept trace
CPU near wall, ``gc_s`` near wall                 the collector
CPU near wall, ``gc_s`` low, leg ``admit/tree``   the tree (an eviction pass,
or ``admit/lookup``                               a 61k-token key)
CPU low OUTSIDE the device legs, with switches    the thread lost the
                                                  processor or the
                                                  interpreter's lock
================================================  ==========================
"""

from __future__ import annotations

import collections
import gc
import json
import logging
import time
from typing import Any, Callable, Iterable

from . import flight
from . import stats as obs_stats
from . import trace as obs_trace

try:  # the thread's own switches and faults: Linux
    import resource

    _RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
except ImportError:  # pragma: no cover - no such platform in the tests
    resource = None
    _RUSAGE_THREAD = None

log = logging.getLogger(__name__)

# A leg's own time over this is a slow leg.  0.1 s is the gap limit of
# ``serve.slo_ok_pct`` in two of the benchmark's serving cells (80 ms in
# the other two; perfbench/traffic/*.json ``slo.itl_ms``): one such leg
# alone makes every live lane miss its limit, whatever the rounds around
# it did.  The slowest leg of a healthy run (an admission's blocked fetch
# behind a round in flight: 95th percentile 6 ms serving GPT-2, 41 ms
# serving SmallThinker behind 12,288-token prefixes; PERF.md, PR 38) stays
# under it.
SLOW_LEG_S = 0.1
KEPT = 64   # slow legs a server keeps (the newest)

# the caller's time between two rounds, less the admissions inside it
CALLER = "serve/caller"
# the two legs in which the host waits for the chip
DEVICE_LEGS = frozenset({"serve/round/device", "serve/admit/first_token"})

COUNTERS = ("serve.slow_legs", "serve.slow_leg_s", "serve.slow_leg_cpu_s",
            "serve.slow_leg_gc_s", "serve.slow_leg_device_wait_s")

# ----------------------------------------------------------- the collector
# seconds inside the garbage collector and collections by generation,
# process-wide, from a gc.callbacks pair: nothing runs until a collection
# does.  A collection runs on whichever thread allocated last, holding the
# interpreter's lock, so it is every thread's time.
_gc_seconds = 0.0
_gc_collections = [0, 0, 0]
_gc_started: float | None = None
_gc_hist: obs_stats.Histogram | None = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_seconds, _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
    elif _gc_started is not None:
        took = time.perf_counter() - _gc_started
        _gc_started = None
        _gc_seconds += took
        _gc_collections[info["generation"]] += 1
        _gc_hist.observe(took)


def watch_gc() -> None:
    """Time every collection of this process into the always-on histogram
    ``proc.gc_s`` (idempotent; the histogram is looked up anew, so a
    registry cleared by a test gets it back with the next server)."""
    global _gc_hist
    _gc_hist = obs_stats.histogram("proc.gc_s")
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _thread_usage() -> tuple[int, int, int]:
    """(involuntary switches, voluntary switches, major faults) of the
    calling thread so far; zeros where the platform does not say."""
    if _RUSAGE_THREAD is None:
        return (0, 0, 0)
    usage = resource.getrusage(_RUSAGE_THREAD)
    return (usage.ru_nivcsw, usage.ru_nvcsw, usage.ru_majflt)


class _Watched:
    """A context manager's block as a watched leg: a mark on either side
    (kept, so that an enclosing leg can take this one out of its own
    time), the rule at its end."""

    __slots__ = ("_watch", "_name", "_inner", "start", "end")

    def __init__(self, watch: "SlowLegs", name: str, inner):
        self._watch = watch
        self._name = name
        self._inner = inner
        self.start = self.end = None

    def __enter__(self):
        self.start = self._watch.mark()
        return self._inner.__enter__()

    def __exit__(self, *exc) -> None:
        self._inner.__exit__(*exc)
        self.end = self._watch.mark()
        self._watch.over(self._name, self.start, self.end,
                         **getattr(self._inner, "args", {}))

    @property
    def taken(self) -> tuple:
        """What an enclosing leg subtracts: this leg's (start, end), once
        it has run."""
        return ((self.start, self.end),) if self.end is not None else ()


class SlowLegs:
    """The slow legs of one serving thread.

    ``held`` is asked once after each slow leg for what the server held
    (slots, the admission in hand, the device's memory): a dict merged into
    the record."""

    def __init__(self, held: Callable[[], dict]):
        self._held = held
        self.records: collections.deque = collections.deque(maxlen=KEPT)
        # made here, so that they read 0 and are never absent
        self._counters = {name: obs_stats.counter(name) for name in COUNTERS}
        self._programs = obs_stats.counter("serve.programs")
        watch_gc()
        self._usage = _thread_usage()

    # ------------------------------------------------------- every leg
    def mark(self) -> tuple:
        """A leg's boundary: (wall, the thread's CPU time, seconds in the
        collector, collections of generation 0, 1, 2, programs built)."""
        return (time.perf_counter(), time.thread_time(), _gc_seconds,
                *_gc_collections, self._programs.value)

    def enter(self) -> tuple:
        """On entry to ``submit`` and to a round: the thread's switches and
        faults so far (what a slow leg's are counted from), and a mark."""
        self._usage = _thread_usage()
        return self.mark()

    def leg(self, name: str, hist=None, **args: Any) -> _Watched:
        """``obs.trace.timed(name, hist, **args)`` as a watched leg; the
        block's ``as`` target is the ``timed``, whose ``args`` may be added
        to until the block ends (they go into a slow leg's record too)."""
        return self.wrap(name, obs_trace.timed(name, hist, **args))

    def wrap(self, name: str, inner) -> _Watched:
        """Any context manager's block (a carved leg) as a watched leg."""
        return _Watched(self, name, inner)

    def over(self, name: str, start: tuple, end: tuple,
             less: Iterable[tuple] = (), **args: Any) -> None:
        """The rule: the leg from mark ``start`` to mark ``end``, less the
        (start, end) pairs of the legs inside it that are watched under
        their own names, is a slow leg if its own wall time is over
        :data:`SLOW_LEG_S`."""
        wall = end[0] - start[0]
        for inner_start, inner_end in less:
            wall -= inner_end[0] - inner_start[0]
        if wall > SLOW_LEG_S:
            self._keep(name, start, end, less, args)

    # ----------------------------------------------------- a slow leg
    def _keep(self, name: str, start: tuple, end: tuple,
              less: Iterable[tuple], args: dict) -> None:
        own = [b - a for a, b in zip(start, end)]
        for inner_start, inner_end in less:
            own = [x - (b - a) for x, a, b
                   in zip(own, inner_start, inner_end)]
        wall, cpu, gc_s, gen0, gen1, gen2, programs = own
        # (two clocks: a part never reads over the whole)
        cpu = min(max(cpu, 0.0), wall)
        gc_s = min(max(gc_s, 0.0), wall)
        usage = _thread_usage()
        switched = [now - was for was, now in zip(self._usage, usage)]
        self._usage = usage
        record = {
            "leg": name, "wall_s": wall, "cpu_s": cpu,
            # the leg's start on the Unix clock, which is the spans' clock
            "at": time.time() - (time.perf_counter() - start[0]),
            "gc_s": gc_s, "gc_collections": [gen0, gen1, gen2],
            "involuntary_switches": switched[0],
            "voluntary_switches": switched[1], "major_faults": switched[2],
            "programs_built": programs, **args, **self._held()}
        self.records.append(record)
        count = self._counters
        count["serve.slow_legs"].add()
        count["serve.slow_leg_s"].add(wall)
        count["serve.slow_leg_cpu_s"].add(cpu)
        count["serve.slow_leg_gc_s"].add(gc_s)
        if name in DEVICE_LEGS:
            count["serve.slow_leg_device_wait_s"].add(wall)
        log.warning("slow leg %s: %.3f s (cpu %.3f s, gc %.3f s) %s", name,
                    wall, cpu, gc_s, json.dumps(record, default=float))
        flight.record("serve.slow_leg", a=int(wall * 1e6), b=int(cpu * 1e6),
                      note=slow_leg_note(record))
        with obs_trace.span("serve/slow_leg", **record):
            pass


def slow_leg_note(record: dict) -> str:
    """A slow leg's evidence as the 48 bytes of a flight record's note
    (wall and CPU time ride in ``a`` and ``b``): the leg without its
    ``serve/``, milliseconds in the collector, involuntary/voluntary
    switches, major faults, prefix nodes evicted.  The dearest first: a
    note too long is cut at its end.
    ``obs.postmortem.decode_slow_leg`` is the inverse."""
    return (f"{record['leg'].removeprefix('serve/')} "
            f"gc={record['gc_s'] * 1e3:.0f} "
            f"cs={record['involuntary_switches']}"
            f"/{record['voluntary_switches']} "
            f"mf={record['major_faults']} ev={record.get('evicted', 0)}")
