"""Distributed trace spans with wire-level propagation.

A *trace* is one logical operation across the cluster (e.g. one training
step: worker pull -> compute -> push -> PS apply -> barrier); a *span* is
one timed piece of it in one thread of one process.  Spans carry
(trace_id, span_id, parent_id); the current span rides a thread-local
stack, and crosses process boundaries as a ``b"trace_id/span_id"`` blob in
a high-numbered extension field of the RPC request messages
(rpc/messages.py — reference protoc gencode skips unknown fields, so
reference C++ peers are unaffected; proven by tests/test_wire_interop.py).

Recording is OFF by default: ``span()`` costs one truthiness check when
disabled, so instrumentation can stay unconditionally in hot paths.
Enable with :func:`enable`, ``PSDT_TRACE=1``, or ``PSDT_TRACE_FILE=path``
(the latter also registers an atexit Chrome-trace dump, ``%d`` in the path
expands to the pid — how multi-process cluster runs each drop their slice;
:func:`merge_chrome_traces` stitches the slices into one file that renders
in ``chrome://tracing`` / Perfetto with a shared trace id per step).

**Clock.**  A span's ``ts`` is ``time.time()`` at its opening: the Unix
clock, in seconds.  ``dur`` is a difference of that clock for ``span`` /
``server_span`` / ``SpanHolder`` and of ``time.perf_counter()`` for
:class:`timed` (which times its block once for the histogram and the
span).

**One timeline with the profiler.**  While recording is on and ``jax`` is
ALREADY imported in the process, every span also holds a
``jax.profiler.TraceAnnotation("psdt/<span name>")`` open for its
lifetime, on the thread that does the work: a ``jax.profiler`` trace taken
at the same time (``PSDT_TRACE_DIR``) then shows the program's spans on
the profiler's own clock beside the device operations.  A process that
never imports JAX (parameter server, coordinator) pays nothing and is
never made to import it.  ``jax.profiler.ProfileData`` counts an event's
``start_ns`` from the start of the profiler session (the trace's own
``profile_start_time``, on the Unix clock), so ``ts - start_ns * 1e-9`` is
one constant per session: over 200 spans of one session on the v5e it
held to 7 us, and an annotation outlasts its span's ``dur`` by 5 us
(PERF.md section 6, PR 24).
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Any, Iterator

_BUFFER_MAX = 200_000  # spans kept per process (oldest dropped)

_enabled = False
_buffer: deque = deque(maxlen=_BUFFER_MAX)
_lock = threading.Lock()
_tls = threading.local()


def enable(on: bool = True) -> None:
    """Turn span recording on/off process-wide."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def _new_id() -> str:
    return os.urandom(8).hex()


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _mirror(name: str):
    """An open ``psdt/<name>`` annotation on the profiler's timeline, or
    None where this process has not imported JAX (see module docstring).
    Only called while recording is on."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)  # None mid-import too
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation("psdt/" + name)
    annotation.__enter__()
    return annotation


def _open(name: str, args: dict, remote: tuple[str, str] | None = None):
    """Push a new span onto this thread's stack (child of ``remote`` when
    given, else of the innermost open span, else the root of a fresh
    trace) and open its mirror.  A span without an ``iteration`` of its
    own takes its parent's, so that the legs opened deep in the data
    plane (rings, codec) can be matched to the step they served."""
    stack = _stack()
    if remote is not None:
        trace_id, parent_id = remote
    elif stack:
        trace_id, parent_id = stack[-1][0], stack[-1][1]
    else:
        trace_id, parent_id = _new_id(), ""
    if "iteration" not in args and stack and stack[-1][2] is not None:
        args["iteration"] = stack[-1][2]
    span_id = _new_id()
    stack.append((trace_id, span_id, args.get("iteration")))
    return trace_id, span_id, parent_id, _mirror(name)


def _close(name: str, opened, t0: float, dur: float, args: dict) -> None:
    trace_id, span_id, parent_id, annotation = opened
    if annotation is not None:
        annotation.__exit__(None, None, None)
    stack = _stack()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][1] == span_id:
            # the top, unless a span opened inside this one was left open
            # by an exception: that one goes with it
            del stack[i:]
            break
    _record(name, trace_id, span_id, parent_id, t0, dur, args)


def current() -> tuple[str, str] | None:
    """(trace_id, span_id) of the innermost open span on this thread."""
    stack = _stack()
    return (stack[-1][0], stack[-1][1]) if stack else None


def wire_context() -> bytes:
    """Current span serialized for the RPC extension field (empty bytes
    when tracing is off or no span is open — proto3 elides the field, so
    the wire bytes are identical to an uninstrumented build)."""
    if not _enabled:
        return b""
    ctx = current()
    return f"{ctx[0]}/{ctx[1]}".encode("ascii") if ctx else b""


def parse_context(raw: bytes | str) -> tuple[str, str] | None:
    """Inverse of :func:`wire_context`; None on empty/garbage (a peer that
    does not trace simply leaves the field at its default)."""
    if not raw:
        return None
    try:
        text = raw.decode("ascii") if isinstance(raw, (bytes, bytearray,
                                                       memoryview)) else raw
        trace_id, _, span_id = text.partition("/")
        if len(trace_id) == 16 and len(span_id) == 16:
            return trace_id, span_id
    except (UnicodeDecodeError, ValueError):
        pass
    return None


def _record(name: str, trace_id: str, span_id: str, parent_id: str,
            t0: float, dur: float, args: dict | None) -> None:
    span = {"name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "pid": os.getpid(),
            "tid": threading.get_ident(), "ts": t0, "dur": dur}
    if args:
        span["args"] = args
    with _lock:
        _buffer.append(span)


@contextlib.contextmanager
def span(name: str, **args: Any) -> Iterator[None]:
    """Record one span; nests under the thread's current span (same trace)
    or roots a fresh trace.  No-op when tracing is disabled."""
    if not _enabled:
        yield
        return
    opened = _open(name, args)
    t0 = time.time()
    try:
        yield
    finally:
        _close(name, opened, t0, time.time() - t0, args)


class timed:
    """Time a block ONCE for both records: the histogram (always, when one
    is given) and the span ``name`` (while recording is on).

    >>> with timed("worker/pack", hist, bytes=n):
    ...     pack()

    :meth:`carve` takes a leg OUT of the block: the time spent inside the
    carved leg (entered any number of times) is summed into one record of
    its own and subtracted from this block's, so that the two add up to
    the block's wall time.  That is how a ring frame splits into moving
    bytes and being blocked on the peer without one span per probe.  In
    the span buffer the carved leg is laid first and the block's own time
    after it, both inside the block's real interval (durations exact,
    order inside the block not kept); on the profiler's timeline the
    block's annotation stays open and each entry of the carved leg nests
    in it where it happened."""

    __slots__ = ("_name", "_hist", "args", "_opened", "_ts", "_t0",
                 "_carved")

    def __init__(self, name: str, hist=None, **args: Any):
        self._name = name
        self._hist = hist
        self.args = args          # may be added to until the block ends
        self._carved: _Carved | None = None

    def __enter__(self) -> "timed":
        if _enabled:
            self._opened = _open(self._name, self.args)
            self._ts = time.time()
        else:
            self._opened = None
        self._t0 = time.perf_counter()
        return self

    def carve(self, name: str, hist=None) -> "_Carved":
        self._carved = _Carved(name, hist)
        return self._carved

    def __exit__(self, *exc) -> None:
        own = time.perf_counter() - self._t0
        carved = self._carved
        out = carved.total if carved is not None else 0.0
        own -= out
        if self._hist is not None:
            self._hist.observe(own)
        if out and carved.hist is not None:
            carved.hist.observe(out)
        if self._opened is None:
            return
        if out:
            # a sibling of the block's own span: same parent, same args
            _record(carved.name, self._opened[0], _new_id(),
                    self._opened[2], self._ts, out, self.args)
        _close(self._name, self._opened, self._ts + out, own, self.args)


class _Carved:
    """The leg a :class:`timed` block carves out of itself; re-enterable."""

    __slots__ = ("name", "hist", "total", "_t0", "_annotation")

    def __init__(self, name: str, hist):
        self.name = name
        self.hist = hist
        self.total = 0.0

    def __enter__(self) -> "_Carved":
        self._annotation = _mirror(self.name) if _enabled else None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.total += time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)


class phases:
    """A block cut into spans that follow one another without a gap, on
    one thread, under one parent: the first is named here, and
    :meth:`next` ends the one that is open and names the one that begins
    at the same instant.

    >>> with phases("rpc/round/send") as phase:
    ...     send()
    ...     phase.next("rpc/round/turn")
    ...     wait()

    A cut costs ONE clock read where it is made; the spans are recorded
    when the block ends.  So a cut may stand where the thread has no
    time to lose: between two ring reads while a dozen other threads want
    the interpreter's lock, a span closed and another opened (two ids,
    two annotations, the buffer's lock) is enough Python for the thread
    to be caught outside the ring's wait it was heading for, and the
    wait's time then lies under no leg (PERF.md section 6, PR 52).  The
    price: the phases are not pushed on the thread's stack, so what the
    block opens keeps the parent it would have had (the phases hold it in
    TIME, not by id), and they have no mirror on the profiler's timeline.
    They are children of the span open when the block ends and take its
    ``iteration``."""

    __slots__ = ("_names", "_cuts", "args")

    def __init__(self, name: str, **args: Any):
        self._names = [name]
        self._cuts: list[float] | None = None
        self.args = args

    def __enter__(self) -> "phases":
        if _enabled:
            self._cuts = [time.time()]
        return self

    def next(self, name: str) -> None:
        if self._cuts is not None:
            self._names.append(name)
            self._cuts.append(time.time())

    def __exit__(self, *exc) -> None:
        cuts = self._cuts
        if cuts is None:
            return
        cuts.append(time.time())
        stack = _stack()
        trace_id, parent_id, iteration = (stack[-1] if stack
                                          else (_new_id(), "", None))
        if iteration is not None:
            self.args.setdefault("iteration", iteration)
        for name, t0, t1 in zip(self._names, cuts, cuts[1:]):
            _record(name, trace_id, _new_id(), parent_id, t0, t1 - t0,
                    self.args)


@contextlib.contextmanager
def attach(ctx: tuple[str, str] | None) -> Iterator[None]:
    """Make ``ctx`` (a :func:`current` result captured on ANOTHER thread)
    this thread's innermost span, without recording a span of its own.
    The span stack is thread-local, so work handed to a pool (e.g. the
    sharded-PS fan-out) would otherwise root fresh traces instead of
    nesting under the caller's push/pull span.  No-op for None/disabled."""
    if not _enabled or ctx is None:
        yield
        return
    stack = _stack()
    stack.append((ctx[0], ctx[1], None))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def server_span(name: str, ctx: bytes | str, **args: Any) -> Iterator[None]:
    """Server-side span adopting a REMOTE parent from the request's wire
    context: the handler's work joins the caller's trace.  Falls back to
    :func:`span` semantics when the context is absent/unparseable."""
    if not _enabled:
        yield
        return
    opened = _open(name, args, remote=parse_context(ctx))
    t0 = time.time()
    try:
        yield
    finally:
        _close(name, opened, t0, time.time() - t0, args)


class SpanHolder:
    """Deferred-context server span for CLIENT-STREAMING handlers: the
    remote parent arrives on the first request chunk, after the handler
    already started.  Construct at handler entry (stamps t0), call
    :meth:`adopt` as chunks arrive (first parseable context wins — it is
    pushed onto the thread's span stack, with the chunk's ``iteration``
    where the caller has one, so spans the handler opens later, e.g.
    ``ps/apply`` after draining a streamed push, join the caller's trace
    and the ring and codec legs of the handler's thread know their round
    as the worker's do), and :meth:`finish` on the way out.  What the
    thread did before the context arrived (the ring's wait for the
    round's first frame) keeps no iteration.  adopt/finish must run on
    the handler's thread (they do: gRPC drains the request iterator inside
    the handler call)."""

    __slots__ = ("name", "args", "_t0", "_span_id", "_trace_id",
                 "_parent_id", "_pushed", "_annotation")

    def __init__(self, name: str, **args: Any):
        self.name = name
        self.args = args
        self._t0 = time.time() if _enabled else 0.0
        self._span_id = _new_id() if _enabled else ""
        self._annotation = _mirror(name) if _enabled else None
        self._trace_id: str | None = None
        self._parent_id = ""
        self._pushed = False

    def adopt(self, ctx: bytes | str, iteration: int | None = None) -> None:
        if not _enabled or self._pushed:
            return
        parsed = parse_context(ctx)
        if parsed is None:
            return
        self._trace_id, self._parent_id = parsed
        if iteration is not None:
            self.args.setdefault("iteration", iteration)
        _stack().append((self._trace_id, self._span_id,
                         self.args.get("iteration")))
        self._pushed = True

    def finish(self) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if not _enabled:
            return
        if self._pushed:
            stack = _stack()
            if stack and stack[-1][1] == self._span_id:
                stack.pop()
            self._pushed = False
        _record(self.name, self._trace_id or _new_id(), self._span_id,
                self._parent_id, self._t0, time.time() - self._t0,
                self.args)


# ----------------------------------------------------------------- export
def spans() -> list[dict]:
    """Snapshot of the recorded spans (oldest first)."""
    with _lock:
        return list(_buffer)


def clear() -> None:
    with _lock:
        _buffer.clear()


def chrome_trace_events(recorded: list[dict] | None = None) -> list[dict]:
    """Spans -> Chrome-trace (catapult) complete events: ``ph="X"``,
    microsecond ``ts``/``dur``, pid/tid lanes.  The trace/span ids ride in
    ``args`` so Perfetto's query/filter view can group one distributed
    step across processes by ``trace_id``."""
    events = []
    for s in (spans() if recorded is None else recorded):
        events.append({
            "name": s["name"], "ph": "X", "cat": "psdt",
            "ts": s["ts"] * 1e6, "dur": max(s["dur"], 1e-7) * 1e6,
            "pid": s["pid"], "tid": s["tid"],
            "args": {"trace_id": s["trace_id"], "span_id": s["span_id"],
                     "parent_id": s["parent_id"], **s.get("args", {})},
        })
    return events


def export_chrome_trace(path: str,
                        recorded: list[dict] | None = None) -> str:
    """Write this process's spans as a Chrome-trace JSON file; returns the
    path.  Open in chrome://tracing or https://ui.perfetto.dev."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"traceEvents": chrome_trace_events(recorded),
                   "displayTimeUnit": "ms"}, fh)
    return path


def merge_chrome_traces(paths: list[str], out_path: str) -> str:
    """Concatenate several per-process Chrome-trace files (written by
    :func:`export_chrome_trace` / PSDT_TRACE_FILE) into one.  Events keep
    their pid lanes; spans of one step stay correlated by args.trace_id."""
    events: list[dict] = []
    for path in paths:
        with open(path) as fh:
            doc = json.load(fh)
        events.extend(doc["traceEvents"] if isinstance(doc, dict) else doc)
    events.sort(key=lambda e: e.get("ts", 0.0))
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return out_path


# Env wiring: PSDT_TRACE=1 records; PSDT_TRACE_FILE=path also dumps at
# process exit (the zero-code path for real multi-process cluster runs).
if os.environ.get("PSDT_TRACE", "").lower() in ("1", "true", "yes"):
    enable()
_TRACE_FILE = os.environ.get("PSDT_TRACE_FILE", "")
if _TRACE_FILE:
    enable()
    atexit.register(
        lambda: export_chrome_trace(
            _TRACE_FILE.replace("%d", str(os.getpid()))))

    def _dump_on_sigterm(signum, frame):
        # servers (PS/coordinator) normally die by SIGTERM, which skips
        # atexit — without this their halves of every cross-process trace
        # vanish.  Only claims the signal when nobody else has a handler.
        export_chrome_trace(_TRACE_FILE.replace("%d", str(os.getpid())))
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    try:
        if signal.getsignal(signal.SIGTERM) is signal.SIG_DFL:
            signal.signal(signal.SIGTERM, _dump_on_sigterm)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass
