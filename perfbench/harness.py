"""What every cell needs: the files it is made of, the set-up clock, the
compile log, the device check, the profiler window and the result line.

The harness is driven by data.  ``BENCHMARK.json`` names a cell's
configuration and traffic; ``perfbench/configs/<config>.json`` and
``perfbench/traffic/<traffic>.json`` hold them; the traffic file's ``job``
names the module under ``perfbench/jobs/`` that drives the program; each
per-layer metric has ``perfbench/metrics/<name>.json`` naming a reader under
``perfbench/readers/``.  Nothing here lists cells, jobs or metrics.
"""

from __future__ import annotations

import contextlib
import copy
import importlib
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def process_start_time() -> float:
    """Wall time at which this process was created, from /proc; the time of
    this call where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        boot = time.time() - uptime
        return boot + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) for a workload's name."""
    benchmark = load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = load_json(os.path.join(CHECKOUT, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    return benchmark, cell, config, traffic


def metrics_of(benchmark: dict, cell: dict, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that this
    cell reports: those with no ``workloads`` key, or that list the cell."""
    return [m for m in benchmark[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


class Setup:
    """The set-up clock: seconds from process start, split into named
    parts that add up to ``setup_s``."""

    def __init__(self, started: float):
        self.started = started
        self._last = started
        self.parts: dict[str, float] = {}

    def mark(self, part: str, now: float | None = None) -> None:
        now = time.time() if now is None else now
        self.parts[part] = self.parts.get(part, 0.0) + (now - self._last)
        self._last = now


class CompileLog:
    """Every backend compilation and persistent-cache hit of the process,
    with its wall time, from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles: list[tuple[float, float, str]] = []
        self.cache_hits: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, seconds: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.time(), seconds,
                                  str(kwargs.get("fun_name", "?"))))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits.append(time.time())

    def between(self, start: float, end: float) -> dict:
        """Programs built between two wall times.  A cache hit still passes
        through the backend-compile event (it times the load), so what was
        really compiled is builds less hits."""
        builds = [(s, n) for t, s, n in self.compiles if start <= t < end]
        hits = sum(1 for t in self.cache_hits if start <= t < end)
        return {"programs": len(builds), "cache_hits": hits,
                "compiled": len(builds) - hits,
                "build_s": sum(s for s, _ in builds),
                "slowest": [n for _, n in sorted(builds, reverse=True)[:4]]}


class MemorySampler:
    """The most bytes each chip held while the cell ran, from the
    runtime's ``memory_stats()`` polled 20 times a second.  The runtime
    keeps two accounts: ``bytes_in_use`` (buffers: weights, caches,
    batches, results) and ``bytes_reserved`` (what it sets aside for the
    temporaries of the programs it runs), each with a peak of its own.
    ``peak()`` is the most the two held together at one poll, and no less
    than either peak alone."""

    def __init__(self, devices):
        self._devices = devices
        self._buffers = 0
        self._reserved = 0
        self._together = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True,
                                        name="perfbench-memory")

    def _sample(self) -> None:
        for device in self._devices:
            stats = device.memory_stats() or {}
            in_use = stats.get("bytes_in_use", 0)
            reserved = stats.get("bytes_reserved", 0)
            self._buffers = max(self._buffers, in_use,
                                stats.get("peak_bytes_in_use", 0))
            self._reserved = max(self._reserved, reserved,
                                 stats.get("peak_bytes_reserved", 0))
            self._together = max(self._together, in_use + reserved)

    def _poll(self) -> None:
        while not self._stop.wait(0.05):
            self._sample()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self._sample()

    def parts(self) -> dict:
        self._sample()
        return {"buffers_peak_bytes": int(self._buffers),
                "reserved_peak_bytes": int(self._reserved),
                "together_peak_bytes": int(self._together),
                "limit_bytes": int((self._devices[0].memory_stats() or {})
                                   .get("bytes_limit", 0))}

    def peak(self) -> int:
        parts = self.parts()
        return max(parts["buffers_peak_bytes"], parts["reserved_peak_bytes"],
                   parts["together_peak_bytes"])


class Context:
    """One run of one cell."""

    def __init__(self, *, benchmark, cell, config, traffic, seed, seconds,
                 trace, rehearsal, setup, compile_log, devices):
        self.benchmark = benchmark
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearsal = rehearsal
        self.setup = setup
        self.compile_log = compile_log
        self.devices = devices
        self.memory = MemorySampler(devices)
        self.window: tuple[float, float] | None = None
        self.trace_dir = os.path.join(CHECKOUT, ".perfbench_trace",
                                      cell["name"])
        self._tracing = False
        self._stopper: threading.Thread | None = None

    # ---------------------------------------------------------- window
    def open_window(self, now: float | None = None) -> float:
        """The end of set-up.  Returns the wall time of the opening."""
        now = time.time() if now is None else now
        self.setup.mark("warmup", now)
        self.window = (now, now)
        return now

    def close_window(self, now: float | None = None) -> None:
        now = time.time() if now is None else now
        self.window = (self.window[0], now)

    # -------------------------------------------------------- profiler
    def start_trace(self) -> None:
        if not self.trace or self._tracing:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True

    def stop_trace(self) -> None:
        """Stop the profiler on a thread of its own, so that the loop that
        asked does not stall while the trace is written."""
        if not self._tracing or self._stopper is not None:
            return
        import jax

        self._stopper = threading.Thread(target=jax.profiler.stop_trace,
                                         name="perfbench-trace-stop")
        self._stopper.start()

    def finish_trace(self) -> dict | None:
        """Wait for the trace and reduce it; None when none was taken or
        it holds no device operation."""
        from . import reduce

        if not self._tracing:
            return None
        self.stop_trace()
        self._stopper.join()
        path = reduce.find_xplane(self.trace_dir)
        reduced = reduce.reduce_trace(reduce.load_xplane(path)) if path \
            else None
        if not os.environ.get("PERFBENCH_KEEP_TRACE"):
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return reduced

    def annotate(self, name: str):
        """A host span on the profiler's clock (``bench/<name>``), free
        when no trace is being taken."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench/{name}")


def tiny(config: dict, traffic: dict) -> tuple[dict, dict]:
    """Copies of a configuration and a traffic file at sizes a CPU runs in
    seconds (``run.py --rehearse``): what every job's ``shrink`` starts
    from.  The control flow stays the cell's, the sizes do not."""
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config.update(n_embd=64, n_head=2, n_layer=2, n_positions=128,
                  n_ctx=128, vocab_size=512)
    config["assumed"].update(dtype="float32", loss_chunk=32)
    traffic["check"].update(sequences=2, tokens=32)
    if "batch_size" in traffic:
        traffic["batch_size"] = 4
    return config, traffic


def say(**fields) -> None:
    """One line of detail before the result line."""
    print(json.dumps(fields, default=float), flush=True)


def enable_compile_cache() -> str:
    """The persistent compilation cache, before the program's first jit:
    where ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``
    (the program's own choice, made by its own function), and with no floor
    on compile time or size, so that a program's presence in the cache never
    depends on how long a cold run happened to take."""
    import jax

    from parameter_server_distributed_tpu.utils.compile_cache import (
        enable_compile_cache as program_cache)

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_report(devices, trace_reduced: dict | None,
                  memory_peak_bytes: int) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak_bytes}
    if trace_reduced:
        out["busy_s"] = trace_reduced["busy_s"]
        out["window_s"] = trace_reduced["window_s"]
    return out


def read_per_layer(benchmark: dict, cell: dict, observed: dict) -> dict:
    """Every per-layer metric of the cell through its own reader; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    for metric in metrics_of(benchmark, cell, "per_layer"):
        spec = load_json(os.path.join(HERE, "metrics",
                                      f"{metric['name']}.json"))
        reader = importlib.import_module(
            f"perfbench.readers.{spec['reader']}")
        value = reader.read(observed, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def fail(message: str) -> "NoReturn":  # noqa: F821
    print(f"perfbench: {message}", file=sys.stderr, flush=True)
    raise SystemExit(1)
