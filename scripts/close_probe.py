#!/usr/bin/env python3
"""What the barrier close's sweep costs on this machine, and how wide it runs.

    chiprun -- python3 scripts/close_probe.py [--tree DIR] [--micro 0|1] \
        [--workload ps_round_gpt2m --seed N --seconds S --trace 0|1] [--name OUT]

Two parts, both printed as JSON lines and written to
``chiprun_out/<name>.json``:

- ``micro`` (no JAX): ``usable_cores()``; one 100.7M-element float32 tensor
  copied into NEW memory (``np.array``) beside ``np.copyto`` into a touched
  buffer and a zeroed ``bytearray`` of its size; the native out-of-place
  Adam swept over five such arrays cut into k element ranges on k threads,
  and k threads each first-touching a range of fresh pages.
- the cell, run in this process by the tree's ``perfbench/run.py`` with the
  close watched: every ``ps.apply.parallelism`` the core set with the
  ``ps.apply.stripe_ms`` observations of that close and the counter
  ``ps.close.fresh_bytes`` as it then stood.

``--tree`` is the checkout to probe (default: the one this file lies in; any
tree from PR 39 on, whose kernels write a separate output), so that a parent
unpacked under ``.perfbench_copies/parent`` is read by the same script as the
change.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 100_663_296          # blocks/mlp/w1 of the scanned GPT-2 medium


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def timed(fn, repeat: int = 3) -> list[float]:
    out = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        out.append(round(time.perf_counter() - t0, 4))
    return out


def on_threads(k: int, fn) -> float:
    """Wall seconds of ``fn(i, k)`` on k threads at once."""
    threads = [threading.Thread(target=fn, args=(i, k)) for i in range(k)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return round(time.perf_counter() - t0, 4)


def micro() -> dict:
    import numpy as np

    from parameter_server_distributed_tpu import native
    from parameter_server_distributed_tpu.core.stripes import usable_cores

    cores = usable_cores()
    out: dict = {"usable_cores": cores, "cpu_count": os.cpu_count(),
                 "native_lib": native.lib() is not None}
    p = np.full(BIG, 0.5, np.float32)
    touched = np.zeros(BIG, np.float32)
    out["np_array_fresh_s"] = timed(lambda: np.array(p, np.float32))
    out["np_copyto_touched_s"] = timed(lambda: np.copyto(touched, p))
    out["bytearray_zeroed_s"] = timed(lambda: bytearray(4 * BIG), 2)

    def first_touch(k):
        fresh = np.empty(BIG, np.float32)

        def fill(i, k):
            a, b = BIG * i // k, BIG * (i + 1) // k
            fresh[a:b] = 1.0
        return on_threads(k, fill)

    ks = sorted({1, 2, 4, 8, cores})
    out["first_touch_s_by_threads"] = {k: first_touch(k) for k in ks}

    g = np.full(BIG, 1e-3, np.float32)
    m = np.zeros(BIG, np.float32)
    v = np.zeros(BIG, np.float32)

    def sweep(i, k):
        a, b = BIG * i // k, BIG * (i + 1) // k
        assert native.adam_native(p[a:b], g[a:b], m[a:b], v[a:b],
                                  touched[a:b], 1e-3, 0.9, 0.999, 1e-8, 1)

    if out["native_lib"]:
        on_threads(1, sweep)      # warm
        out["adam_out_of_place_s_by_threads"] = {
            k: min(on_threads(k, sweep) for _ in range(2)) for k in ks}
    return out


class _Watch:
    """Class-level taps on obs/stats: the two metrics' own objects only."""

    def __init__(self, stats):
        self.closes: list[dict] = []     # one entry a striped close
        pending: list[float] = []
        gauge = stats.gauge("ps.apply.parallelism")
        hist = stats.histogram("ps.apply.stripe_ms")
        fresh = stats.counter("ps.close.fresh_bytes")
        set_, observe = stats.Gauge.set, stats.Histogram.observe
        watch = self

        def tapped_set(self, v):
            if self is gauge:
                watch.closes.append({
                    "parallelism": v, "stripe_ms": pending[:],
                    "fresh_bytes": fresh.value})
                pending.clear()
            return set_(self, v)

        def tapped_observe(self, v):
            if self is hist:
                pending.append(round(v, 1))
            return observe(self, v)

        stats.Gauge.set = tapped_set
        stats.Histogram.observe = tapped_observe


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=HERE)
    parser.add_argument("--micro", type=int, default=1)
    parser.add_argument("--workload", default="")
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, default=1)
    parser.add_argument("--name", default="close_probe")
    parser.add_argument("--rehearse", action="store_true",
                        help="the cell on the CPU at a tiny size")
    args = parser.parse_args()
    tree = os.path.abspath(args.tree)
    os.chdir(tree)
    sys.path.insert(0, tree)
    report: dict = {"tree": tree}
    if args.micro:
        report["micro"] = micro()
        say(detail="micro", **report["micro"])
    if args.workload:
        from parameter_server_distributed_tpu.obs import stats

        watch = _Watch(stats)
        from perfbench import run as bench_run

        argv = ["--workload", args.workload, "--seed", str(args.seed),
                "--trace", str(args.trace)]
        if args.seconds is not None:
            argv += ["--seconds", str(args.seconds)]
        if args.rehearse:
            argv.append("--rehearse")
        rc = bench_run.main(argv)
        report["close"] = {"rc": rc, "closes": watch.closes}
        say(detail="close", **report["close"])
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.name}.json"), "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
