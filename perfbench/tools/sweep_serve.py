"""Find the knee of a serving cell once, on the chip: one server, the
traffic file's mix offered at each of a list of rates for a short window,
and for each rate the tails, the backlog at the end and whether time to
first token climbed from the first half of the window to the second.

    chiprun -- python3 perfbench/tools/sweep_serve.py --workload serve_chat_gpt2m \
        --rates 2,4,8,12,16 --seconds 20 [--slots 64]

The knee is the highest rate at which the backlog at the end is no larger
than at the start (zero) and time to first token does not climb through
the window.  The result goes into the traffic file by hand, with the table
in PERF.md.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import harness, traffic_gen  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--slots", type=int, default=0)
    parser.add_argument("--seed", type=int, default=2147483747)
    args = parser.parse_args()
    started = harness.process_start_time()
    benchmark, cell, config, traffic = harness.load_cell(args.workload)
    if args.slots:
        traffic["server"]["slots"] = args.slots

    import jax

    harness.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.fail("the sweep is a measurement: it needs the chip")
    from perfbench.jobs import serve

    ctx = harness.Context(
        benchmark=benchmark, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=False, rehearsal=False,
        setup=harness.Setup(started), compile_log=harness.CompileLog(),
        devices=devices[:1])
    vocab = config["vocab_size"]
    systems = traffic_gen.system_prompts(traffic, vocab, args.seed)
    model, params, server = serve.build(ctx, systems)
    ctx.setup.mark("warmup")
    print(json.dumps({"slots": traffic["server"]["slots"],
                      "setup_parts": ctx.setup.parts}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = copy.deepcopy(traffic)
        mix["arrivals"]["rate_per_s"] = rate
        schedule = traffic_gen.serve_schedule(mix, vocab, args.seed,
                                              args.seconds, systems)
        opened = time.time()
        mark = time.time()
        loop = serve.offer(ctx, server, schedule, opened)
        closed = time.time()
        seen = serve.latencies(loop, schedule, opened, closed, args.seconds,
                               traffic["slo"])
        built = ctx.compile_log.between(mark, closed)
        compiles = f"{built['programs']} {built['slowest']}"
        row = {"rate_per_s": rate, "requests": len(schedule),
               "compiles": compiles, **seen["summary"]}
        print(json.dumps({k: (round(v, 2) if isinstance(v, float) else v)
                          for k, v in row.items()}), flush=True)
        # let what is still in flight finish before the next rate
        leftover = serve.Loop(ctx, server)
        leftover.live, leftover.pending = loop.live, loop.pending
        leftover.drive([], time.time(), time.time() + 60, float("inf"))
    peak = (devices[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(json.dumps({"memory_peak_gb": peak / 1e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
