#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object.  Exits
non-zero, with no result line, when JAX offers no TPU or fewer chips than
the cell asks for.  ``--rehearse`` (not used by the driver) runs the same
path on the CPU at a tiny size and prints a line that is not a result.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    started = harness.process_start_time()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="CPU, tiny sizes, prints no result")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(
            harness.CHECKOUT, "parameter_server_distributed_tpu")):
        harness.fail("the program (parameter_server_distributed_tpu/) is "
                     "not in this checkout: nothing to measure")
    benchmark, cell, config, traffic = harness.load_cell(args.workload)
    seconds = (float(benchmark["run_seconds"]) if args.seconds is None
               else args.seconds)
    setup = harness.Setup(started)

    import jax

    cache_dir = harness.enable_compile_cache()
    compile_log = harness.CompileLog()
    job = importlib.import_module(f"perfbench.jobs.{traffic['job']}")
    if args.rehearse:
        config, traffic = job.shrink(config, traffic)
    setup.mark("imports")
    devices = jax.devices()
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu"
                              or len(devices) < cell["chips"]):
        harness.fail(f"cell {cell['name']} needs {cell['chips']} TPU "
                     f"chip(s); JAX offers {len(devices)} x {platform}")
    devices = devices[:cell["chips"]]
    # touch the chip(s): runtime and device start-up are a part of set-up
    jax.block_until_ready([jax.device_put(0, d) for d in devices])
    setup.mark("runtime")
    harness.say(detail="start", workload=cell["name"], seed=args.seed,
                seconds=seconds, trace=args.trace,
                compile_cache_dir=cache_dir,
                compile_cache_entries=len(os.listdir(cache_dir))
                if os.path.isdir(cache_dir) else 0,
                platform=platform, device_kind=devices[0].device_kind)

    ctx = harness.Context(
        benchmark=benchmark, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearsal=args.rehearse, setup=setup, compile_log=compile_log,
        devices=devices)
    with ctx.memory:
        result = job.run(ctx)

    opened, closed = ctx.window
    in_setup = compile_log.between(started, opened)
    in_window = compile_log.between(opened, closed)
    setup_s = opened - started
    harness.say(detail="setup", setup_s=setup_s, parts=setup.parts,
                programs_in_setup=in_setup, programs_in_window=in_window)
    harness.say(detail="memory", **ctx.memory.parts())
    checks = dict(result["checks"])
    checks["no_compilation_in_window"] = in_window["programs"] == 0
    correct = bool(checks["logits"]["ok"] and result["failed"] == 0 and all(
        value for value in checks.values() if isinstance(value, bool)))
    harness.say(detail="checks", **checks)

    traced = result.get("traced")
    if args.trace:
        metrics = harness.read_per_layer(benchmark, cell,
                                         result["observed"])
    else:
        metrics = {}
        values = dict(result["end_to_end"], setup_s=setup_s)
        for metric in harness.metrics_of(benchmark, cell, "end_to_end"):
            if metric["name"] in values:
                metrics[metric["name"]] = {
                    "value": float(values[metric["name"]]),
                    "unit": metric["unit"]}
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": harness.device_report(devices, traced,
                                            ctx.memory.peak())}
    if traced:
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    if args.rehearse:
        line = {"rehearsal": True, "not_a_result": line}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
