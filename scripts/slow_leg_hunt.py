#!/usr/bin/env python3
"""Runs of one serving cell with the flight recorder on, every slow leg of
every run kept beside that run's tail statistics (ISSUE 38).

    chiprun --timeout 3400 -- python3 scripts/slow_leg_hunt.py \
        --workload serve_docs_chat_smallthinker --name pr38_st_a \
        --plan P,C,C,P,Ct,Ct,Ck

The plan is a list of runs in order.  ``P`` runs the parent's tree
(``.perfbench_copies/parent``, unpacked there with ``git archive`` before the
call), ``C`` this tree, ``N`` a copy of it under ``.perfbench_copies/nopoll``
(a control: the benchmark's memory poll taken out by hand); a ``t`` after it asks for ``--trace 1`` (run through
``SHIM``, which keeps the two registry snapshots the serving job takes around
its window, so that the line holds every ``serve.*`` histogram's exact count
and sum over the window: ``window_sums``; recording stays off), a ``k`` for
a traced run with ``PSDT_TRACE_FILE=<file> PERFBENCH_KEEP_TRACE=1`` (recording
on, the spans dumped at exit) whose kept trace
``perfbench/tools/idle_by_span.py`` then reads (the chip's idle time by the
program's own legs) and whose spans inside the window are added up by name
(``span_sums``: a ``timed`` span's duration is its histogram's observation,
so these are the histograms' exact sums over the window).  A ``P`` and the ``C`` beside it share a seed; every
other run has one of its own.  Each run is a process of its own with
``PSDT_FLIGHT_DIR`` set to a fresh directory; this process never touches
JAX.  One JSON line a run goes to ``chiprun_out/<name>.jsonl`` and, cut
short, to standard output: the result line's metrics, the window's tail
statistics, and every slow leg (the log's records, and the flight ring's
through ``obs.postmortem.decode_slow_leg``) with whether it fell inside the
measured window.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from parameter_server_distributed_tpu.obs import postmortem  # noqa: E402

PARENT = os.path.join(REPO, ".perfbench_copies", "parent")
# a copy of this tree whose benchmark does not poll the device's memory
# (a control for what else runs in the process; made by hand before a call)
NO_POLL = os.path.join(REPO, ".perfbench_copies", "nopoll")
TREES = {"P": PARENT, "C": REPO, "N": NO_POLL}
WORK = os.path.join(REPO, ".perfbench_work", "hunt")
BASE_SEED = 2147483000     # the driver's seeds are large
TAIL = ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
        "itl_p99_ms", "slo_ok_pct", "sent", "unfinished", "rounds")


def seeds(plan: list[str], offset: int) -> list[int]:
    """One seed a run, but an untraced P and the untraced C next to it (the
    two sides of one comparison) share theirs."""
    out: list[int] = []
    fresh = BASE_SEED + 650 * offset
    waiting = None      # the side of a plain run that has no partner yet
    for run in plan:
        if len(run) == 1 and waiting not in (None, run):
            out.append(fresh)
            waiting = None
            continue
        fresh += 650
        out.append(fresh)
        waiting = run if len(run) == 1 else None
    return out


# ``perfbench/run.py`` as it is, but the registry snapshots the job takes
# before and after its window (``program.registry_snapshot``) are kept, and
# the growth of every ``serve.*`` histogram between them is written down:
# sums the result line does not carry.  Reads only; the benchmark's files
# are not touched.
SHIM = """
import json, os, runpy, sys
sys.path.insert(0, os.getcwd())
from perfbench import program
taken, snapshot = [], program.registry_snapshot
def keeping():
    taken.append(snapshot())
    return taken[-1]
program.registry_snapshot = keeping
sys.argv = ["perfbench/run.py"] + sys.argv[2:]
try:
    runpy.run_path("perfbench/run.py", run_name="__main__")
finally:
    if len(taken) >= 2:
        before, after = taken[0]["histograms"], taken[1]["histograms"]
        empty = {"count": 0, "sum": 0.0}
        with open(os.environ["HUNT_SUMS"], "w") as f:
            json.dump({name: [h["count"] - before.get(name, empty)["count"],
                              h["sum"] - before.get(name, empty)["sum"]]
                       for name, h in after.items()
                       if name.startswith(("serve.", "proc."))}, f)
"""


def span_sums(path: str, opened: float, closed: float) -> dict:
    """{span name: [count, seconds]} of the spans of a ``PSDT_TRACE_FILE``
    dump that began inside the window."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    out: dict[str, list] = {}
    for event in events:
        if opened <= event["ts"] * 1e-6 <= closed:
            held = out.setdefault(event["name"], [0, 0.0])
            held[0] += 1
            held[1] += event["dur"] * 1e-6
    return out


def process_start_time(pid: int, fallback: float) -> float:
    """When the child was created, as ``perfbench/harness.py`` reads its
    own: ``setup_s`` counts from there, so the window opened ``setup_s``
    after it."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return fallback


def run_once(tree: str, workload: str, seed: int, mode: str,
             index: int, rehearse: bool) -> dict:
    flight_dir = os.path.join(WORK, f"flight{index}")
    spans_path = os.path.join(WORK, f"spans{index}.json")
    shutil.rmtree(flight_dir, ignore_errors=True)
    env = dict(os.environ, PSDT_FLIGHT_DIR=flight_dir)
    if mode == "k":
        env.update(PSDT_TRACE_FILE=spans_path, PERFBENCH_KEEP_TRACE="1")
    sums_path = os.path.join(WORK, f"sums{index}.json")
    head = ["perfbench/run.py"]
    if mode == "t":
        env["HUNT_SUMS"] = sums_path
        head = ["-c", SHIM, "run"]
    command = [sys.executable, *head, "--workload", workload,
               "--seed", str(seed), "--trace", str(int(bool(mode)))]
    if rehearse:
        command += ["--rehearse", "--seconds", "2"]
    spawned = time.time()
    child = subprocess.Popen(command, cwd=tree, env=env, text=True,
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    started = process_start_time(child.pid, spawned)
    out, err = child.communicate()
    wall = time.time() - spawned
    lines = []
    for raw in out.splitlines():
        try:
            lines.append(json.loads(raw))
        except ValueError:
            pass

    def detail(name: str) -> dict:
        return next((l for l in lines if l.get("detail") == name), {})

    result = lines[-1] if lines else {}
    result = result.get("not_a_result", result)     # (a rehearsal's)
    if "metrics" not in result:
        result = {}
    setup, window = detail("setup"), detail("serve_window")
    opened = started + setup.get("setup_s", 0.0)
    closed = opened + window.get("window_s", 0.0)
    logged = []
    for raw in err.splitlines():
        if raw.startswith("slow leg ") and "{" in raw:
            try:
                logged.append(json.loads(raw[raw.index("{"):]))
            except ValueError:
                pass
    for record in logged:
        record["in_window"] = opened <= record["at"] <= closed
    ring = [postmortem.decode_slow_leg(e) for e in postmortem.merge_events(
        postmortem.load_rings(flight_dir)) if e["event"] == "serve.slow_leg"]
    entry = {
        "rc": child.returncode, "wall_s": wall, "seed": seed,
        "correct": result.get("correct"), "failed": result.get("failed"),
        "metrics": {k: v["value"] for k, v in
                    result.get("metrics", {}).items()},
        "device": result.get("device"),
        "tail": {k: window.get(k) for k in TAIL},
        "setup_s": setup.get("setup_s"), "parts": setup.get("parts"),
        "programs_in_window": (setup.get("programs_in_window") or {}).get(
            "programs"),
        "window": [opened, closed],
        "slow_legs": logged, "slow_legs_in_ring": ring,
        "idle_gaps": (result.get("breakdown") or {}).get("idle_gaps"),
        "stderr_tail": err[-2000:] if child.returncode else ""}
    if mode == "t" and os.path.exists(sums_path):
        with open(sums_path) as f:
            entry["window_sums"] = json.load(f)
        os.remove(sums_path)
    if mode == "k" and child.returncode == 0:
        entry["span_sums"] = span_sums(spans_path, opened, closed)
        os.remove(spans_path)
        read = subprocess.run(
            [sys.executable, "perfbench/tools/idle_by_span.py",
             os.path.join(".perfbench_trace", workload)], cwd=tree,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True)
        try:
            entry["idle_by_span"] = json.loads(
                read.stdout[read.stdout.index("{"):])
        except ValueError:
            entry["idle_by_span_error"] = (read.stdout + read.stderr)[-1500:]
        shutil.rmtree(os.path.join(tree, ".perfbench_trace", workload),
                      ignore_errors=True)
    shutil.rmtree(flight_dir, ignore_errors=True)
    return entry


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--name", required=True)
    parser.add_argument("--plan", required=True,
                        help="comma list of P, C, Ct, Ck (see above)")
    parser.add_argument("--seed-offset", type=int, default=0,
                        help="so that a second call draws other seeds")
    parser.add_argument("--rehearse", action="store_true",
                        help="on the CPU at a tiny size: this script's own "
                             "rehearsal, no measurement")
    args = parser.parse_args()
    plan = args.plan.split(",")
    if any(run[0] not in TREES or run[1:] not in ("", "t", "k")
           for run in plan):
        parser.error(f"bad plan {args.plan!r}")
    for side in {run[0] for run in plan}:
        if not os.path.isdir(TREES[side]):
            parser.error(f"no tree at {TREES[side]}")
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    os.makedirs(WORK, exist_ok=True)
    out_path = os.path.join(out_dir, f"{args.name}.jsonl")
    ok = True
    for index, (run, seed) in enumerate(zip(
            plan, seeds(plan, args.seed_offset))):
        entry = {"run": run, "workload": args.workload, **run_once(
            TREES[run[0]], args.workload, seed,
            run[1:], index, args.rehearse)}
        ok &= entry["rc"] == 0
        with open(out_path, "a") as f:
            f.write(json.dumps(entry, default=float) + "\n")
        brief = {k: entry[k] for k in ("run", "seed", "rc", "correct",
                                       "failed", "setup_s", "tail",
                                       "programs_in_window")}
        brief["metrics"] = {
            k: v for k, v in entry["metrics"].items()
            if k in ("itl_p95_ms", "setup_s", "device.idle_pct.serve",
                     "serve.round_host_p50_ms", "serve.ttft_p95_ms",
                     "serve.slo_ok_pct") or "admit_" in k or "slow" in k}
        brief["slow_legs_in_window"] = [
            {k: r.get(k) for k in ("leg", "wall_s", "cpu_s", "gc_s")}
            for r in entry["slow_legs"] if r["in_window"]]
        brief["slow_legs_outside"] = len(entry["slow_legs"]) - len(
            brief["slow_legs_in_window"])
        brief["stderr"] = entry["stderr_tail"][-600:]
        if "window_sums" in entry:
            brief["window_sums"] = {
                k: v for k, v in entry["window_sums"].items()
                if k.startswith("serve.admit")}
        if "span_sums" in entry:
            brief["span_sums"] = {k: v for k, v in entry["span_sums"].items()
                                  if k.startswith("serve/admit")
                                  or k == "serve/slow_leg"}
        if "idle_by_span" in entry:
            report = entry["idle_by_span"] or {}
            brief["idle_s"] = report.get("idle_s")
            brief["idle_by_thread"] = {
                thread: held["idle_s"] for thread, held in list(
                    report.get("idle_by_thread", {}).items())[:1]}
        print(json.dumps(brief, default=float), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
