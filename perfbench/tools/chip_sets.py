"""Run sets of runs of one cell as the driver's check does: each set in a
copy of the tree of its own (its own compile cache, HOME, TMPDIR), a cold
run first, then warm runs in turn across the sets with the same seeds in
every set, then one traced run.  Prints medians and spreads and writes
every line to ``chiprun_out/<name>.jsonl``.

    chiprun --timeout 3000 -- python3 perfbench/tools/chip_sets.py \
        --workload spmd_step_gpt2m --sets 2 --runs 6 --traced 1

The parent process never touches JAX: each run is a process of its own and
holds the chip alone.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench.reduce import iqr_spread  # noqa: E402  (imports no JAX)
SKIP = {".git", "chiprun_out", ".jax_cache", ".perfbench_copies",
        ".perfbench_trace", ".perfbench_work", "__pycache__",
        ".pytest_cache", "build"}
BASE_SEED = 2147483000   # the driver's seeds are large: above 2**31 - 1 too


def copy_tree(dst: str) -> None:
    shutil.rmtree(dst, ignore_errors=True)
    shutil.copytree(REPO, dst, ignore=lambda d, names: [
        n for n in names if n in SKIP])


def run_once(copy: str, workload: str, seed: int, seconds, trace: int,
             own_cache: bool = True):
    env = dict(os.environ)
    if own_cache:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for var, sub in (("HOME", ".home"), ("XDG_CACHE_HOME", ".home/.cache"),
                     ("TMPDIR", ".tmp")):
        env[var] = os.path.join(copy, sub)
        os.makedirs(env[var], exist_ok=True)
    command = [sys.executable, "perfbench/run.py", "--workload", workload,
               "--seed", str(seed), "--trace", str(trace)]
    if seconds is not None:
        command += ["--seconds", str(seconds)]
    t0 = time.time()
    done = subprocess.run(command, cwd=copy, env=env, capture_output=True,
                          text=True)
    lines = []
    for raw in done.stdout.splitlines():
        try:
            lines.append(json.loads(raw))
        except ValueError:
            pass
    return {"rc": done.returncode, "wall_s": time.time() - t0,
            "lines": lines,
            "stderr_tail": done.stderr[-3000:] if done.returncode else ""}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--traced", type=int, default=1)
    parser.add_argument("--cold", type=int, default=1,
                        help="0: no cold runs, and the sets share the "
                             "machine's compile cache where it has one "
                             "(a second look at spreads, not at set-up)")
    parser.add_argument("--name", default="")
    args = parser.parse_args()
    name = args.name or args.workload
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{name}.jsonl")
    copies = [os.path.join(REPO, ".perfbench_copies", f"set{chr(65 + i)}")
              for i in range(args.sets)]
    for copy in copies:
        copy_tree(copy)

    records = []

    def record(set_index, kind, seed, trace):
        result = run_once(copies[set_index], args.workload, seed,
                          args.seconds, trace, own_cache=bool(args.cold))
        entry = {"set": chr(65 + set_index), "kind": kind, "seed": seed,
                 "trace": trace, **result}
        records.append(entry)
        with open(out_path, "a") as f:
            f.write(json.dumps(entry) + "\n")
        last = result["lines"][-1] if result["lines"] else {}
        setup = next((l for l in result["lines"]
                      if l.get("detail") == "setup"), {})
        print(json.dumps({"set": entry["set"], "kind": kind, "seed": seed,
                          "rc": result["rc"],
                          "wall_s": round(result["wall_s"], 1),
                          "correct": last.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      last.get("metrics", {}).items()},
                          "parts": setup.get("parts"),
                          "in_setup": setup.get("programs_in_setup"),
                          "in_window": setup.get("programs_in_window"),
                          "stderr": result["stderr_tail"][-1500:]}),
              flush=True)
        return entry

    for i in range(args.sets if args.cold else 0):
        record(i, "cold", BASE_SEED, 0)
    for r in range(args.runs):
        for i in range(args.sets):
            record(i, "warm", BASE_SEED + 1 + r * 650, 0)
    if args.traced:
        entry = record(0, "traced", BASE_SEED + 7, 1)
        for line in entry["lines"][-1:]:
            print(json.dumps(line), flush=True)

    summary = {}
    for i in range(args.sets):
        label = chr(65 + i)
        warm = [e for e in records if e["set"] == label
                and e["kind"] == "warm" and e["lines"]
                and "metrics" in e["lines"][-1]]
        names = sorted({k for e in warm for k in e["lines"][-1]["metrics"]})
        for metric in names:
            values = [e["lines"][-1]["metrics"][metric]["value"]
                      for e in warm]
            summary.setdefault(metric, {})[label] = {
                "n": len(values), "median": statistics.median(values),
                "spread": iqr_spread(values) if len(values) > 1 else None,
                "values": values}
    print(json.dumps({"summary": summary}), flush=True)
    with open(out_path, "a") as f:
        f.write(json.dumps({"summary": summary}) + "\n")
    return 0 if all(e["rc"] == 0 for e in records) else 1


if __name__ == "__main__":
    sys.exit(main())
