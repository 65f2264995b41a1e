"""What a scope's share or roofline metric read, row by row, from a kept
trace: the ``by_scope`` rows that ``scope_share_pct`` / ``scopes_share_pct``
match for each of ``--scopes``, and under them every device operation with
its count, its self time and the bytes the COMPILER counted for it
(``bytes_accessed`` in the trace's metadata), so that a family's own count
of bytes can be held against what the operations that moved them say.

    PERFBENCH_KEEP_TRACE=1 python3 perfbench/run.py --workload W --seed N --trace 1
    python3 perfbench/tools/scope_rows.py .perfbench_trace/W --scopes attn/sparse attn/linear

Prints one JSON object a scope.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import reduce  # noqa: E402  (imports no JAX)


def rows(trace: dict, scope: str, top: int = 12) -> dict:
    reduced = reduce.reduce_trace(trace)
    matched = {path: seconds for path, seconds in reduced["by_scope"].items()
               if f"/{scope}/" in f"/{path}/"}
    ops = trace.get("ops") or {}
    held: dict[tuple, list] = {}
    for plane, events in trace["device"].items():
        runs = sorted(trace.get("programs", {}).get(plane, []),
                      key=lambda run: run[1])
        starts = [start for _, start, _ in runs]
        for name, start, _, self_s in reduce.self_times(events):
            i = bisect.bisect_right(starts, start) - 1
            program = runs[i][0] if i >= 0 and start < runs[i][2] else ""
            meta = ops.get(program, {}).get(name, {})
            path = meta.get("tf_op") or ""
            if f"/{scope}/" not in f"/{path}/":
                continue
            entry = held.setdefault((name, path), [0, 0.0, meta])
            entry[0] += 1
            entry[1] += self_s
    listed = sorted(held.items(), key=lambda kv: -kv[1][1])
    return {
        "scope": scope, "busy_s": reduced["busy_s"],
        "window_s": reduced["window_s"],
        "matched_s": sum(matched.values()),
        "by_scope": dict(sorted(matched.items(), key=lambda kv: -kv[1])[:top]),
        "compiler_bytes": sum(n * float(meta.get("bytes_accessed") or 0)
                              for (_, _), (n, _, meta) in held.items()),
        "ops": [{"op": name, "path": path[-90:], "calls": n,
                 "self_s": round(seconds, 6),
                 "bytes_accessed_a_call": meta.get("bytes_accessed"),
                 "flops_a_call": meta.get("flops")}
                for (name, path), (n, seconds, meta) in listed[:top]]}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace_dir")
    parser.add_argument("--scopes", nargs="+", required=True)
    args = parser.parse_args()
    path = reduce.find_xplane(args.trace_dir)
    if not path:
        print(f"no trace under {args.trace_dir}", file=sys.stderr)
        return 1
    trace = reduce.load_xplane(path)
    for scope in args.scopes:
        print(json.dumps(rows(trace, scope), default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
