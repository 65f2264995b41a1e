"""Share of a parent span's time that the union of the named child spans
covers, on the parent's own thread: how much of a round the legs account
for.  Children are clipped to their parent; overlapping children count
once."""

from .. import reduce
from ._window import in_window


def read(observed, parent, children):
    parents = in_window(observed, parent)
    if not parents:
        return None
    kids = [s for s in observed.get("spans", []) if s["name"] in children]
    if not kids:
        return None
    total = covered = 0.0
    for p in parents:
        start, end = p["ts"], p["ts"] + p["dur"]
        total += p["dur"]
        covered += reduce.union_length(
            (max(start, s["ts"]), min(end, s["ts"] + s["dur"]))
            for s in kids
            if s["tid"] == p["tid"] and s["ts"] < end
            and s["ts"] + s["dur"] > start)
    return 100.0 * covered / total if total > 0 else None
