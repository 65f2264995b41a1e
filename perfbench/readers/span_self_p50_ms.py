"""Median self time of a parent span: its duration less the part its named
child spans (same ``iteration``) cover.  For ``worker/step`` less
``worker/compute`` this is the host's part of a PS round."""

from .. import reduce
from ._window import in_window


def read(observed, span, child):
    parents = in_window(observed, span)
    if not parents:
        return None
    covered = {}
    for s in in_window(observed, child):
        key = s.get("args", {}).get("iteration")
        covered[key] = covered.get(key, 0.0) + s["dur"]
    return 1e3 * reduce.percentile(
        [p["dur"] - covered.get(p.get("args", {}).get("iteration"), 0.0)
         for p in parents], 50)
