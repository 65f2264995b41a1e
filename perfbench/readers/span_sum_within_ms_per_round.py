"""Time spent inside the named ``obs/trace`` spans as far as they lie inside
a ``within`` span of their own thread (and, with ``outside``, not inside one
of those): a ring's waits by the phase of the round they fell in, each end of
the ring by its own thread's phases.  Summed over the ``within`` spans that
open inside the window, divided by its rounds.

None where the window holds no ``within`` span; 0.0 where it holds them and
none of the named spans lies in them (a carved leg that never waited leaves
no span)."""

from .. import reduce
from ._window import in_window


def _overlap(a, b):
    return max(a[0], b[0]), min(a[1], b[1])


def _by_thread(found) -> dict:
    out = {}
    for s in found:
        out.setdefault(s["tid"], []).append((s["ts"], s["ts"] + s["dur"]))
    return out


def read(observed, spans, within, outside=None):
    frames = _by_thread(in_window(observed, within))
    if not frames or not observed.get("rounds"):
        return None
    everything = observed.get("spans", [])
    holes = _by_thread(s for s in everything if s["name"] == outside)
    total = 0.0
    for tid, held in _by_thread(
            s for s in everything if s["name"] in spans).items():
        cuts = holes.get(tid, ())
        for frame in frames.get(tid, ()):
            for span in held:
                clip = _overlap(span, frame)
                if clip[0] < clip[1]:
                    inside = (_overlap(clip, hole) for hole in cuts)
                    total += clip[1] - clip[0] - reduce.union_length(
                        cut for cut in inside if cut[0] < cut[1])
    return 1e3 * total / observed["rounds"]
