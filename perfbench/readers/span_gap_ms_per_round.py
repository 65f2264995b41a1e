"""Time between one span of the name and the next on the same thread, both
inside the window: for ``worker/step`` what a round costs outside every
step (the loop's bookkeeping, the caller, a store let go of).  The mean over
the window's gaps, in ms; None where no thread holds two such spans."""

from ._window import in_window


def read(observed, span):
    by_thread = {}
    for s in in_window(observed, span):
        by_thread.setdefault(s["tid"], []).append(s)
    gaps = []
    for held in by_thread.values():
        held.sort(key=lambda s: s["ts"])
        gaps += [max(0.0, after["ts"] - before["ts"] - before["dur"])
                 for before, after in zip(held, held[1:])]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
