"""Time spent inside the named ``obs/trace`` spans, summed over the window
and divided by its rounds.  With ``thread_of``, only the spans recorded on
the thread of that span of the same ``iteration`` count (``worker/step``:
the worker's own thread, and not the server's end of the same ring)."""

from ._window import in_window

_NO_THREAD = object()


def _iteration(span):
    return span.get("args", {}).get("iteration", _NO_THREAD)


def read(observed, spans, thread_of=None):
    found = [s for name in spans for s in in_window(observed, name)]
    if thread_of is not None:
        thread = {_iteration(s): s["tid"]
                  for s in in_window(observed, thread_of)}
        thread.pop(_NO_THREAD, None)
        found = [s for s in found
                 if thread.get(_iteration(s), _NO_THREAD) == s["tid"]]
    if not found or not observed.get("rounds"):
        return None
    return 1e3 * sum(s["dur"] for s in found) / observed["rounds"]
