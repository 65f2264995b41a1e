"""Share of the window that one of the program's spans was open."""

from ._window import in_window


def read(observed, span):
    spans = in_window(observed, span)
    if not spans:
        return None
    return 100.0 * sum(s["dur"] for s in spans) / observed["window_s"]
