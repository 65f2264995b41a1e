"""Median duration of one of the program's ``obs/trace`` spans."""

from .. import reduce
from ._window import in_window


def read(observed, span):
    spans = in_window(observed, span)
    if not spans:
        return None
    return 1e3 * reduce.percentile([s["dur"] for s in spans], 50)
