"""The q-th percentile of the observations one ``obs/stats`` histogram took
inside the window, off its log buckets (within about 9%)."""

from .. import reduce
from ._window import histogram_in_window


def read(observed, histogram, q):
    delta = histogram_in_window(observed, histogram)
    value = delta and reduce.histogram_percentile(delta, q)
    return 1e3 * value if value else None
