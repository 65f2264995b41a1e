"""Sum of the observations one ``obs/stats`` histogram took inside the
window, as a share of the window."""

from ._window import histogram_in_window


def read(observed, histogram):
    delta = histogram_in_window(observed, histogram)
    if not delta or delta["count"] <= 0:
        return None
    return 100.0 * delta["sum"] / observed["window_s"]
