"""1 - the union of the device's operation intervals over the traced
window, from the profiler's trace, averaged over the chips used."""

from .. import reduce


def read(observed):
    trace = observed.get("trace")
    if not trace:
        return None
    return 100.0 * reduce.idle_share(trace["busy_s"], trace["window_s"])
