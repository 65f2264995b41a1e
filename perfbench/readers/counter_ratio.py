"""How far one ``obs/stats`` counter moved inside the window over how far
another did, times ``scale``: a mean per counted event (the largest
expert's load over the mean, summed over (layer, round) pairs, over the
pairs) or a share (distinct experts touched over the places there were).
None where the program has no such counters, or nothing was counted."""

from . import counter_delta


def read(observed, numerator, denominator, scale=1.0):
    below = counter_delta.read(observed, denominator)
    above = counter_delta.read(observed, numerator)
    if not below or above is None:
        return None
    return scale * above / below
