"""A percentile of a list of samples the job kept (all requests of the
window)."""

from .. import reduce


def read(observed, samples, q):
    values = observed.get(samples)
    if not values:
        return None
    return reduce.percentile(values, q)
