"""One ``obs/stats`` gauge as it stood when the window closed, times
``scale`` (bytes to GB).  None where the program has no such gauge."""


def read(observed, gauge, scale=1.0):
    gauges = observed["registry_after"].get("gauges", {})
    if gauge not in gauges:
        return None
    return scale * gauges[gauge]
