"""Shared by the span and histogram readers: what fell inside the window."""

from .. import reduce


def in_window(observed: dict, name: str) -> list[dict]:
    start, end = observed["window"]
    return [s for s in observed.get("spans", [])
            if s["name"] == name and start <= s["ts"] <= end]


def histogram_in_window(observed: dict, name: str) -> dict | None:
    """The observations one ``obs/stats`` histogram took inside the window,
    or None where the program never made the histogram."""
    after = observed["registry_after"]["histograms"].get(name)
    if after is None:
        return None
    before = (observed["registry_before"] or {}).get(
        "histograms", {}).get(name)
    return reduce.histogram_delta(before, after)
