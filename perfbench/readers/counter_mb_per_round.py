"""Bytes the named counters of ``obs/stats`` moved in the window, per
round, in MB (a count, not a time)."""


def read(observed, counters):
    before = observed["registry_before"]["counters"]
    after = observed["registry_after"]["counters"]
    if not observed.get("rounds") or not any(c in after for c in counters):
        return None
    moved = sum(after.get(c, 0) - before.get(c, 0) for c in counters)
    return moved / observed["rounds"] / 1e6
