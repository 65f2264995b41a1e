"""How far one ``obs/stats`` counter moved inside the window (a count).
None where the program has no such counter; 0 where it has and nothing
was counted."""


def read(observed, counter):
    after = observed["registry_after"]["counters"]
    if counter not in after:
        return None
    before = (observed.get("registry_before") or {}).get("counters", {})
    return after[counter] - before.get(counter, 0)
