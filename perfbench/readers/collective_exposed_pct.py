"""Share of the traced window that a device's op line spent inside
collective operations: on an in-order line, time in which no compute ran."""


def read(observed):
    trace = observed.get("trace")
    if not trace or trace["devices"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
