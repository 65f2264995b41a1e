"""A memory-bound block's share of its roofline: the least time the chip
could take to move the bytes the block has to move (over the published
HBM peak, ``peaks.py``) over the device time the trace shows under the
block's ``jax.named_scope`` paths (self time, as ``scopes_share_pct``:
several paths, since the chip's grouped matmul is a custom call that
carries only its own name, ``ragged-dot-none:``).

The bytes are counted by a function of the configuration's family module
(``bytes``), from counters the program keeps (``counters``: the function's
arguments, each the sum of how far the named counters moved).  The
counters cover the whole window and the trace only its first seconds, so
the bytes are scaled by the traced share of the window: exact where the
rounds are alike all through the window, and a little high where the
server fills up during the traced part.  None where there is no trace, no
such block in it, no such counter, or no published peak for the device (a
rehearsal)."""

import os

from .. import families, harness, peaks
from . import counter_delta, scopes_share_pct


def read(observed, scopes, config, bytes, counters):
    trace = observed.get("trace")
    if not trace or not trace.get("by_scope"):
        return None
    inside = scopes_share_pct.inside(trace, scopes)
    if inside <= 0:
        return None
    counted = {}
    for argument, names in counters.items():
        moved = [counter_delta.read(observed, name) for name in names]
        if all(m is None for m in moved):
            return None
        counted[argument] = sum(m or 0 for m in moved)
    import jax

    try:
        peak = peaks.peaks_for(jax.devices()[0].device_kind)
    except KeyError:
        return None
    configuration = harness.load_json(os.path.join(
        harness.HERE, "configs", f"{config}.json"))
    moved = getattr(families.of(configuration), bytes)(configuration,
                                                       **counted)
    traced_share = trace["window_s"] / observed["window_s"]
    least_s = moved * traced_share / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / inside
