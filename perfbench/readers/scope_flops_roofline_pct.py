"""A block's share of the chip's matrix peak: the least time the chip could
take to make the products the block has to make (over the published
bfloat16 peak, ``peaks.py``) over the device time the trace shows under the
block's ``jax.named_scope`` paths.  ``scope_bytes_roofline_pct``'s twin for
the other side of the roofline: a kernel near the chip's ridge reports
both, and the higher says which side binds.

The operations are counted as that reader counts bytes: by a function
(``flops``) of the family module of the configuration of the cell the
metric is read in, from counters the program keeps, scaled by the traced
share of the window.  So this reader IS that one with another count and
another peak: it reads the count's share of the HBM peak there and
re-scales it by the two peaks' ratio.  None wherever that reader finds
nothing (no trace, no such block, counter, configuration, function or
published peak)."""

from .. import peaks
from . import scope_bytes_roofline_pct


def read(observed, scopes, flops, counters):
    of_hbm_peak = scope_bytes_roofline_pct.read(observed, scopes, flops,
                                                counters)
    if of_hbm_peak is None:
        return None
    import jax

    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    return of_hbm_peak * peak["hbm_bytes_per_s"] / peak["bf16_flops"]
