"""Pallas TPU kernels (blockwise attention, fused optimizer updates).

:func:`interpret_mode` is the single place that decides whether a
``pallas_call`` lowers through Mosaic or runs in the Pallas interpreter.
"""

from __future__ import annotations

import jax

# jax.ad_checkpoint names of the blockwise attention kernel's output and
# per-row logsumexp as its backward reads them
# (``fused_attention._attention_fwd``): the two residuals of its vjp that
# cost a kernel call to rebuild.  A jax.checkpoint around the call whose
# policy keeps both (``Transformer._remat_policy``) runs the forward kernel
# once a step; under any other policy the names are identities.  They live
# here so that a model names them without importing pallas.
ATTN_KERNEL_KEPT = ("attn_kernel_out", "attn_kernel_lse")


def interpret_mode(*operands) -> bool:
    """True when a ``pallas_call`` over ``operands`` must be interpreted.

    Decided from the platform of the devices the call will run on: the
    devices that hold the concrete operands, or — for tracers and
    uncommitted host values, i.e. inside ``jit`` — the default backend's
    devices, which is where jit places such work.  Only a CPU backend
    interprets; every other platform lowers the kernel for real, so a
    backend Mosaic cannot target fails at compile time instead of
    silently timing the interpreter.
    """
    platforms = {device.platform
                 for x in operands
                 if isinstance(x, jax.Array)
                 and not isinstance(x, jax.core.Tracer)
                 for device in x.devices()}
    if not platforms:
        platforms = {jax.devices()[0].platform}
    return platforms == {"cpu"}
