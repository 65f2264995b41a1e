"""A decode round's latent attention as one kernel over the rows AS STORED.

What ``models/generation.py`` runs on a TPU for a latent layer's single
token a lane (``transformer.round_arm`` holds the rule): the query
arrives ABSORBED (every head's own part already through its key matrix,
the shared part beside it, zeros to the row's width), so a head's score
against a position is one dot product with that position's row and its
result a weighted sum of rows.  All heads of a lane share the rows: a block
of positions is fetched ONCE, scored against every head, and summed into
every head's accumulator under an online softmax, so a position's row is
read once a round where the plain-XLA form reads the whole part twice.

A lane's positions past its length are never fetched: the lengths are
prefetched scalars, a block wholly past a lane's last position is skipped
twice over (``pl.when`` skips the work, and the block index is clamped to
the lane's last block, so the step fetches nothing new), and only the block
the length falls in pays for the mask.  A round's cost follows the
positions its lanes HOLD, not the part's size.

The arithmetic is the plain path's: operands go to the MXU in their own
dtype with float32 accumulation, the scores are scaled, masked and
exponentiated in float32, the probabilities are cast to the rows' dtype
before the second product; what differs is the order of rounding any
online softmax has.  On a CPU backend the kernel runs interpreted
(``ops.pallas.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

LANES = 128
NEG = -1e30          # a hidden score: exp(NEG - m) is exactly 0 in float32
BLOCK = 1024         # positions a grid step fetches (1.3 MB of 640 lanes)


def fits(q_shape: tuple[int, ...], rows_shape: tuple[int, ...]) -> bool:
    """Whether the kernel takes these shapes: q ``[B, H, W]`` against rows
    ``[B, M, W]``, rows of whole registers, positions in whole blocks,
    heads a whole number of sublane tiles of 16."""
    if len(q_shape) != 3 or len(rows_shape) != 3:
        return False
    b, h, w = q_shape
    return (rows_shape[0] == b and rows_shape[2] == w and w % LANES == 0
            and h % 16 == 0 and rows_shape[1] % BLOCK == 0)


def _kernel(lengths_ref, q_ref, rows_ref, out_ref, top_ref, denom_ref,
            acc_ref, *, scale: float):
    lane, step = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[lane]

    @pl.when(step == 0)
    def _():
        top_ref[...] = jnp.full_like(top_ref, NEG)
        denom_ref[...] = jnp.zeros_like(denom_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step * BLOCK < length)
    def _():
        q, rows = q_ref[0], rows_ref[0]                  # [H, W], [BLOCK, W]
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, BLOCK]
        at = step * BLOCK + jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                                     1)
        scores = jnp.where(at < length, scores, NEG)
        top = top_ref[...]                                # [H, LANES]
        new_top = jnp.maximum(top, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(top - new_top)
        p = jnp.exp(scores - new_top[:, :1])
        denom_ref[...] = alpha * denom_ref[...] + jnp.sum(p, axis=1,
                                                          keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        top_ref[...] = new_top

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        out_ref[0] = acc_ref[...] / denom_ref[...][:, :1]


def latent_decode_attention(q: jax.Array, rows: jax.Array,
                            lengths: jax.Array, scale: float) -> jax.Array:
    """q [B, H, W] absorbed queries (one token a lane); rows [B, M, W] the
    part with the round's token already written; ``lengths`` [B] how many
    positions of a lane are live, the new token included (at least 1).
    Returns the softmax-weighted sums of rows [B, H, W] in float32:
    ``softmax(scale * q rows^T over the live positions) rows``."""
    batch, heads, width = q.shape
    blocks = rows.shape[1] // BLOCK

    def lane_rows(lane, step, lengths):
        # a block past the lane's last one is the last one again: nothing
        # is fetched for it
        return lane, jnp.minimum(step, (lengths[lane] - 1) // BLOCK), 0

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, blocks),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda b, j, n: (b, 0, 0)),
                pl.BlockSpec((1, BLOCK, width), lane_rows)],
            out_specs=pl.BlockSpec((1, heads, width),
                                   lambda b, j, n: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, LANES), jnp.float32),
                            pltpu.VMEM((heads, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((batch, heads, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(q, rows),
    )(lengths.astype(jnp.int32), q, rows)
