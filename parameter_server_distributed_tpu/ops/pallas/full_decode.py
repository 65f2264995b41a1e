"""A decode round's full attention as one kernel over K and V AS THEY LIE.

What ``models/generation.py`` runs on a TPU for a full softmax layer's
single token a lane (``transformer.round_arm`` holds the rule),
``latent_decode.py``'s pattern over two parts in place of one: a block of
positions of K and the same block of V are fetched ONCE, scored against the
lane's query rows and summed into their accumulators under an online
softmax, where the plain-XLA form reads both parts whole (the scores, then
the weighted sum), whatever the lanes hold.

A lane's positions past its length are never fetched: the lengths are
prefetched scalars, a block wholly past a lane's last position is skipped
twice over (``pl.when`` skips the work, and the block index is clamped to
the lane's last block, so the step fetches nothing new), and only the block
the length falls in pays for the mask (its stale rows of V are zeroed too:
a probability of zero times whatever lies there is not zero for every
value).  A round's cost follows the positions its lanes HOLD.

The kernel takes a part where it lies, through one body and two index
maps.  A part the device lays BY HEAD (``generation._lies_by_head``) comes
as ``[B, KV', M, D']`` and a grid step takes ``(1, h, block, D')``: ``h``
rows of heads, each against its own query rows.  A part laid BY POSITION
comes as ``[B, 1, M * KV', D']``, a position's rows of heads one under
the other as they are in memory, and a grid step takes ``(1, 1, block *
KV', D')``: ONE product of every head's query rows against every row of the
block, in which a query row sees only the rows of its own head (a select on
the scores; the products cost the MXU what a product a head would, since
the block passes through it once either way, and nothing is read with a
stride).  How many positions a step takes follows the part's shape
(:func:`block_positions`).

The arithmetic is the plain path's: operands go to the MXU in their own
dtype with float32 accumulation, the scores are scaled, masked and
exponentiated in float32, the probabilities are cast to the part's dtype
before the second product; what differs is the order of rounding any
online softmax has.  ONE query row against a row of heads laid by head (a
matrix-vector product: the MXU would pass the whole block for one row of
results) is multiplied and summed on the VPU instead, the same products
(of the part's dtype, exact in float32) under the same float32 sums.  On a
CPU backend the kernel runs interpreted (``ops.pallas.interpret_mode``).
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
# Positions a grid step fetches, read on the chip (PERF.md section 6,
# PR 55).  Laid by head: 256 where a row of heads has ONE query row (the
# VPU's form) and 512 otherwise, of as many rows of heads as keep K under
# STEP_BYTES: fifteen of Olmo Hybrid's thirty.  All thirty at 128 positions
# are a tenth faster a round and sixty copies of the body, which the
# runtime compiles again for every layer whenever a process loads the
# round, cached or not: 5.6 s of its set-up.  Laid by position: as many as
# make POSITION_BYTES of K, 512 at most (256 at 8 rows of 128 lanes in
# bfloat16, 512 at 4): smaller steps pay 0.35 us each before they move a
# byte, larger ones fetch more past a lane's end.
STEP_BYTES = 1 << 20
POSITION_BYTES = 1 << 19
LARGEST_BLOCK = 512


def block_positions(part_shape: tuple[int, ...], itemsize: int,
                    by_head: bool, each: int) -> int:
    """The positions a grid step fetches of a part ``[B, M, KV', D']`` of
    ``itemsize`` bytes an element, laid ``by_head`` or by position, against
    ``each`` query rows a row of heads: a power of two from 128 to
    LARGEST_BLOCK."""
    if by_head:
        return min(256, LARGEST_BLOCK) if each == 1 else LARGEST_BLOCK
    fit = POSITION_BYTES // (part_shape[2] * part_shape[3] * itemsize)
    return min(LARGEST_BLOCK, max(128, 1 << max(fit, 1).bit_length() - 1))


def fits(q_shape: tuple[int, ...], part_shape: tuple[int, ...]) -> bool:
    """Whether the kernel takes these shapes: q ``[B, KV', R, D']`` (the R
    query rows of a row of heads, each head's queries in its own lanes)
    against a part ``[B, M, KV', D']``: rows of whole registers, positions
    in whole blocks of the largest size."""
    if len(q_shape) != 4 or len(part_shape) != 4:
        return False
    b, rows, _, width = q_shape
    return (part_shape[0] == b and part_shape[2] == rows
            and part_shape[3] == width and width % LANES == 0
            and part_shape[1] % LARGEST_BLOCK == 0)


def heads_a_step(rows: int, block_bytes: int) -> int:
    """How many rows of heads of a part laid by head a grid step takes:
    the largest divisor of ``rows`` whose K blocks (``block_bytes`` each)
    stay under STEP_BYTES."""
    room = max(1, STEP_BYTES // block_bytes)
    return max(h for h in range(1, rows + 1) if rows % h == 0 and h <= room)


def _sublanes(dtype) -> int:
    """Rows of a register tile of ``dtype`` (8 of 32 bits, 16 of 16)."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel(lengths_ref, q_ref, k_ref, v_ref, out_ref, wide_ref, top_ref,
            denom_ref, acc_ref, *, scale: float, block: int, under: int,
            each: int):
    """``block``: the positions of a step; ``under``: the rows of heads
    that lie under one position in the block (1 where the part lies by
    head); ``each``: the query rows a row of heads has."""
    lane, step = pl.program_id(0), pl.program_id(2)
    length = lengths_ref[lane]
    heads, rows = q_ref.shape[1:3]
    first = step * block

    @pl.when(step == 0)
    def _():
        # the query rows padded to whole sublane tiles, here and not in HBM
        wide_ref[...] = jnp.zeros_like(wide_ref)
        wide_ref[:, :rows, :] = q_ref[0]
        top_ref[...] = jnp.full_like(top_ref, NEG)
        denom_ref[...] = jnp.zeros_like(denom_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def one_row(h, ragged: bool):
        """A row of heads' single query row, on the VPU: the scores a
        column [N, 1], positions down the sublanes."""
        k = k_ref[0, h].astype(jnp.float32)
        v = v_ref[0, h].astype(jnp.float32)
        q = wide_ref[h, :1, :].astype(jnp.float32)
        scores = jnp.sum(k * q, axis=1, keepdims=True) * scale
        if ragged:
            live = first + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0) < length
            scores = jnp.where(live, scores, NEG)
            v = jnp.where(live, v, 0.0)
        top = top_ref[h, :1, :1]
        new_top = jnp.maximum(top, jnp.max(scores, axis=0, keepdims=True))
        alpha = jnp.exp(top - new_top)
        p = jnp.exp(scores - new_top)
        denom_ref[h, :1, :] = alpha * denom_ref[h, :1, :] + jnp.sum(
            p, axis=0, keepdims=True)
        acc_ref[h, :1, :] = alpha * acc_ref[h, :1, :] + jnp.sum(
            p.astype(k_ref.dtype).astype(jnp.float32) * v, axis=0,
            keepdims=True)
        top_ref[h, :1, :] = jnp.broadcast_to(new_top, (1, LANES))

    def rows_of_heads(h, ragged: bool):
        q, k, v = wide_ref[h], k_ref[0, h], v_ref[0, h]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [R, N]
        seen = None
        if ragged or under > 1:
            at = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        if under > 1:
            # a query row sees the rows of its own head
            mine = jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                            0) // each
            seen = at % under == mine
        if ragged:
            live = first + at // under < length
            seen = live if seen is None else seen & live
            held = first + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0) // under < length
            v = jnp.where(held, v, jnp.zeros_like(v))
        if seen is not None:
            scores = jnp.where(seen, scores, NEG)
        top = top_ref[h]                                      # [R, LANES]
        new_top = jnp.maximum(top, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(top - new_top)
        p = jnp.exp(scores - new_top[:, :1])
        denom_ref[h] = alpha * denom_ref[h] + jnp.sum(p, axis=1,
                                                      keepdims=True)
        acc_ref[h] = alpha[:, :1] * acc_ref[h] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        top_ref[h] = new_top

    def attend(ragged: bool):
        # (written out a row of heads after the other: a loop around them
        # costs the round more than it saves the compiler, 0.59 ms a layer
        # for 0.47 at two turns of fifteen, PERF.md section 6, PR 55)
        for h in range(heads):
            (one_row if rows == under == 1 else rows_of_heads)(h, ragged)

    @pl.when(first + block <= length)
    def _():
        attend(False)

    @pl.when((first < length) & (first + block > length))
    def _():
        attend(True)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        denom = denom_ref[...][:, :rows, :1]
        # (a lane that holds nothing has summed nothing: zeros, not 0 / 0)
        out_ref[0] = acc_ref[...][:, :rows] / jnp.where(denom > 0, denom, 1)


def full_decode_attention(q: jax.Array, keys: jax.Array, values: jax.Array,
                          lengths: jax.Array, scale: float,
                          by_head: bool) -> jax.Array:
    """q [B, KV', R, D'] a lane's query rows by row of heads (one token a
    lane); keys/values a layer's parts with the round's token already
    written, [B, KV', M, D'] where ``by_head`` and [B, M, KV', D']
    otherwise; ``lengths`` [B] how many positions of a lane are live, the
    new token included (0: the lane's result is zeros).  Returns
    ``softmax(scale * q keys^T over the live positions) values`` by row of
    heads, [B, KV', R, D'] in float32."""
    _, rows, each, width = q.shape
    itemsize = keys.dtype.itemsize
    if by_head:
        block = block_positions(
            (q.shape[0], keys.shape[2], rows, width), itemsize, True, each)
        heads = heads_a_step(rows, block * width * itemsize)
    else:
        block, heads = block_positions(keys.shape, itemsize, False, each), 1
    return _attend(q, keys, values, lengths.astype(jnp.int32), scale=scale,
                   by_head=by_head, block=block, heads=heads,
                   interpret=interpret_mode(q, keys, values))


# (jitted so that a round's layers share ONE trace and one lowering of the
# kernel: 24 of GPT-2's were 3.6 s of every process's set-up on the chip's
# host, PERF.md section 6, PR 55)
@functools.partial(jax.jit, static_argnames=("scale", "by_head", "block",
                                             "heads", "interpret"))
def _attend(q, keys, values, lengths, *, scale: float, by_head: bool,
            block: int, heads: int, interpret: bool):
    batch, rows, each, width = q.shape
    if by_head:
        held, under = keys.shape[2], 1
    else:
        # a position's rows of heads one under the other, as they lie
        held, under = keys.shape[1], rows
        q = q.reshape(batch, 1, rows * each, width)
        keys = keys.reshape(batch, 1, held * rows, width)
        values = values.reshape(batch, 1, held * rows, width)
    padded = -(-q.shape[2] // _sublanes(q.dtype)) * _sublanes(q.dtype)

    def lane_rows(lane, group, step, lengths):
        # a block past the lane's last one is the last one again: nothing
        # is fetched for it
        last = jnp.maximum(lengths[lane] - 1, 0) // block
        return lane, group, jnp.minimum(step, last), 0

    def lane_queries(lane, group, step, lengths):
        return lane, group, 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, under=under,
                          each=each),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, q.shape[1] // heads, held // block),
            in_specs=[
                pl.BlockSpec((1, heads) + q.shape[2:], lane_queries),
                pl.BlockSpec((1, heads, block * under, width), lane_rows),
                pl.BlockSpec((1, heads, block * under, width), lane_rows)],
            out_specs=pl.BlockSpec((1, heads) + q.shape[2:], lane_queries),
            scratch_shapes=[pltpu.VMEM((heads, padded, width), q.dtype),
                            pltpu.VMEM((heads, padded, LANES), jnp.float32),
                            pltpu.VMEM((heads, padded, LANES), jnp.float32),
                            pltpu.VMEM((heads, padded, width),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, keys, values)
    return out.reshape(batch, rows, each, width)
