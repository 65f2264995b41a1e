"""A decode round's state-space recurrence as one kernel over the matrix
WHERE IT LIES, for the lanes that hold a request and for no other.

What ``ops/ssd.py`` runs on a TPU for an ``ssm`` layer's single token a
lane (``transformer.round_arm`` holds the rule), ``full_decode.py``'s
pattern on a state: the matrix ``[B, H, P, N]`` float32 comes in and goes
out as the SAME buffer (``input_output_aliases``), the grid walks the lanes
in an order given by prefetched scalars, live lanes first, and a step takes
``heads`` heads of one lane: per head

    h = exp(dt a) h + (dt x) B^T        y = h C

in float32: the state's update is ``ssd._one_position``'s a product, a
product and a sum an element on the VPU (bit for bit the plain pass's on
the chip), and ``y``'s sum over the last axis runs on the MXU at the
highest precision (the body says why).  The plain-XLA form reads and writes
the matrix of EVERY lane (2 MB a lane and layer at Granite 4.0-H's widths),
whatever the lanes hold.

A lane that holds no request is neither read nor written: a step past the
live lanes is skipped twice over (``pl.when`` skips the body, and its index
maps name the block the step before named, so nothing is fetched and
nothing is written back), its matrix is bit for bit what it was, and its
``y`` is zeros (as the full-decode kernel's at length 0), so that nothing
undefined reaches the lane's norm.  A round's cost follows the lanes that
decode.

What a head needs beside its matrix lies as the body reads it: a head's
value column ``dt x`` [P] and its decay meet the matrix down its SUBLANES,
so both come as ``[B, H / heads, P, heads]`` (a head a column, made by
XLA from the projections' output, 1 MB a layer), and ``y`` goes back the
same way; the group's key and query [N] lie along the lanes as they are.
On a CPU backend the kernel runs interpreted (``ops.pallas.interpret_mode``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

LANES = 128
SUBLANES = 8
_HIGHEST = jax.lax.Precision.HIGHEST
# Heads a grid step takes of a lane, and how many of them the body writes
# out (the rest a loop around them), read on the chip (PERF.md section 6,
# PR 58: ``scripts/ssd_decode_micro.py``, ms a layer at 64 / 38 live lanes
# of 64, where XLA's fusion over every lane reads 0.462).  A step pays
# about 0.35 us before it moves a byte, so the most whose block in and
# block out, each held twice, stay well inside the 16 MB of VMEM a kernel
# is given: 2 MB a block, a whole lane of Granite's (0.471 / 0.299; 32
# heads 0.478 / 0.314, 16 heads 0.536 / 0.358).  The body written out for
# all 64 heads is 0.16 s of the compiler's time a call site at EVERY load
# of the round, cached or not (36 sites: 5.8 s of set-up); 16 written out
# in a loop of four cost a quarter of that and read 0.476 / 0.303, 8 in a
# loop of eight 0.503 / 0.320 (the loop's tiles are cut out of the step's
# columns by lane rotations first).
STEP_BYTES = 2 << 20
UNROLL = 16


def fits(x_shape: tuple[int, ...], matrix_shape: tuple[int, ...]) -> bool:
    """Whether the kernel takes these shapes: a round's values x ``[B, 1,
    H, P]`` against a matrix ``[B, H, P, N]`` whose last two axes are whole
    registers (P in sublanes of 8, N in lanes of 128)."""
    if len(x_shape) != 4 or len(matrix_shape) != 4:
        return False
    batch, heads, dim, width = matrix_shape
    return (tuple(x_shape) == (batch, 1, heads, dim)
            and dim % SUBLANES == 0 and width % LANES == 0)


def heads_a_step(heads: int, head_bytes: int) -> int:
    """How many heads of a lane a grid step takes: the largest divisor of
    ``heads`` whose matrices (``head_bytes`` each) stay under STEP_BYTES."""
    room = max(1, STEP_BYTES // head_bytes)
    return max(h for h in range(1, heads + 1) if heads % h == 0 and h <= room)


def _unrolled(heads: int) -> int:
    """How many heads of a step the body writes out: the largest divisor
    of ``heads`` up to UNROLL (the rest is a loop around them)."""
    return max(u for u in range(1, UNROLL + 1) if heads % u == 0)


def _kernel(order_ref, live_ref, written_ref, decay_ref, b_ref, c_ref,
            state_ref, y_ref, out_ref, written_tiles, decay_tiles, y_tiles,
            *, each: int):
    """``each``: the heads that share a group's key and query.  The heads
    of a step go through in a loop of tiles of ``_unrolled`` heads: a
    head's column of a tile is a static slice of lanes, and the tile a
    dynamic index into a scratch the step's columns are cut into first
    (UNROLL says why)."""
    step, block = pl.program_id(0), pl.program_id(1)
    heads, width = state_ref.shape[1], state_ref.shape[3]
    unrolled = _unrolled(heads)
    live = live_ref[0]

    def a_tile(tile, carry):
        written, decay = written_tiles[tile], decay_tiles[tile]
        for j in range(unrolled):
            h = tile * unrolled + j
            group = (block * heads + h) // each
            key = b_ref[0, pl.ds(group, 1), :]                  # [1, N]
            query = c_ref[0, pl.ds(group, 1), :]
            state = (state_ref[0, h] * decay[:, j:j + 1]
                     + written[:, j:j + 1] * key)               # [P, N]
            out_ref[0, h] = state
            # the sum over the state's width on the MXU, whose six passes
            # of a float32 product (HIGHEST) err by less than the rounding
            # of one: a lane-reduce a register on the XLU, beside the
            # columns' broadcasts, is what the step would wait for (0.546
            # ms a layer for 0.473 at 64 live lanes, where the matrices'
            # DMA alone takes 0.463)
            y_tiles[tile, :, j:j + 1] = jax.lax.dot_general(
                state, jnp.broadcast_to(query, (SUBLANES, width)),
                (((1,), (1,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)[:, :1]
        return carry

    @pl.when(step < live)
    def _():
        tiles = [slice(tile * unrolled, (tile + 1) * unrolled)
                 for tile in range(heads // unrolled)]
        for tile, lanes in enumerate(tiles):
            written_tiles[tile] = written_ref[0, 0, :, lanes]
            decay_tiles[tile] = decay_ref[0, 0, :, lanes]
        jax.lax.fori_loop(0, len(tiles), a_tile, 0)
        for tile, lanes in enumerate(tiles):
            y_ref[0, 0, :, lanes] = y_tiles[tile]

    @pl.when((live == 0) & (step == 0) & (block == 0))
    def _():
        # no lane decodes: every step names this one block, which goes
        # back as it came (a block never written would go back undefined)
        out_ref[...] = state_ref[...]


def ssd_decode(written: jax.Array, decay: jax.Array, b: jax.Array,
               c: jax.Array, state: jax.Array, live: jax.Array,
               heads: int | None = None) -> tuple[jax.Array, jax.Array]:
    """One position of the recurrence for the lanes ``live`` [B] (bool)
    names: ``written`` dt x [B, H, P] and ``decay`` exp(dt a) [B, H],
    float32; b and c [B, G, N], G groups of H / G neighbouring heads;
    ``state`` [B, H, P, N] float32, updated where it lies (donate it).
    Returns (y [B, H, P] float32, zeros for a lane that is not live; the
    state, a lane that is not live as it was).  ``heads``: the heads a
    grid step takes (default :func:`heads_a_step`)."""
    dim, width = state.shape[2:]
    if heads is None:
        heads = heads_a_step(state.shape[1], dim * width * 4)
    return _decode(written, decay, b, c, state, live, heads=heads,
                   interpret=interpret_mode(state, written))


# (jitted so that a round's layers share ONE trace and one lowering of the
# kernel, as full_decode._attend)
@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _decode(written, decay, b, c, state, live, *, heads: int,
            interpret: bool):
    batch, total, dim, width = state.shape
    blocks = total // heads
    each = total // b.shape[1]
    live = live.astype(jnp.bool_)
    # live lanes first, each kind in the lanes' own order
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)[None]

    def columns(v):
        """[B, H, P] -> [B, H / heads, P, heads]: a head a column."""
        return v.reshape(batch, blocks, heads, dim).transpose(0, 1, 3, 2)

    def at(step, block, order, count):
        # a step past the live lanes is the last live step again: nothing
        # is fetched for it and nothing written back
        idle = step >= count[0]
        lane = order[jnp.minimum(step, jnp.maximum(count[0] - 1, 0))]
        return lane, jnp.where(idle, blocks - 1, block)

    def of_block(step, block, order, count):
        return at(step, block, order, count) + (0, 0)

    def of_lane(step, block, order, count):
        return at(step, block, order, count)[0], 0, 0

    column = pl.BlockSpec((1, 1, dim, heads), of_block)
    matrix = pl.BlockSpec((1, heads, dim, width), of_block)
    shared = pl.BlockSpec((1,) + b.shape[1:], of_lane)
    y, state = pl.pallas_call(
        functools.partial(_kernel, each=each),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, blocks),
            in_specs=[column, column, shared, shared, matrix],
            out_specs=[column, matrix],
            scratch_shapes=[pltpu.VMEM(
                (heads // _unrolled(heads), dim, _unrolled(heads)),
                jnp.float32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((batch, blocks, dim, heads),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # (operand 6, after the two prefetched scalars, is the state)
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(order, count, columns(written),
      columns(jnp.broadcast_to(decay[:, :, None], written.shape)),
      b, c, state)
    # a lane the kernel never visited: zeros, not what the buffer held
    y = jnp.where(live[:, None, None],
                  y.transpose(0, 1, 3, 2).reshape(batch, total, dim), 0.0)
    return y, state
