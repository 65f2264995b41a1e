"""Causal attention as one blockwise kernel over ``[B, S, H, D]`` AS STORED.

What the model's default path runs for a full-causal layer on a TPU
(``models.transformer.Transformer.attend`` holds the rule): a block of
scores lives in VMEM only, so no ``[B, H, S, S]`` array is ever written to
HBM.  Online softmax forward; the backward recomputes the probabilities
from the saved output and per-row logsumexp (O(S) residuals), both named
for ``jax.checkpoint`` (``ops.pallas.ATTN_KERNEL_KEPT``): a policy that
keeps them spares the remat forward the kernel.

**No transposes outside the kernel.**  The projections hand attention
``[B, S, H*D]`` (the head split is a reshape), and that is what the kernels
read: a block is ``[rows, 128 lanes]`` holding as many whole heads as fit a
row of 128 lanes (``pack``: two heads of 64, one of 128).  A head inside a
row is reached by zeroing the other lanes of the query (and of dO), never
by slicing lanes: a contraction over 128 lanes of which 64 are zero costs
the MXU what a contraction over 64 costs (half its depth either way), and
every load, store and product stays a full row.  Grouped K/V heads
(``H = groups * KV``) stay unexpanded: a grid cell holds one row of K/V
heads and the ``pack * groups`` query heads that read it, and dK/dV sum over
the group inside the kernel.

**The arithmetic is the einsum path's** (``causal_attention``): operands go
to the MXU in their own dtype (bf16 in, f32 accumulation), the scores are
scaled, masked and exponentiated in f32, the probabilities are cast to the
values' dtype before the second product, and the running maximum, the
denominator and every accumulator are f32.  The mask is exact (a hidden
pair contributes exactly 0) and the exponent is ``jnp.exp``; nothing is
approximated.  What differs from the einsum is only the order of rounding
that any online softmax has: the probabilities are cast before the division
by the denominator, not after.

Grid ``(batch, K/V rows, q blocks, k blocks)`` with the streamed dimension
innermost; blocks wholly above the diagonal are skipped twice over
(``pl.when`` skips the work, and the streamed block index is clamped to the
diagonal so a skipped step fetches nothing), and only blocks the diagonal
crosses pay for the mask.  Block sizes come from the sequence length.
On a CPU backend the kernels run interpreted (``ops.pallas.interpret_mode``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ATTN_KERNEL_KEPT, interpret_mode

LANES = 128
NEG = -1e30          # a hidden score: exp(NEG - m) is exactly 0 in float32
_VMEM_LIMIT = 64 << 20


def fits(q_shape: tuple[int, ...], kv_shape: tuple[int, ...]) -> bool:
    """Whether the kernel takes these shapes: q ``[B, S, H, D]`` against
    k/v ``[B, S, KV, D]`` over the SAME positions, heads of 64 or 128
    (whole heads fill rows of 128 lanes), K/V heads a whole number of
    rows, query heads a whole number of groups, the sequence in blocks of
    128."""
    if len(q_shape) != 4 or len(kv_shape) != 4:
        return False
    b, s, h, d = q_shape
    kv = kv_shape[2]
    return (kv_shape == (b, s, kv, d) and d in (64, LANES) and kv > 0
            and h % kv == 0 and kv % (LANES // d) == 0 and s % LANES == 0)


def block_for(seq: int) -> int:
    """One rule for the q and the k blocks of all three kernels: up to
    1,024 positions the whole sequence is ONE block (a grid step a cell,
    nothing streamed); a longer one goes in blocks of 512, or of 256 or
    128 where 512 does not divide it.  A block is worked through in strips
    of query rows (:func:`_strips`), so its size sets what a grid step
    fetches and keeps, not the size of a tile of scores.  Read on the chip
    (PERF.md, PR 30): 24 layers forward + backward at [64, 1024, 16, 64],
    481 ms in one block against 633 ms in blocks of 512; 24 layers forward
    at [1, 16384, 28 over 4, 128], 595 ms in blocks of 512 against 911 ms
    in blocks of 1,024."""
    if seq <= 1024:
        return seq
    return next(block for block in (512, 256, LANES) if seq % block == 0)


# query rows of a strip where the diagonal crosses a block: thin strips
# waste least of it (at 1,024 positions 128: 481 ms, 256: 508, 512: 526,
# 1,024: 575; same source) ...
ROWS = LANES
# ... and of a strip below the diagonal, where nothing is wasted and a taller
# strip reuses each tile of keys the MXU holds for more rows (at 16,384
# positions 256: 595 ms, 512: 634, 128: 675)
BELOW_ROWS = 256


def _plan(pack: int, groups: int, d: int) -> tuple[tuple[int, int, int], ...]:
    """For each query head of a grid cell: (row of 128 lanes of the q block
    it lies in, its lane offset there, the lane offset of its K/V head in
    the K/V row)."""
    return tuple(((i * d) // LANES, (i * d) % LANES, (i // groups) * d)
                 for i in range(pack * groups))


def _lanes_of(shape, offset: int, d: int):
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= offset) & (lane < offset + d)


def _take(x, q_off: int, k_off: int, d: int):
    """A head's lanes of a q-side row ``x`` [rows, 128], moved to where its
    K/V head lies, zeros elsewhere."""
    if d == LANES:
        return x
    x = jnp.where(_lanes_of(x.shape, q_off, d), x, jnp.zeros_like(x))
    if q_off != k_off:
        # (the chip rotates 32-bit lanes only)
        x = pltpu.roll(x.astype(jnp.float32), (k_off - q_off) % LANES,
                       1).astype(x.dtype)
    return x


def _put(x, q_off: int, k_off: int, d: int):
    """The inverse of :func:`_take` for a result that is valid in the K/V
    head's lanes: moved back to the query head's lanes, zeros elsewhere."""
    if d == LANES:
        return x
    if q_off != k_off:
        x = pltpu.roll(x, (q_off - k_off) % LANES, 1)
    return jnp.where(_lanes_of(x.shape, q_off, d), x, jnp.zeros_like(x))


def _store(ref, plan, d: int, pieces) -> None:
    """Write a q-side block (o or dq) from one float32 piece per query head,
    each valid in its K/V head's lanes: moved back (:func:`_put`) and added
    up row of 128 lanes by row."""
    out: dict[int, jax.Array] = {}
    for (slab, q_off, k_off), piece in zip(plan, pieces):
        piece = _put(piece, q_off, k_off, d)
        out[slab] = piece if slab not in out else out[slab] + piece
    for slab, row in out.items():
        ref[0, :, slab * LANES:(slab + 1) * LANES] = row.astype(ref.dtype)


def _row(ref, slab: int):
    """Row-of-128-lanes ``slab`` of a block, every row of it."""
    return ref[0, :, slab * LANES:(slab + 1) * LANES]


def _heads(ref, plan, d: int, scale: float = 1.0, fold: bool = False):
    """Each query head of a q-side block (q or dO) over the block's whole
    height, taken ONCE per grid step (:func:`_take`), q with its scale
    (:func:`_scaled`); a strip then slices its rows off (:func:`_rows`)."""
    return [_scaled(_take(_row(ref, slab), q_off, k_off, d), scale, fold)
            for slab, q_off, k_off in plan]


def _rows(x, start: int, n: int):
    return lax.slice_in_dim(x, start, start + n, axis=0)


def _strips(crossed: bool, block_q: int, block_k: int, rows: int):
    """A block of scores as strips of ``rows`` query rows: (first row, rows,
    key columns the strip needs).  Below the diagonal a strip needs every
    column.  Where the diagonal crosses a block with block_q == block_k
    (so q_start == k_start), a strip needs the columns up to its own last
    row and no more: at 1,024 positions in one block, strips of 128 compute
    36 of the 64 squares of 128, where the mask needs 32.5."""
    if not crossed:
        rows = BELOW_ROWS
    rows = min(rows, block_q)
    rows = rows if block_q % rows == 0 else LANES
    return tuple(
        (start, rows, min(block_k, start + rows)
         if crossed and block_q == block_k else block_k)
        for start in range(0, block_q, rows))


def _visible(q_start, k_start, rows: int, cols: int, transposed: bool):
    """[rows, cols] (or its transpose): key position <= the query's."""
    shape = (cols, rows) if transposed else (rows, cols)
    q_axis = 1 if transposed else 0
    ahead = lax.sub(lax.broadcasted_iota(jnp.int32, shape, q_axis),
                    lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    return lax.ge(ahead, lax.broadcast(
        jnp.asarray(k_start - q_start, jnp.int32), shape))


def _on_blocks(q_start, k_start, block_q: int, block_k: int, single: bool,
               step) -> None:
    """Run ``step(crossed)`` for a block that reaches the causal triangle:
    with the mask where the diagonal crosses it, without below.  A
    sequence that is a ``single`` block is crossed, and nothing else is
    built into the kernel."""
    if single:
        step(True)
        return
    reaches = k_start <= q_start + block_q - 1
    crossed = k_start + block_k - 1 > q_start
    pl.when(reaches & crossed)(functools.partial(step, True))
    pl.when(reaches & jnp.logical_not(crossed))(functools.partial(step, False))


def _scaled(q, scale: float, fold: bool):
    """1 / sqrt(D) goes onto q where it is a power of two (heads of 64: an
    exponent shift, so q @ k.T comes out bit for bit as the scaled scores);
    otherwise it stays on the float32 scores."""
    return q * jnp.asarray(scale, q.dtype) if fold else q


# The arithmetic of one strip of one head, as pure functions of values; the
# kernels below only move blocks between references and these.  Written in
# ``jax.lax`` primitives: every strip has a width of its own, and a
# ``jax.numpy`` function is traced anew for every new shape it meets, which
# a step program's set-up pays on each start (1.5 s of 5.3 s of tracing on
# the chip's host: PERF.md, PR 30).
def _matmul(a, b, transpose_b: bool = False):
    """a @ b (or a @ b.T) on the MXU in the operands' dtype, float32 out."""
    contract = (((1,), (1 if transpose_b else 0,)), ((), ()))
    return lax.dot_general(a, b, contract,
                           preferred_element_type=jnp.float32)


def _along(x, like):
    """A per-row [n, 1] column or a per-column [1, n] row spread over the
    shape of ``like``."""
    return lax.broadcast_in_dim(x, like.shape, (0, 1))


def _scores(q, k, visible, scale: float, fold: bool, transposed: bool):
    """The masked, scaled scores of one strip in float32: [rows, cols], or
    [cols, rows] ``transposed``.  q and k go to the MXU as they are."""
    s = _matmul(k, q, True) if transposed else _matmul(q, k, True)
    if not fold:
        s = lax.mul(s, lax.full_like(s, scale))
    return s if visible is None else lax.select(visible, s,
                                                lax.full_like(s, NEG))


def _softmax_strip(q, k, v, visible, m_prev, l_prev, acc_prev, *, scale,
                   fold):
    """One online-softmax update: (running max, denominator, accumulator)
    of the rows of ``q`` after the keys ``k``/``v``."""
    s = _scores(q, k, visible, scale, fold, False)
    m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(s, (1,)), (1,)))
    p = lax.exp(lax.sub(s, _along(m_new, s)))
    alpha = lax.exp(lax.sub(m_prev, m_new))
    l_new = lax.add(lax.mul(alpha, l_prev),
                    lax.expand_dims(lax.reduce_sum(p, (1,)), (1,)))
    # valid in the K/V head's lanes; the rest is dropped at the end
    acc_new = lax.add(lax.mul(_along(alpha, acc_prev), acc_prev),
                      _matmul(lax.convert_element_type(p, v.dtype), v))
    return m_new, l_new, acc_new


def _dq_strip(q, g, k, v, visible, lse, delta, *, scale, fold):
    """ds @ K for the rows of ``q``: p = exp(s - lse), ds = p (dp - delta),
    ``lse`` and ``delta`` columns."""
    s = _scores(q, k, visible, scale, fold, False)
    p = lax.exp(lax.sub(s, _along(lse, s)))
    dp = _matmul(g, v, True)
    ds = lax.mul(p, lax.sub(dp, _along(delta, dp)))
    return _matmul(lax.convert_element_type(ds, k.dtype), k)


def _dkv_strip(q, g, k, v, visible, lse, delta, *, scale, fold):
    """(ds.T @ q, p.T @ dO) for the keys ``k``/``v`` from the query rows of
    ``q``, everything transposed: ``lse`` and ``delta`` are rows."""
    s_t = _scores(q, k, visible, scale, fold, True)
    p_t = lax.exp(lax.sub(s_t, _along(lse, s_t)))
    dv = _matmul(lax.convert_element_type(p_t, g.dtype), g)
    dp_t = _matmul(v, g, True)
    ds_t = lax.mul(p_t, lax.sub(dp_t, _along(delta, dp_t)))
    # (a folded q carries the scale that dk needs)
    dk = _matmul(lax.convert_element_type(ds_t, q.dtype), q)
    return dk, dv


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, plan, d: int, scale: float, fold: bool, block_q: int,
                block_k: int, rows: int, single: bool):
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(crossed: bool):
        queries = _heads(q_ref, plan, d, scale, fold)
        for start, n, cols in _strips(crossed, block_q, block_k, rows):
            k, v = k_ref[0, :cols], v_ref[0, :cols]     # [cols, 128]
            at = slice(start, start + n)
            visible = _visible(q_start + start, k_start, n, cols,
                               False) if crossed else None
            for i, q in enumerate(queries):
                m_ref[i, at], l_ref[i, at], acc_ref[i, at] = _softmax_strip(
                    _rows(q, start, n), k, v, visible, m_ref[i, at],
                    l_ref[i, at], acc_ref[i, at], scale=scale, fold=fold)

    _on_blocks(q_start, k_start, block_q, block_k, single, step)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        n = len(plan)
        _store(o_ref, plan, d, [acc_ref[i] / l_ref[i] for i in range(n)])
        for i in range(n):
            # one logsumexp per row, rows along the lanes (a [S, 1] column
            # in HBM would pad every value to a row of 128)
            lse_ref[0, 0, i:i + 1, :] = (m_ref[i] + jnp.log(l_ref[i])).T


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref, dq_ref, delta_ref,
               acc_ref, lse_col, delta_col, *, plan, d: int, scale: float,
               fold: bool, block_q: int, block_k: int, rows: int,
               single: bool):
    """dQ of one q block, K/V streaming.  ds = p * (dp - delta) and
    dq = scale * ds @ K.  ``delta`` (the row sums of dO * O) is computed
    here from the resident blocks and handed to the dK/dV kernel, rows
    along the lanes like the logsumexp."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for i, (slab, q_off, _) in enumerate(plan):
            go = (_row(g_ref, slab).astype(jnp.float32)
                  * _row(o_ref, slab).astype(jnp.float32))
            if d != LANES:
                go = jnp.where(_lanes_of(go.shape, q_off, d), go, 0.0)
            delta = jnp.sum(go, axis=-1, keepdims=True)
            delta_col[i] = delta
            delta_ref[0, 0, i:i + 1, :] = delta.T
            lse_col[i] = lse_ref[0, 0, i:i + 1, :].T

    def step(crossed: bool):
        queries = _heads(q_ref, plan, d, scale, fold)
        cotangents = _heads(g_ref, plan, d)
        for start, n, cols in _strips(crossed, block_q, block_k, rows):
            k, v = k_ref[0, :cols], v_ref[0, :cols]
            at = slice(start, start + n)
            visible = _visible(q_start + start, k_start, n, cols,
                               False) if crossed else None
            for i, (q, g) in enumerate(zip(queries, cotangents)):
                acc_ref[i, at] = lax.add(acc_ref[i, at], _dq_strip(
                    _rows(q, start, n), _rows(g, start, n), k, v, visible,
                    lse_col[i, at], delta_col[i, at], scale=scale,
                    fold=fold))

    _on_blocks(q_start, k_start, block_q, block_k, single, step)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finalize():
        _store(dq_ref, plan, d,
               [acc_ref[i] * scale for i in range(len(plan))])


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, plan, d: int, scale: float,
                fold: bool, block_q: int, block_k: int, rows: int,
                single: bool):
    """dK/dV of one k block, Q/dO streaming.  Scores are computed
    TRANSPOSED ([keys, queries]) so that p.T and ds.T, which the two
    products need, are what the exponent yields, and the per-row logsumexp
    and delta are read as stored, rows along the lanes.  A query head's q
    and dO are zero outside its K/V head's lanes, so every head of the
    group adds into ONE accumulator: dK/dV come out K/V-sized."""
    kj, qi = pl.program_id(2), pl.program_id(3)
    q_start, k_start = qi * block_q, kj * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(crossed: bool):
        queries = _heads(q_ref, plan, d, scale, fold)
        cotangents = _heads(g_ref, plan, d)
        for start, n, cols in _strips(crossed, block_q, block_k, rows):
            k, v = k_ref[0, :cols], v_ref[0, :cols]
            at = slice(start, start + n)
            visible = _visible(q_start + start, k_start, n, cols,
                               True) if crossed else None
            for i, (q, g) in enumerate(zip(queries, cotangents)):
                dk, dv = _dkv_strip(
                    _rows(q, start, n), _rows(g, start, n), k, v, visible,
                    lse_ref[0, 0, i:i + 1, at], delta_ref[0, 0, i:i + 1, at],
                    scale=scale, fold=fold)
                dk_acc[:cols] = lax.add(dk_acc[:cols], dk)
                dv_acc[:cols] = lax.add(dv_acc[:cols], dv)

    _on_blocks(q_start, k_start, block_q, block_k, single, step)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finalize():
        dk = dk_acc[...]
        dk_ref[0] = (dk if fold else dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


class _Calls:
    """The three ``pallas_call``s of one shape, built once: a call that
    comes again (the forward pass runs as the primal and as the vjp's
    forward rule; an unrolled model calls every layer) reuses the traced
    kernel instead of tracing some hundreds of operations again."""

    def __init__(self, batch: int, seq: int, heads: int, kv_heads: int,
                 d: int, dtype, block_q: int, block_k: int, rows: int,
                 interpret: bool):
        pack, groups = LANES // d, heads // kv_heads
        plan = _plan(pack, groups, d)
        n = len(plan)
        cells = kv_heads // pack                    # K/V rows of 128 lanes
        q_lanes = n * d                             # q lanes of one cell
        nq, nk = seq // block_q, seq // block_k
        scale = 1.0 / math.sqrt(d)
        static = dict(plan=plan, d=d, scale=scale,
                      fold=math.frexp(scale)[0] == 0.5, block_q=block_q,
                      block_k=block_k, rows=rows, single=nq == nk == 1)

        def call(kernel, grid, in_specs, out_specs, out_shape, scratch):
            return pl.pallas_call(
                functools.partial(kernel, **static),
                grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shape, scratch_shapes=scratch,
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel", "parallel",
                                         "arbitrary"),
                    vmem_limit_bytes=_VMEM_LIMIT),
                interpret=interpret)

        def last_k(i):      # the last k block q block i can see
            return ((i + 1) * block_q - 1) // block_k

        def first_q(j):     # the first q block that sees k block j
            return (j * block_k) // block_q

        q_like = jax.ShapeDtypeStruct((batch, seq, heads * d), dtype)
        kv_like = jax.ShapeDtypeStruct((batch, seq, kv_heads * d), dtype)
        per_row = jax.ShapeDtypeStruct((batch, cells, n, seq), jnp.float32)
        q_spec = pl.BlockSpec((1, block_q, q_lanes),
                              lambda b, c, i, j: (b, i, c))
        kv_spec = pl.BlockSpec(
            (1, block_k, LANES),
            lambda b, c, i, j: (b, jnp.minimum(j, last_k(i)), c))
        row_spec = pl.BlockSpec((1, 1, n, block_q),
                                lambda b, c, i, j: (b, c, 0, i))
        column = pltpu.VMEM((n, block_q, 1), jnp.float32)
        # (q, k, v) -> (o, logsumexp)
        self.forward = call(
            _fwd_kernel, (batch, cells, nq, nk), [q_spec, kv_spec, kv_spec],
            [q_spec, row_spec], [q_like, per_row],
            [pltpu.VMEM((n, block_q, LANES), jnp.float32), column, column])
        # (q, k, v, o, dO, logsumexp) -> (dq, delta)
        self.dq = call(
            _dq_kernel, (batch, cells, nq, nk),
            [q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
            [q_spec, row_spec], [q_like, per_row],
            [pltpu.VMEM((n, block_q, LANES), jnp.float32), column, column])

        # roles swap: a k block stays, the q blocks that see it stream past
        def streamed(j, i):
            return jnp.maximum(i, first_q(j))

        q_stream = pl.BlockSpec((1, block_q, q_lanes),
                                lambda b, c, j, i: (b, streamed(j, i), c))
        row_stream = pl.BlockSpec(
            (1, 1, n, block_q), lambda b, c, j, i: (b, c, 0, streamed(j, i)))
        kv_stay = pl.BlockSpec((1, block_k, LANES),
                               lambda b, c, j, i: (b, j, c))
        # (q, k, v, dO, logsumexp, delta) -> (dk, dv)
        self.dkv = call(
            _dkv_kernel, (batch, cells, nk, nq),
            [q_stream, kv_stay, kv_stay, q_stream, row_stream, row_stream],
            [kv_stay, kv_stay], [kv_like, kv_like],
            [pltpu.VMEM((block_k, LANES), jnp.float32),
             pltpu.VMEM((block_k, LANES), jnp.float32)])


@functools.lru_cache(maxsize=64)
def _calls(*key) -> _Calls:
    return _Calls(*key)


def _calls_for(q, heads, kv_heads, block_q, block_k, rows, interpret):
    batch, seq, width = q.shape
    return _calls(batch, seq, heads, kv_heads, width // heads,
                  jnp.dtype(q.dtype), block_q, block_k, rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _attention(q, k, v, *shape):
    """q [B, S, H*D], k/v [B, S, KV*D] -> o like q."""
    return _calls_for(q, *shape).forward(q, k, v)[0]


def _attention_fwd(q, k, v, *shape):
    o, lse = map(checkpoint_name, _calls_for(q, *shape).forward(q, k, v),
                 ATTN_KERNEL_KEPT)
    return o, (q, k, v, o, lse)     # lse: [B, cells, heads of a cell, S]


def _attention_bwd(*args):
    *shape, (q, k, v, o, lse), g = args
    calls = _calls_for(q, *shape)
    dq, delta = calls.dq(q, k, v, o, g, lse)
    dk, dv = calls.dkv(q, k, v, g, lse, delta)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def fused_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           block_q: int | None = None,
                           block_k: int | None = None, rows: int = ROWS,
                           interpret: bool | None = None) -> jax.Array:
    """Causal attention, q [B, S, H, D] against k/v [B, S, KV, D] (grouped
    K/V heads unexpanded) -> [B, S, H, D]; a drop-in for
    ``models.transformer.causal_attention`` wherever :func:`fits` holds,
    differentiable, with O(S) residuals.  The blocks default to
    :func:`block_for` of the sequence length."""
    if not fits(q.shape, k.shape) or v.shape != k.shape:
        raise ValueError(
            f"fused_causal_attention does not take q {q.shape} against "
            f"k {k.shape}, v {v.shape}: see fits()")
    b, s, h, d = q.shape
    kv = k.shape[2]
    block_q = block_q or block_for(s)
    block_k = block_k or block_for(s)
    if (s % block_q or s % block_k or block_q % LANES or block_k % LANES
            or rows % LANES):
        raise ValueError(f"blocks ({block_q}, {block_k}) and strips of "
                         f"{rows} rows must be multiples of {LANES} that "
                         f"divide the sequence length {s}")
    if interpret is None:
        interpret = interpret_mode(q, k, v)
    out = _attention(q.reshape(b, s, h * d), k.reshape(b, s, kv * d),
                     v.reshape(b, s, kv * d), h, kv, block_q, block_k,
                     min(rows, block_q), bool(interpret))
    return out.reshape(b, s, h, d)
