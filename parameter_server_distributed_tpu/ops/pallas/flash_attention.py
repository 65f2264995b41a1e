"""Pallas TPU flash attention (causal) — blockwise forward AND backward.

Blockwise attention computed entirely in VMEM with online softmax — the
single-device analogue of ring attention (ops/ring_attention.py): same
accumulation math, but blocks stream from HBM instead of rotating over ICI.

All kernels stream K/V (or Q/dO) through the innermost grid dimension, so
VMEM residency per step is O(block^2) regardless of sequence length — no
full-sequence tensor is ever resident.  Running state (online-softmax
m/l/acc, grad accumulators) lives in f32 VMEM scratch that persists across
the sequential TPU grid; outputs are written once in the stream's final
step, in the input dtype.  Blocks entirely outside the causal triangle are skipped twice
over: `pl.when` skips the compute, and the streaming index_map CLAMPS the
block index to the causal frontier so consecutive out-of-range steps
revisit the same resident block and trigger no HBM DMA — block fetch count
matches the old per-kernel fori_loop frontier exactly.

Backward is the standard two-kernel flash decomposition: the forward saves
only O and the per-row logsumexp (O(S) residuals, not the O(S^2) attention
matrix), probabilities are recomputed blockwise from them (the
softmax-jacobian delta row term is recomputed in-kernel from O/dO rather
than materialized in HBM):

- dQ kernel: grid (BH, q-blocks, k-blocks), K/V streaming innermost;
- dK/dV kernel: grid (BH, k-blocks, q-blocks), Q/dO streaming innermost.

On a CPU backend the kernels run in interpret mode
(ops/pallas.interpret_mode), so tests exercise identical code paths there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

NEG_INF = -1e30


def _iota_pos(start, rows: int, cols: int, axis: int):
    return start + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), axis)


def _kv_frontier_spec(block: int, block_q: int, block_k: int, d: int,
                      bps: int):
    """BlockSpec for a K/V operand streamed over inner grid dim j, with the
    block index clamped to the causal frontier of q block i: steps past the
    frontier revisit the resident block (no DMA) and `pl.when` skips their
    compute.

    ``bps`` = q blocks per sequence SEGMENT: under the GQA fold
    (:func:`flash_attention_gqa`) the q-rows axis is G segments of S rows
    sharing one K/V sequence, so the frontier depends on i's position
    WITHIN its segment (i % bps), not on i itself.  bps == total q blocks
    reduces to the plain single-segment layout."""
    def clamp(i, j):
        i_pos = jax.lax.rem(i, bps)
        return jnp.minimum(j, ((i_pos + 1) * block_q - 1) // block_k)

    return pl.BlockSpec((1, block, d), lambda b, i, j: (b, clamp(i, j), 0))


def _q_frontier_spec(block: int, block_q: int, block_k: int, *,
                     bps: int, d: int | None = None):
    """BlockSpec for a Q/dO operand streamed over inner grid dim j in the
    dK/dV kernel: indices before this k block's first attending q block are
    clamped up to it — per SEGMENT under the GQA fold (the clamp floor
    repeats every ``bps`` q blocks, so within-segment pre-frontier steps
    revisit the resident block while segment boundaries restart the
    stream).  d=None selects the lane-major per-row layout
    (lse: (BH, 1, S) blocked (1, 1, block), see _flash_fwd)."""
    def clamp(i, j):
        j_seg = jax.lax.rem(j, bps)
        seg = j // bps
        return seg * bps + jnp.maximum(j_seg, (i * block_k) // block_q)

    if d is None:
        return pl.BlockSpec((1, 1, block), lambda b, i, j: (b, 0, clamp(i, j)))
    return pl.BlockSpec((1, block, d), lambda b, i, j: (b, clamp(i, j), 0))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, block_q: int, block_k: int, scale: float,
                      bps: int):
    qi, kj = pl.program_id(1), pl.program_id(2)
    # q position is segment-relative: under the GQA fold the q-rows axis
    # is G segments of S rows sharing one K/V sequence (bps blocks each)
    q_start, k_start = jax.lax.rem(qi, bps) * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k_start < q_start + block_q)  # block touches causal triangle
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale       # [block_q, D]
        k = k_ref[0].astype(jnp.float32)               # [block_k, D]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        mask = (_iota_pos(q_start, block_q, 1, 0)
                >= _iota_pos(k_start, 1, block_k, 1))
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # lse rows live along lanes in HBM (see _flash_fwd layout note)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).T      # [1, block_q]


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, block_q: int,
               block_k: int, interpret: bool,
               bps: int = 0) -> tuple[jax.Array, jax.Array]:
    """q,k,v: [BH, S, D] -> (o [BH, S, D], lse [BH, 1, S]).

    lse layout: one logsumexp per q row, stored LANE-major as (BH, 1, S)
    and blocked (1, 1, block_q).  The naive (BH, S) array blocked
    (1, block_q) violates Mosaic's last-two-dims tiling rule, and the
    sublane-major (BH, S, 1) alternative satisfies it but lane-pads 1->128
    (a 128x HBM expansion — 2 GB at batch 256).  Lane-major costs one
    (block_q, 1)->(1, block_q) transpose per q-block finalize and pads
    only sublanes (1->8)."""
    bh, s, d = q.shape
    sk = k.shape[1]          # K/V sequence (= s unless GQA-folded)
    bps = bps or s // block_q
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q,
                               block_k=block_k, scale=scale, bps=bps)
    qblk = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    qrow = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    kblk = _kv_frontier_spec(block_k, block_q, block_k, d, bps)
    o, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype),      # o
                   jax.ShapeDtypeStruct((bh, 1, s), jnp.float32)],  # lse
        grid=(bh, s // block_q, sk // block_k),
        in_specs=[qblk, kblk, kblk],
        out_specs=[qblk, qrow],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),   # acc
                        pltpu.VMEM((block_q, 1), jnp.float32),   # m
                        pltpu.VMEM((block_q, 1), jnp.float32)],  # l
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref,
                         dq_ref, acc_ref, delta_ref, *, block_q: int,
                         block_k: int, scale: float, bps: int):
    """dQ for one q block, K/V streaming over the inner grid dimension.
    ds = p * (dp - delta); dq = scale * ds @ K.  Accumulates in f32 VMEM
    scratch and writes the (possibly bf16) output once at stream end —
    an f32 output array would double the HBM footprint (and pad 2x when
    D=64).  delta (softmax-jacobian row correction sum_d g*o) is computed
    here from the resident o/g blocks rather than materialized in HBM."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    q_start, k_start = jax.lax.rem(qi, bps) * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        delta_ref[...] = jnp.sum(
            g_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True)

    @pl.when(k_start < q_start + block_q)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        lse = lse_ref[0].T                             # [block_q, 1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = (_iota_pos(q_start, block_q, 1, 0)
                >= _iota_pos(k_start, 1, block_k, 1))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[...])
        acc_ref[...] += jnp.dot(ds, k,
                                preferred_element_type=jnp.float32) * scale

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                          block_k: int, scale: float, bps: int):
    """dK/dV for one k block, Q/dO streaming over the inner grid dimension.
    dv = p^T @ dO; dk = scale * ds^T @ Q.  Same scratch-accumulate /
    write-once layout as the dQ kernel; delta is recomputed per streamed
    q block (one [block_q, D] elementwise reduce — cheap next to the four
    matmuls)."""
    ki, qj = pl.program_id(1), pl.program_id(2)
    k_start = ki * block_k
    q_start = jax.lax.rem(qj, bps) * block_q

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(q_start + block_q > k_start)  # q block reaches this k block
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        g = g_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        lse = lse_ref[0].T                             # [block_q, 1]
        delta = jnp.sum(
            g * o_ref[0].astype(jnp.float32), axis=-1, keepdims=True)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = (_iota_pos(q_start, block_q, 1, 0)
                >= _iota_pos(k_start, 1, block_k, 1))
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)   # [block_q, block_k]
        dv_acc[...] += jnp.dot(p.T, g, preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[...] += jnp.dot(ds.T, q,
                               preferred_element_type=jnp.float32) * scale

    @pl.when(qj == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, g, block_q: int, block_k: int,
               interpret: bool, bps: int = 0):
    bh, s, d = q.shape
    sk = k.shape[1]          # K/V sequence (= s unless GQA-folded)
    bps = bps or s // block_q
    scale = 1.0 / math.sqrt(d)

    qblk = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    qrow = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    kblk = _kv_frontier_spec(block_k, block_q, block_k, d, bps)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, scale=scale, bps=bps),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        grid=(bh, s // block_q, sk // block_k),
        in_specs=[qblk, kblk, kblk, qblk, qblk, qrow],
        out_specs=qblk,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret=interpret,
    )(q, k, v, o, g, lse)

    # streaming roles swap: k blocks are the outer (revisited) dimension;
    # under the GQA fold every k block streams ALL G segments' q blocks,
    # so dK/dV come back kv_heads-sized with the group sum built in
    kout = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    qstream = _q_frontier_spec(block_q, block_q, block_k, bps=bps, d=d)
    qstream_row = _q_frontier_spec(block_q, block_q, block_k, bps=bps)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, scale=scale, bps=bps),
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        grid=(bh, sk // block_k, s // block_q),
        in_specs=[qstream, kout, kout, qstream, qstream, qstream_row],
        out_specs=[kout, kout],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, o, g, lse)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, block_q, block_k, interpret, bps=0):
    o, _ = _flash_fwd(q, k, v, block_q, block_k, interpret, bps)
    return o


def _flash_vjp_fwd(q, k, v, block_q, block_k, interpret, bps=0):
    o, lse = _flash_fwd(q, k, v, block_q, block_k, interpret, bps)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(block_q, block_k, interpret, bps, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_bwd(q, k, v, o, lse, g, block_q, block_k, interpret, bps)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Causal flash attention, [B, S, H, D] -> [B, S, H, D] (drop-in for
    models.transformer.causal_attention)."""
    b, s, h, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must divide by blocks "
                         f"({block_q}, {block_k})")
    if interpret is None:
        interpret = interpret_mode(q, k, v)

    def fold(x):  # [B,S,H,D] -> [B*H, S, D]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)

    out = _flash(fold(q), fold(k), fold(v), block_q, block_k, interpret)
    return jnp.transpose(out.reshape(b, h, s, d), (0, 2, 1, 3))


def flash_attention_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None) -> jax.Array:
    """Causal flash attention with UNexpanded GQA K/V: q [B, S, H, D],
    k/v [B, S, KV, D] -> [B, S, H, D].

    Instead of repeating K/V up to H heads (G x the HBM capacity and
    expand-materialization traffic of :func:`flash_attention` after
    expand_gqa), the G query heads of each kv head fold into the q-rows
    axis: q becomes [B*KV, G*S, D] against k/v [B*KV, S, D].  The kernels
    treat the folded axis as G causal SEGMENTS sharing one K/V sequence
    (segment-relative positions + frontier clamps, ``bps`` = blocks per
    segment), and the dK/dV kernel streams all G segments' q blocks per k
    block — so dK/dV come back kv_heads-sized with the group reduction
    built in, never materializing H-sized K/V gradients."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"query heads {h} must divide by kv heads {kv}")
    groups = h // kv
    if groups == 1:
        return flash_attention(q, k, v, block_q, block_k, interpret)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must divide by blocks "
                         f"({block_q}, {block_k})")
    if interpret is None:
        interpret = interpret_mode(q, k, v)

    # head h = kv_head * G + group (repeat_kv convention)
    qf = jnp.transpose(q.reshape(b, s, kv, groups, d),
                       (0, 2, 3, 1, 4)).reshape(b * kv, groups * s, d)

    def fold_kv(x):  # [B,S,KV,D] -> [B*KV, S, D]
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * kv, s, d)

    out = _flash(qf, fold_kv(k), fold_kv(v), block_q, block_k, interpret,
                 s // block_q)
    out = out.reshape(b, kv, groups, s, d)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s, h, d)
