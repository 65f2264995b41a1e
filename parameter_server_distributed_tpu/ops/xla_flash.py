"""Blockwise online-softmax causal attention in plain XLA ops.

The same flash-attention recurrence as the pallas kernels
(ops/pallas/flash_attention.py) — running max / rescaled accumulator /
denominator per K/V block — but expressed as a ``lax.scan`` over key
blocks so XLA compiles it natively on EVERY backend.  Three uses:

- the long-context path anywhere pallas is unavailable or the shapes
  don't fit its tiling (on a CPU backend the pallas kernels run in
  interpret mode, which is orders of magnitude slower than compiled
  code);
- an apples-to-apples A/B contender for the pallas kernels on TPU
  (`--attention=xla_flash`; which one wins is not measured on the
  chip);
- long sequences on a host: dense attention materializes the
  [B, H, S, S] probability tensor (4 GB at S=8192, H=16, f32) while
  this streams O(S * block) working sets.

Memory: forward residuals are O(S) (out, running stats) — the scan body
is wrapped in ``jax.checkpoint`` so the backward pass recomputes each
block's probabilities instead of saving them, exactly the flash backward
trade.  GQA K/V stay UNexpanded: query-head groups contract against the
[B, S, KV, D] cache directly (no materialized repeat), mirroring
models/generation.decode_block.

No reference analogue (the reference has no model layer — SURVEY.md §1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("block_k",))
def xla_flash_attention(q: Array, k: Array, v: Array,
                        block_k: int = 512) -> Array:
    """Causal attention, blockwise-streamed over keys.

    q: [B, S, H, D]; k/v: [B, S, H, D] or GQA [B, S, KV, D] (unexpanded).
    Returns [B, S, H, D] in q's dtype.  S must divide by ``block_k``
    (callers pick block_k = min(block_k, S); see :func:`auto_block`).
    """
    b, s, h, d = q.shape
    kv = k.shape[2]
    if h % kv:
        raise ValueError(f"query heads {h} must divide by kv heads {kv}")
    g = h // kv
    if s % block_k:
        raise ValueError(f"seq {s} must divide by block_k {block_k}")
    nk = s // block_k
    scale = 1.0 / math.sqrt(d)

    # head h = kv_head * G + group (repeat_kv convention, matching
    # expand_gqa / flash_attention_gqa)
    qg = q.reshape(b, s, kv, g, d)
    kb = k.reshape(b, nk, block_k, kv, d)
    vb = v.reshape(b, nk, block_k, kv, d)
    q_pos = jnp.arange(s, dtype=jnp.int32)

    def block_update(carry, xs):
        acc, m, l = carry                     # [B,KV,G,S,D], [B,KV,G,S], ...
        j, k_j, v_j = xs                      # k_j/v_j: [B, block_k, KV, D]
        scores = jnp.einsum("bqegd,bjed->begqj", qg, k_j,
                            preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jnp.arange(block_k, dtype=jnp.int32)
        mask = q_pos[:, None] >= k_pos[None, :]           # [S, block_k]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # fully-masked rows keep m == -inf; exp(-inf - -inf) must be 0,
        # not nan, so clamp the shift for those rows
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m - shift)
        p = jnp.exp(scores - shift[..., None])            # [B,KV,G,S,Bk]
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("begqj,bjed->begqd", p, v_j,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (acc_new, m_new, l_new), None

    init = (jnp.zeros((b, kv, g, s, d), jnp.float32),
            jnp.full((b, kv, g, s), -jnp.inf, jnp.float32),
            jnp.zeros((b, kv, g, s), jnp.float32))
    xs = (jnp.arange(nk, dtype=jnp.int32),
          jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))
    # checkpoint: backward recomputes each block's probabilities instead
    # of keeping S^2 residuals — the flash backward memory trade
    (acc, _, l), _ = jax.lax.scan(jax.checkpoint(block_update), init, xs)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, h, d).astype(q.dtype)


def auto_block(seq: int, block_k: int = 512) -> int:
    """Largest divisor-of-seq block not exceeding ``block_k``."""
    block = min(block_k, seq)
    while seq % block:
        block -= 1
    return block


def make_xla_flash_attention(block_k: int = 512):
    """Attention-fn factory matching the Transformer contract
    (models/transformer.py attention_fn: q [B,S,H,D], k/v [B,S,KV,D])."""
    def attend(q: Array, k: Array, v: Array) -> Array:
        return xla_flash_attention(q, k, v,
                                   block_k=auto_block(q.shape[1], block_k))
    return attend


def blockwise_attention(q: Array, k: Array, v: Array, starts: Array, *,
                        window: int = 0, block_q: int = 512,
                        block_k: int = 512) -> Array:
    """Causal (and, with ``window`` W > 0, windowed) attention of a block
    of queries against keys stored BY POSITION, a block of scores at a
    time, with the key blocks that the mask hides entirely skipped.

    q [B, T, H, D] are the queries at positions starts[b] .. starts[b]+T-1;
    k/v [B, M, KV, D] hold position j at index j (a whole sequence for a
    forward pass, where starts is 0 and M == T; a cached prefix followed
    by the block itself for an extension).  Query i sees key j where
    j <= i and, under a window, i - j < W.  Returns [B, T, H, D] in q's
    dtype.

    Query blocks run one after another (``lax.map``), and for each the key
    blocks from the last one it can see downwards: a ``lax.scan`` of the
    static count a query block can ever meet (all of them without a
    window, about W / block_k with one) whose body is a ``lax.cond`` that
    does nothing for a block outside the mask of every query in it.  So
    the work follows the mask, the memory is one block of scores, and the
    whole is differentiable.
    """
    b, t, h, d = q.shape
    m, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q = min(block_q, t)
    block_k = min(block_k, m)
    pad = -t % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = (t + pad) // block_q
    nk = -(-m // block_k)
    # key blocks one query block can meet: every one without a window
    # (and for several rows, whose starts may lie anywhere), else those
    # under its span of W - 1 + block_q positions
    meets = nk if not window or b > 1 else min(
        nk, (window - 1 + block_q - 1) // block_k + 2)
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, nq, block_q, kv, g, d)
    offsets = jnp.arange(block_q, dtype=jnp.int32)
    k_offsets = jnp.arange(block_k, dtype=jnp.int32)

    def query_block(args):
        qi, q_blk = args                                  # [B,bq,KV,G,D]
        q_pos = starts[:, None] + qi * block_q + offsets  # [B, bq]
        last = jnp.max(q_pos)
        first = jnp.min(q_pos)
        top = jnp.minimum(last // block_k, nk - 1)        # highest block

        def key_block(carry, step):
            kb = top - step
            # the block's real start; the last block of an M that does
            # not divide is read shifted back, and what the block before
            # it already covered is masked out
            begin = jnp.minimum(kb * block_k, m - block_k)
            k_pos = begin + k_offsets                     # [bk]
            outside = (kb < 0) | ((window > 0)
                                  & ((kb + 1) * block_k - 1 <= first - window))

            def update(carry):
                acc, top_score, denom = carry
                k_j = jax.lax.dynamic_slice_in_dim(k, begin, block_k, 1)
                v_j = jax.lax.dynamic_slice_in_dim(v, begin, block_k, 1)
                scores = jnp.einsum(
                    "bqegd,bjed->begqj", q_blk, k_j,
                    preferred_element_type=jnp.float32) * scale
                seen = ((k_pos[None, None, :] <= q_pos[:, :, None])
                        & (k_pos >= kb * block_k)[None, None, :])
                if window:
                    seen &= (q_pos[:, :, None] - k_pos[None, None, :]
                             < window)
                scores = jnp.where(seen[:, None, None], scores, -jnp.inf)
                new_top = jnp.maximum(top_score, jnp.max(scores, axis=-1))
                shift = jnp.where(jnp.isneginf(new_top), 0.0, new_top)
                alpha = jnp.exp(top_score - shift)
                p = jnp.exp(scores - shift[..., None])
                pv = jnp.einsum("begqj,bjed->begqd", p.astype(v.dtype), v_j,
                                preferred_element_type=jnp.float32)
                return (acc * alpha[..., None] + pv, new_top,
                        denom * alpha + jnp.sum(p, axis=-1))

            return jax.lax.cond(outside, lambda c: c, update, carry), None

        init = (jnp.zeros((b, kv, g, block_q, d), jnp.float32),
                jnp.full((b, kv, g, block_q), -jnp.inf, jnp.float32),
                jnp.zeros((b, kv, g, block_q), jnp.float32))
        (acc, _, denom), _ = jax.lax.scan(
            key_block, init, jnp.arange(meets, dtype=jnp.int32))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)   # [B,bq,KV,G,D]

    blocks = jax.lax.map(query_block, (jnp.arange(nq, dtype=jnp.int32),
                                       jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nq * block_q, h, d)
    return out[:, :t]
