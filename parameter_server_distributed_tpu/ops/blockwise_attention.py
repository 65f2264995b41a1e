"""Blockwise online-softmax causal attention in plain XLA ops.

One recurrence, :func:`blockwise_attention`: running max, rescaled
accumulator and denominator per key block, query blocks one after another
(``lax.map``) and for each a ``lax.scan`` over the key blocks its mask can
meet, so XLA compiles it natively on every backend, the memory is one
block of scores and the work follows the mask (causal, and a window where
one is given).  Grouped K/V stay unexpanded: query-head groups contract
against [B, M, KV, D] directly.  It is the default path's long-sequence
arm off the chip and under a window (``models/transformer.device_arm``)
and what ``models/generation.py`` extends a long cached prefix with.

No reference analogue (the reference has no model layer — SURVEY.md §1).
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


def blockwise_attention(q: Array, k: Array, v: Array | None, starts: Array,
                        *, window: int = 0, block_q: int = 512,
                        block_k: int = 512,
                        expand: Callable | None = None) -> Array:
    """Causal (and, with ``window`` W > 0, windowed) attention of a block
    of queries against keys stored BY POSITION, a block of scores at a
    time, with the key blocks that the mask hides entirely skipped.

    q [B, T, H, D] are the queries at positions starts[b] .. starts[b]+T-1;
    k/v [B, M, KV, D] hold position j at index j (a whole sequence for a
    forward pass, where starts is 0 and M == T; a cached prefix followed
    by the block itself for an extension).  Query i sees key j where
    j <= i and, under a window, i - j < W.  Returns [B, T, H, D] in q's
    dtype.

    ``expand``: the keys and values are not stored, they are MADE a key
    block at a time.  ``k`` is then what is stored, [B, M, ...] by
    position (``v`` None), and ``expand(k[:, block])`` gives the block's
    (K [B, block_k, H, D], V [B, block_k, H, Dv]), a head each for every
    query head: what a latent layer's rows are to its K and V.  Nothing M
    positions long is ever held expanded, and a block the mask hides is
    not expanded at all; a block is expanded once for every query block
    that meets it.  Returns [B, T, H, Dv].

    Query blocks run one after another (``lax.map``), and for each the key
    blocks from the last one it can see downwards: a ``lax.scan`` of the
    static count a query block can ever meet (all of them without a
    window, about W / block_k with one) whose body is a ``lax.cond`` that
    does nothing for a block outside the mask of every query in it.  So
    the work follows the mask, the memory is one block of scores, and the
    whole is differentiable.
    """
    b, t, h, d = q.shape
    m, kv = k.shape[1], (k.shape[2] if expand is None else h)
    g = h // kv
    block_q = min(block_q, t)
    block_k = min(block_k, m)
    d_v = v.shape[-1] if expand is None else jax.eval_shape(
        expand, jax.ShapeDtypeStruct((b, block_k) + k.shape[2:], k.dtype)
    )[1].shape[-1]
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
                if expand is None:
                    v_j = jax.lax.dynamic_slice_in_dim(v, begin, block_k, 1)
                else:
                    k_j, v_j = expand(k_j)
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
                pv = jnp.einsum("begqj,bjed->begqd", p.astype(v_j.dtype), v_j,
                                preferred_element_type=jnp.float32)
                return (acc * alpha[..., None] + pv, new_top,
                        denom * alpha + jnp.sum(p, axis=-1))

            return jax.lax.cond(outside, lambda c: c, update, carry), None

        init = (jnp.zeros((b, kv, g, block_q, d_v), jnp.float32),
                jnp.full((b, kv, g, block_q), -jnp.inf, jnp.float32),
                jnp.zeros((b, kv, g, block_q), jnp.float32))
        (acc, _, denom), _ = jax.lax.scan(
            key_block, init, jnp.arange(meets, dtype=jnp.int32))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return jnp.moveaxis(out, 3, 1).astype(q.dtype)   # [B,bq,KV,G,D]

    blocks = jax.lax.map(query_block, (jnp.arange(nq, dtype=jnp.int32),
                                       jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, nq * block_q, h, d_v)
    return out[:, :t]
