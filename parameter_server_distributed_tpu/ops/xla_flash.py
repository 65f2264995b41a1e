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
  (`PSDT_BENCH_ATTENTION=xla_flash`; which one wins is not measured on
  the chip);
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
