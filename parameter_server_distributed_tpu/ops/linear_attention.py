"""Linear attention with a per-head decayed state (Lightning Attention,
Qin et al., arXiv:2401.04658), in plain XLA.

Per head with decay ``lam = exp(-slope)`` the recurrence is

    S_t = lam * S_{t-1} + k_t v_t^T        (a [D, D] state, float32)
    o_t = q_t^T S_t

and one function runs it for a whole sequence, for a block of tokens
against a cached state (an extension) and for a decode round's single
token: the positions go through in CHUNKS of ``chunk``; inside a chunk the
product is a masked, decayed [C, C] score matrix, what lies before the
chunk enters through the state, and the state advances a chunk at a time.
A chunk of one position IS the recurrence.

Pad positions (a prompt padded to its bucket) must not enter a state that
outlives the call: ``counts`` says how many of a row's positions are real,
and a pad neither decays the state nor adds to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(n_heads: int) -> Array:
    """The per-head decay rates s_h = 2^(-8 (h + 1) / H) (Lightning
    Attention's slopes; ALiBi's series): head h forgets by exp(-s_h) a
    position."""
    return 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32)
                   / n_heads)


def linear_attention(q: Array, k: Array, v: Array, state: Array | None = None,
                     counts: Array | None = None, chunk: int = 128,
                     ) -> tuple[Array, Array]:
    """q, k, v [B, T, H, D] at T consecutive positions; ``state``
    [B, H, D, D] float32 (key dimension, then value dimension) holds the
    positions before them (zeros where None); ``counts`` [B] how many of
    the T are real (all where None).  Returns (o [B, T, H, D] float32,
    unscaled; the state after the last real position)."""
    batch, t, heads, dim = q.shape
    if state is None:
        state = jnp.zeros((batch, heads, dim, dim), jnp.float32)
    if counts is None:
        counts = jnp.full((batch,), t, jnp.int32)
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    chunks = (t + pad) // chunk
    slopes = decay_slopes(heads)
    inside = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))

    def by_chunk(x):
        return jnp.moveaxis(x.reshape(batch, chunks, chunk, heads, dim), 1, 0)

    def advance(state, args):
        first, q_c, k_c, v_c = args                       # [B, C, H, D]
        real = (first + jnp.arange(chunk))[None, :] < counts[:, None]
        k_c = jnp.where(real[:, :, None, None], k_c, 0)
        # log-decay up to and including each position: [B, H, C]
        fall = -slopes[None, :, None] * jnp.cumsum(
            real.astype(jnp.float32), axis=1)[:, None, :]
        with jax.named_scope("intra"):
            between = fall[:, :, :, None] - fall[:, :, None, :]
            weight = jnp.exp(jnp.where(inside, between, -jnp.inf))
            scores = jnp.einsum("bihd,bjhd->bhij", q_c, k_c,
                                preferred_element_type=jnp.float32) * weight
            out = jnp.einsum("bhij,bjhd->bihd", scores.astype(v_c.dtype), v_c,
                             preferred_element_type=jnp.float32)
        with jax.named_scope("state"):
            before = jnp.einsum("bihd,bhde->bihe", q_c.astype(jnp.float32),
                                state, precision=_HIGHEST)
            out = out + before * jnp.exp(fall).transpose(0, 2, 1)[..., None]
            # what each position still weighs at the chunk's end
            left = jnp.exp(fall[:, :, -1:] - fall)        # [B, H, C]
            state = (state * jnp.exp(fall[:, :, -1])[..., None, None]
                     + jnp.einsum(
                         "bjhd,bjhe->bhde",
                         k_c.astype(jnp.float32)
                         * left.transpose(0, 2, 1)[..., None],
                         v_c.astype(jnp.float32), precision=_HIGHEST))
        return state, out

    args = (jnp.arange(chunks, dtype=jnp.int32) * chunk,
            by_chunk(q), by_chunk(k), by_chunk(v))
    if chunks == 1:
        state, out = advance(state, jax.tree.map(lambda x: x[0], args))
        return out[:, :t], state
    state, out = jax.lax.scan(advance, state, args)
    out = jnp.moveaxis(out, 0, 1).reshape(batch, chunks * chunk, heads, dim)
    return out[:, :t], state
