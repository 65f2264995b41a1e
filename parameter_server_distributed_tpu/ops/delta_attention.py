"""Gated delta-rule linear attention (the delta rule of Schlag et al. and
Yang et al.'s gated DeltaNet, arXiv:2412.06464; Kimi Delta Attention,
arXiv:2510.26692), in plain XLA, with a decay PER KEY CHANNEL or PER HEAD.

Per head, with log-decays g_t <= 0 ([Dk], a rate a key channel, or a scalar,
one rate for the head) and a write strength beta_t, the recurrence over a
[Dk, Dv] float32 state (keys and values may differ in width) is

    S'  = diag(exp(g_t)) S_{t-1}               decay
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T   correct what S' reads at k_t
    o_t = S_t^T q_t

and one function runs it for a whole sequence, for a block of tokens
against a cached state (an extension) and for a decode round's single
token.  The positions go through in CHUNKS: with G_i the log-decay summed
from the chunk's start through position i, the corrections u_j = v_j -
S'_j^T k_j of a chunk solve the unit lower-triangular system

    u_i + sum_{j<i} A_ij beta_j u_j = v_i - S_0^T (exp(G_i) * k_i)

and then o_i = S_0^T (exp(G_i) * q_i) + sum_{j<=i} B_ij beta_j u_j with B
as A but q_i for k_i.  A chunk of one position IS the recurrence.

Two arms, by the shape of ``g``:

- a decay a CHANNEL (``g`` [B, T, H, Dk]; the ``kda`` mixer):
  A_ij = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d]).  The decay does not
  factor out of the score matrix, and dividing by a cumulative decay
  overflows as soon as a channel forgets fast: the exponents are formed as
  DIFFERENCES of log-decays, G_i - G_j with j <= i (never positive), before
  ``exp`` (as ``linear_attention.py``'s ``between``), a [C, C, Dk] term a
  head, which is why that arm's chunks are short;
- a decay a HEAD (``g`` [B, T, H]; the ``gdn`` mixer): the decay is one
  number a position and DOES factor out, A_ij = (k_i . k_j) exp(G_i - G_j):
  one product of the chunk's keys with themselves (the MXU's) and a [C, C]
  mask of exponent differences, differences for the same reason.  This arm
  forms no [C, C, Dk] term, and does Dk times fewer exponentials than the
  channel arm would, handed the same decay on every channel.  A decode
  round's single token runs the recurrence as written, elementwise on the
  state as it lies (three passes over it: the read at k_t, the update, the
  read at q_t with the write); through the chunk's einsums the compiler
  takes the state eleven times (396 MB against 112 a layer at 12 lanes x
  30 heads x [96, 192], compiled for a v5e).

``beta`` lies in (0, 1) for a sigmoid write strength and in (0, 2) where
the layer doubles it (``linear_allow_neg_eigval``).  Either way the state
stays bounded for keys of unit length: a position multiplies the state by
(I - beta_t k_t k_t^T) diag(exp(g_t)); the first factor has the eigenvalue
1 - beta_t along k_t, in (-1, 1) for beta_t in (0, 2), and 1 across it, and
the second is at most 1, so neither lengthens any direction: |S_t| <=
|S_{t-1}| + beta_t |v_t|, and under a decay below 1 the sum converges.
Past 2 (or with keys longer than 1) the eigenvalue along k_t leaves the unit
interval and the state can grow without bound.

Pad positions (a prompt padded to its bucket) must not enter a state that
outlives the call: ``counts`` says how many of a row's positions are real,
and a pad neither decays the state nor writes to it; a chunk that holds
pads alone is skipped (its outputs are zeros).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_rule(q: Array, k: Array, v: Array, g: Array, beta: Array,
                     state: Array | None = None,
                     counts: Array | None = None, chunk: int = 64,
                     ) -> tuple[Array, Array]:
    """q, k [B, T, H, Dk] and v [B, T, H, Dv] at T consecutive positions;
    ``g`` float32 log-decays (<= 0), [B, T, H, Dk] a key channel or
    [B, T, H] a head (the module's two arms); ``beta`` [B, T, H];
    ``state`` [B, H, Dk, Dv] float32 holds the positions before them (zeros
    where None); ``counts`` [B] how many of the T are real (all where
    None).  Returns (o [B, T, H, Dv] float32, unscaled; the state after the
    last real position)."""
    batch, t, heads, dim = q.shape
    width = v.shape[-1]
    if state is None:
        state = jnp.zeros((batch, heads, dim, width), jnp.float32)
    by_head = g.ndim == 3
    if by_head:
        # one rate for a head's every channel: a last axis of one, which the
        # products with keys and state below broadcast
        g = g[..., None]
    chunk = min(chunk, t)
    pad = -t % chunk
    if counts is None:
        counts = jnp.full((batch,), t, jnp.int32)
    real = jnp.arange(t + pad)[None, :] < counts[:, None]

    def padded(x):
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x

    q, k, v, g, beta = map(padded, (q, k, v, g, beta))
    # a pad: no decay, nothing written
    g = jnp.where(real[:, :, None, None], g, 0.0)
    beta = jnp.where(real[:, :, None], beta, 0.0)
    if by_head and t == 1:
        with jax.named_scope("state"):
            return _one_position(q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, 0],
                                 beta[:, 0], state)
    chunks = (t + pad) // chunk
    earlier = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), -1)
    upto = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))

    def by_chunk(x):
        return jnp.moveaxis(
            x.reshape(batch, chunks, chunk, *x.shape[2:]), 1, 0)

    def channel_scores(q_c, k_c, fall):
        # exp(G_i - G_j) k_j for j <= i, nothing elsewhere: [B,H,C,C,Dk]
        between = (fall.transpose(0, 2, 1, 3)[:, :, :, None, :]
                   - fall.transpose(0, 2, 1, 3)[:, :, None, :, :])
        carried = jnp.exp(jnp.where(upto[:, :, None], between, -jnp.inf)
                          ) * k_c.transpose(0, 2, 1, 3)[:, :, None, :, :]
        return (jnp.einsum("bihd,bhijd->bhij", k_c, carried,
                           precision=_HIGHEST),
                jnp.einsum("bihd,bhijd->bhij", q_c, carried,
                           precision=_HIGHEST))

    def head_scores(q_c, k_c, fall):
        # exp(G_i - G_j) for j <= i, nothing elsewhere, times the keys'
        # products: [B, H, C, C] throughout
        summed = fall[..., 0].transpose(0, 2, 1)
        carried = jnp.exp(jnp.where(
            upto, summed[:, :, :, None] - summed[:, :, None, :], -jnp.inf))
        return (carried * jnp.einsum("bihd,bjhd->bhij", k_c, k_c,
                                     precision=_HIGHEST),
                carried * jnp.einsum("bihd,bjhd->bhij", q_c, k_c,
                                     precision=_HIGHEST))

    def advance(state, args):
        q_c, k_c, v_c, g_c, b_c = args           # [B, C, H, D] ... [B, C, H]
        fall = jnp.cumsum(g_c, axis=1)      # G_i: [B, C, H, Dk (or 1)]
        with jax.named_scope("intra"):
            a, b = (head_scores if by_head else channel_scores)(
                q_c, k_c, fall)
            strength = b_c.transpose(0, 2, 1)[:, :, None, :]   # beta_j
            lower = jnp.where(earlier, a, 0.0) * strength
        with jax.named_scope("state"):
            # what the state before the chunk gives keys and queries
            decayed = jnp.exp(fall)
            before = jnp.einsum(
                "bnihd,bhde->bnihe",
                jnp.stack([k_c * decayed, q_c * decayed], axis=1), state,
                precision=_HIGHEST)
        with jax.named_scope("intra"):
            rhs = (v_c - before[:, 0]).transpose(0, 2, 1, 3)   # [B,H,C,Dv]
            if chunk == 1:
                u = rhs
            else:
                u = jax.lax.linalg.triangular_solve(
                    lower + jnp.eye(chunk, dtype=lower.dtype), rhs,
                    left_side=True, lower=True, unit_diagonal=True)
            written = u * b_c.transpose(0, 2, 1)[..., None]    # beta_j u_j
            out = before[:, 1] + jnp.einsum(
                "bhij,bhje->bihe", jnp.where(upto, b, 0.0), written,
                precision=_HIGHEST)
        with jax.named_scope("state"):
            # what each position still weighs at the chunk's end
            left = jnp.exp(fall[:, -1:] - fall) * k_c           # [B,C,H,Dk]
            state = (state * jnp.exp(fall[:, -1])[..., None]
                     + jnp.einsum("bjhd,bhje->bhde", left, written,
                                  precision=_HIGHEST))
        return state, out

    args = tuple(map(by_chunk, (q, k, v, g, beta)))
    if chunks == 1:
        state, out = advance(state, jax.tree.map(lambda x: x[0], args))
        return out[:, :t], state

    def idle(state, args):
        return state, jnp.zeros((batch, chunk, heads, width), jnp.float32)

    def step(state, args):
        # a chunk of pads alone (a turn of 60 tokens in its block of 256)
        # leaves the state as it is and is not worked through
        *inputs, any_real = args
        return jax.lax.cond(any_real, advance, idle, state, tuple(inputs))

    state, out = jax.lax.scan(
        step, state, args + (jnp.any(by_chunk(real), axis=(1, 2)),))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, chunks * chunk, heads, width)
    return out[:, :t], state



def _one_position(q: Array, k: Array, v: Array, g: Array, beta: Array,
                  state: Array) -> tuple[Array, Array]:
    """The recurrence's one step with a decay a head, nothing but products
    and sums over the key axis: q, k [B, H, Dk], v [B, H, Dv], g and beta
    [B, H] (both zero for a pad, which then leaves the state as it is),
    state [B, H, Dk, Dv].  Returns (o [B, 1, H, Dv], the state after)."""
    state = state * jnp.exp(g)[..., None, None]
    read = jnp.sum(state * k[..., None], axis=2)
    written = beta[..., None] * (v - read)
    state = state + k[..., None] * written[:, :, None, :]
    return jnp.sum(state * q[..., None], axis=2)[:, None], state
