"""The state-space dual form of a selective scan with ONE decay a head and
position (Mamba-2; Dao and Gu, arXiv:2405.21060), in plain XLA.

Per head, with a step dt_t > 0 (input-dependent), a rate a < 0 (the
head's), a value x_t [P] (the head's own) and a key B_t and a query C_t
[N] that ALL heads of a group share, the recurrence over a [P, N] float32
state is

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t

and one function runs it for a whole sequence, for a block of tokens
against a cached state (an extension) and for a decode round's single
token.  The positions go through in CHUNKS of ``chunk``: with G_i the
log-decay dt a summed from the chunk's start through position i, inside a
chunk the product is a masked, decayed [C, C] score matrix

    y_i = sum_{j<=i} (C_i . B_j) exp(G_i - G_j) dt_j x_j + exp(G_i) h_0 C_i

(C_i . B_j is formed once a GROUP: the heads differ in their decays and
values only), what lies before the chunk enters through the state, and the
state advances a chunk at a time.  Every exponent is a DIFFERENCE of
cumulative log-decays, G_i - G_j with j <= i, never positive (as
``linear_attention.py``'s ``between``): dividing by a cumulative decay
overflows as soon as a head forgets fast.  A decode round's single token
runs the recurrence as written, elementwise on the state as it lies: one
pass that decays and writes it, one reduction over its last axis; or, where
the caller says so (``models/transformer.round_arm`` holds the rule), the
same products and sums through the kernel of ``pallas/ssd_decode.py``,
which moves the matrices of the rows that have a real position and of no
other.

Its neighbours are ``linear_attention.py`` (a constant decay a head, a
square state, a key a head) and the scalar-decay arm of
``delta_attention.py`` (a triangular solve this rule does not need); three
things differ at once here (the decay by position, keys and queries shared
by a group's heads, a state [H, P, N]).

Pad positions (a prompt padded to its bucket) must not enter a state that
outlives the call: ``counts`` says how many of a row's positions are real,
and a pad neither decays the state nor writes to it (its step is zero); a
chunk that holds pads alone is skipped (its outputs are zeros).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd(x: Array, dt: Array, a: Array, b: Array, c: Array,
        state: Array | None = None, counts: Array | None = None,
        chunk: int = 256, kernel: bool = False) -> tuple[Array, Array]:
    """x [B, T, H, P] at T consecutive positions; ``dt`` [B, T, H] float32
    steps (> 0); ``a`` [H] float32 rates (< 0); b, c [B, T, G, N], G
    groups of H / G neighbouring heads; ``state`` [B, H, P, N] float32
    holds the positions before them (zeros where None); ``counts`` [B] how
    many of the T are real (all where None); ``kernel``: a single position
    (T = 1) goes through ops/pallas/ssd_decode.py, and a row whose one
    position is a pad then reads y = 0 (the plain pass reads its unchanged
    state).  Returns (y [B, T, H, P] float32, without the skip; the state
    after the last real position)."""
    batch, t, heads, dim = x.shape
    groups, width = b.shape[2:]
    # a group's key and query meet its heads: heads [G, H / G] throughout
    by_group = (groups, heads // groups)
    if state is None:
        state = jnp.zeros((batch, heads, dim, width), jnp.float32)
    state = state.reshape(batch, *by_group, dim, width)
    if counts is None:
        counts = jnp.full((batch,), t, jnp.int32)
    chunk = min(chunk, t)
    pad = -t % chunk
    real = jnp.arange(t + pad)[None, :] < counts[:, None]

    def padded(v):
        v = v.astype(jnp.float32)
        if pad:
            v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v

    x, dt, b, c = map(padded, (x, dt, b, c))
    # a pad: no decay, nothing written
    dt = jnp.where(real[:, :, None], dt, 0.0)
    fall = (dt * a.astype(jnp.float32)).reshape(batch, -1, *by_group)
    written = (x * dt[..., None]).reshape(batch, -1, *by_group, dim)

    def heads_again(out, state):
        return (out.reshape(batch, -1, heads, dim)[:, :t],
                state.reshape(batch, heads, dim, width))

    if t == 1:
        with jax.named_scope("state"):
            if kernel:
                from .pallas.ssd_decode import ssd_decode

                return heads_again(*ssd_decode(
                    written[:, 0].reshape(batch, heads, dim),
                    jnp.exp(fall[:, 0]).reshape(batch, heads), b[:, 0],
                    c[:, 0], state.reshape(batch, heads, dim, width),
                    counts > 0))
            return heads_again(*_one_position(
                written[:, 0], fall[:, 0], b[:, 0], c[:, 0], state))
    chunks = (t + pad) // chunk
    upto = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))

    def by_chunk(v):
        return jnp.moveaxis(
            v.reshape(batch, chunks, chunk, *v.shape[2:]), 1, 0)

    def advance(state, args):
        # dt x [B, C, G, R, P], dt a [B, C, G, R], keys, queries [B, C, G, N]
        w_c, g_c, b_c, c_c = args
        summed = jnp.moveaxis(jnp.cumsum(g_c, axis=1), 1, -1)  # G_i, C last
        with jax.named_scope("intra"):
            carried = jnp.exp(jnp.where(
                upto, summed[..., :, None] - summed[..., None, :],
                -jnp.inf))                                 # [B, G, R, C, C]
            scores = jnp.einsum("bign,bjgn->bgij", c_c, b_c,
                                precision=_HIGHEST)[:, :, None] * carried
            out = jnp.einsum("bgrij,bjgrp->bigrp", scores, w_c,
                             precision=_HIGHEST)
        with jax.named_scope("state"):
            # what the state before the chunk gives each query
            before = jnp.einsum("bign,bgrpn->bigrp", c_c, state,
                                precision=_HIGHEST)
            out = out + before * jnp.moveaxis(jnp.exp(summed), -1, 1)[
                ..., None]
            # what each position still weighs at the chunk's end
            left = jnp.exp(summed[..., -1:] - summed)       # [B, G, R, C]
            state = (state * jnp.exp(summed[..., -1])[..., None, None]
                     + jnp.einsum(
                         "bjgrp,bjgn->bgrpn",
                         w_c * jnp.moveaxis(left, -1, 1)[..., None], b_c,
                         precision=_HIGHEST))
        return state, out

    args = tuple(map(by_chunk, (written, fall, b, c)))
    if chunks == 1:
        return heads_again(*advance(state,
                                    jax.tree.map(lambda v: v[0], args))[::-1])

    def idle(state, args):
        return state, jnp.zeros((batch, chunk, *by_group, dim), jnp.float32)

    def step(state, args):
        # a chunk of pads alone (a prompt of 384 tokens in its bucket of
        # 1,024) leaves the state as it is and is not worked through
        *inputs, any_real = args
        return jax.lax.cond(any_real, advance, idle, state, tuple(inputs))

    state, out = jax.lax.scan(
        step, state, args + (jnp.any(by_chunk(real), axis=(1, 2)),))
    return heads_again(jnp.moveaxis(out, 0, 1), state)


def _one_position(written: Array, fall: Array, b: Array, c: Array,
                  state: Array) -> tuple[Array, Array]:
    """The recurrence's one step, nothing but products and one sum over the
    state's last axis: ``written`` dt x [B, G, R, P] and ``fall`` dt a [B,
    G, R] (both zero for a pad, which then leaves the state as it is), b
    and c [B, G, N], state [B, G, R, P, N].  Returns (y [B, G, R, P], the
    state after)."""
    state = (state * jnp.exp(fall)[..., None, None]
             + written[..., None] * b[:, :, None, None, :])
    return jnp.sum(state * c[:, :, None, None, :], axis=-1), state
