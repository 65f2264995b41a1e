"""Gated short convolution (the ``conv`` mixer of Liquid AI's LFM2 family),
in plain XLA.

Per channel, with a kernel of K taps w[0] .. w[K-1] (the last meets the
current position) and three projections B, C, x of the layer's input:

    z_t = B_t * x_t
    c_t = w[0] * z_{t-K+1} + ... + w[K-1] * z_t       (depthwise, causal)
    y_t = C_t * c_t

A position reads K - 1 gated inputs before its own and nothing earlier, so
what a decode cache keeps of the layer is a shift register of K - 1
columns, whatever the context's length; positions before the sequence's
first read zeros.  One function runs a whole sequence, a block of tokens
against a cached state (an extension) and a decode round's single token.

Pad positions (a prompt padded to its bucket) must not enter a state that
outlives the call: ``counts`` says how many of a row's positions are real,
and the state returned is the one after the last real position.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def short_conv(x: Array, kernel: Array, state: Array | None = None,
               counts: Array | None = None) -> tuple[Array, Array]:
    """The depthwise causal convolution alone: x [B, T, d] at T consecutive
    positions, ``kernel`` [K, d], ``state`` [B, K - 1, d] the inputs of the
    K - 1 positions before them, oldest first (zeros where None),
    ``counts`` [B] how many of the T are real (all where None).  Returns
    (the taps' sum [B, T, d] in float32, the state after the last real
    position, in x's dtype)."""
    taps = kernel.shape[0]
    batch, t, width = x.shape
    if state is None:
        state = jnp.zeros((batch, taps - 1, width), x.dtype)
    held = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    weights = kernel.astype(jnp.float32)
    conv = sum(weights[k] * held[:, k:k + t].astype(jnp.float32)
               for k in range(taps))
    if counts is None:
        return conv, held[:, t:]
    if t == 1:
        # one position, real or a pad (a decode round's lane that holds no
        # request): a select; the gather below costs a round 55 us a layer
        # on the chip (PERF.md section 6, PR 58)
        return conv, jnp.where((counts > 0)[:, None, None], held[:, 1:],
                               held[:, :-1])
    # the real positions end at index counts + K - 2 of ``held``
    at = counts[:, None] + jnp.arange(taps - 1)[None, :]
    return conv, jnp.take_along_axis(held, at[:, :, None], axis=1)


def gated_short_conv(b: Array, c: Array, x: Array, kernel: Array,
                     state: Array | None = None,
                     counts: Array | None = None) -> tuple[Array, Array]:
    """b, c, x [B, T, d] at T consecutive positions; ``kernel`` [K, d];
    ``state`` [B, K - 1, d] the gated inputs of the K - 1 positions before
    them, oldest first (zeros where None); ``counts`` [B] how many of the
    T are real (all where None).  The products and the taps' sum run in
    float32; the gated inputs are kept in the inputs' dtype, in the state
    and in the sum alike, so that a position's result does not depend on
    whether its neighbours came from the state or from the block.
    Returns (y [B, T, d] in the inputs' dtype, the state after the last
    real position)."""
    conv, after = short_conv(b * x, kernel, state, counts)
    return (c.astype(jnp.float32) * conv).astype(x.dtype), after
