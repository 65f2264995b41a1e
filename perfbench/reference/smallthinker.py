"""SmallThinker (Song et al. 2025, arXiv:2507.20984; PowerInfer's
``SmallThinker-21BA3B-Instruct`` config) forward pass in plain float32
``jax.numpy``: no kernels, no cache, no batching tricks, no sharing of code
with ``models/transformer.py``.  A layer, for the residual stream x:

    h      = RMSNorm(x)                              learned gain, no bias
    r      = h @ W_router                            the router BEFORE attention
    q,k,v  = h @ Wq, h @ Wk, h @ Wv                  28 heads over 4 K/V heads of 128
    rope_layout 1: q,k rotated (half-rotation);      0: no position signal
    a      = softmax(q k^T / sqrt(128) + mask) v     mask causal, and under
                                                     sliding_window_layout 1
                                                     also i - j < window
    x1     = x + a @ Wo
    h2     = RMSNorm(x1)
    S      = top-k of r;  w = softmax(r[S])          over the SELECTED logits
    x2     = x1 + sum_{e in S} w_e (relu(h2 Wgate_e) * (h2 Wup_e)) Wdown_e

and logits = RMSNorm(x_L) @ W_head, the head not tied to the embedding.
Departures from the published description: none known; what the config does
not say (the router's input, the softmax over the selected logits, the
window's edge, the rotary convention, no QK-norm, no biases) is listed under
``assumed`` in the configuration file.

Every expert is computed for every token and weighted by its gate, which is
zero for the tokens that did not choose it: no routing machinery to share a
fault with the program.  Weights may arrive in a narrower dtype (bfloat16
values are exact in float32); one layer, and within it one expert, is
widened at a time, and attention runs a block of query rows at a time, so
that the published widths fit beside the weights.  The arithmetic is
float32 at the highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm1 norm2 [d]; wq [d, H*D]; wk wv [d, KV*D];
    wo [H*D, d]; router [d, E]; w_gate w_up [E, d, F]; w_down [E, F, d]}
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 512   # query rows scored at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _rotate(x, theta):
    """Half-rotation rotary embedding of x [S, heads, D] at positions
    0..S-1: pair (x[i], x[i + D/2]) turns by position * theta^(-2i/D)."""
    seq, _, dim = x.shape
    inverse = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def _attention(q, k, v, window):
    """q [S, H, D], k/v [S, KV, D] -> [S, H, D]; query head h reads K/V head
    h // (H / KV).  The mask is written out, a block of rows at a time."""
    seq, heads, dim = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def block(args):
        start, q_rows = args                                  # [rows, H, D]
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / math.sqrt(dim)
        at = start + jnp.arange(rows)
        seen = keys[None, :] <= at[:, None]
        if window:
            seen &= at[:, None] - keys[None, :] < window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(blocks) * rows,
                              q.reshape(blocks, rows, heads, dim)))
    return out.reshape(blocks * rows, heads, dim)[:seq]


def _experts(h, router_logits, w, top_k):
    """sum over the top_k experts of gate * ReGLU expert, gates the softmax
    over the selected logits.  One expert at a time over every token."""
    experts = router_logits.shape[-1]
    chosen, index = jax.lax.top_k(router_logits, top_k)       # [S, k]
    gates = jax.nn.softmax(chosen, axis=-1)
    # [S, E]: a token's gate for each expert, zero where it chose another
    weight = jnp.sum(jax.nn.one_hot(index, experts) * gates[..., None],
                     axis=1)

    def one(total, args):
        w_gate, w_up, w_down, gate = args
        hidden = jax.nn.relu(h @ _f32(w_gate)) * (h @ _f32(w_up))
        return total + gate[:, None] * (hidden @ _f32(w_down)), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(h),
                            (w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return total


def _layer(x, w, *, n_head, n_kv_head, head_dim, eps, theta, rotary, window,
           top_k):
    seq = x.shape[0]
    h = _rms_norm(x, w["norm1"], eps)
    router_logits = h @ _f32(w["router"])
    q = (h @ _f32(w["wq"])).reshape(seq, n_head, head_dim)
    k = (h @ _f32(w["wk"])).reshape(seq, n_kv_head, head_dim)
    v = (h @ _f32(w["wv"])).reshape(seq, n_kv_head, head_dim)
    if rotary:
        q, k = _rotate(q, theta), _rotate(k, theta)
    mixed = _attention(q, k, v, window).reshape(seq, n_head * head_dim)
    x = x + mixed @ _f32(w["wo"])
    h2 = _rms_norm(x, w["norm2"], eps)
    return x + _experts(h2, router_logits, w, top_k)


def _forward(weights: dict, tokens, place, *, n_head: int, n_kv_head: int,
             head_dim: int, eps: float, theta: float, rope_layout,
             window_layout, window: int, top_k: int):
    """Logits [B, S, V]; ``place`` is applied to every block of rows of them
    as it is computed."""
    with jax.default_matmul_precision("highest"):
        def one(sequence):
            x = _f32(weights["embed"][sequence])
            for w, rotary, windowed in zip(weights["layers"], rope_layout,
                                           window_layout):
                x = _layer(x, w, n_head=n_head, n_kv_head=n_kv_head,
                           head_dim=head_dim, eps=eps, theta=theta,
                           rotary=bool(rotary),
                           window=window if windowed else 0, top_k=top_k)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            seq = x.shape[0]
            x = jnp.pad(x, ((0, -seq % 8), (0, 0)))
            return jnp.concatenate([place(x[start:start + ROWS] @ head)
                                    for start in range(0, x.shape[0], ROWS)]
                                   )[:seq]

        return jnp.stack([one(tokens[b]) for b in range(tokens.shape[0])])


def forward(weights: dict, tokens, **model):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another.  The logits of the
    cell's replayed request (12,368 x 151,936 float32: 7.5 GB) do
    not fit a 16 GB chip beside 7.9 GB of weights, so each block of rows
    goes to the HOST's memory as it is computed and the result is put
    together there: where the numbers lie, nothing of how they are
    computed."""
    return _forward(
        weights, tokens,
        lambda block: jax.device_put(block, jax.memory.Space.Host), **model)


def loss(weights: dict, tokens, **model):
    """(mean next-token cross-entropy, logits): position p predicts token
    p + 1, the last position has no target.  Differentiable; the logits
    stay on the device, where the softmax over them runs."""
    logits = _forward(weights, tokens, lambda block: block, **model)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked), logits
