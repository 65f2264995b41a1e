"""LFM2-MoE (Liquid AI; ``LFM2-24B-A2B``'s ``config.json``, ``model_type``
``lfm2_moe``) forward pass in plain float32 ``jax.numpy``: no kernels, no
cache, no grouped matmul, no sharing of code with ``models/transformer.py``.
A layer, for the residual stream x (RMS norms with a learned gain, eps 1e-5,
no bias anywhere):

    u   = RMSNorm_op(x)
    conv layer:   B, C, z = split3(u @ W_in)              2048 -> 3 x 2048
                  g_t = B_t * z_t                          elementwise
                  c_t = w[:,0] g_{t-2} + w[:,1] g_{t-1} + w[:,2] g_t
                                                           depthwise, causal,
                                                           zeros before t = 0
                  x1  = x + (C * c) @ W_out
    attn layer:   q, k, v = u @ Wq, u @ Wk, u @ Wv         32 heads over 8 K/V
                                                           heads of 64
                  q, k = RMSNorm(q), RMSNorm(k)            a gain[64] each, on
                                                           every head
                  q, k rotated (half-rotation, theta 1e6)
                  x1  = x + softmax(q k^T / 8 + causal mask) v @ Wo
    h   = RMSNorm_ffn(x1)
    dense layer (the first ``num_dense_layers``):
                  x2  = x1 + (silu(h @ W1) * (h @ W3)) @ W2      width 11,776
    expert layer: s = sigmoid(h @ W_router)                float32
                  S = top-4 of (s + expert_bias)           bias in the
                                                           SELECTION only
                  g = s[S] / (sum s[S] + 1e-6) * routed_scaling_factor
                  x2  = x1 + sum_{e in S} g_e (silu(h W1_e) * (h W3_e)) W2_e

and logits = RMSNorm(x_L) @ W_head, the head the embedding's transpose
(tied; the caller hands it over as a matrix of its own).

Departures from the published description, each also under ``assumed`` in
the configuration's file: the published decode cache keeps ``conv_L_cache``
= 3 columns of a conv layer's input, of which the kernel reads two beside
the current one (here nothing is cached at all: the sum above reads the
whole sequence); the kernel is stored [d, 3] here as the published
``conv.weight`` [d, 1, 3] is, tap 2 meeting the current position.

Every expert is computed for every token and weighted by its gate, which is
zero for the tokens that did not choose it: no routing machinery to share a
fault with the program.  The selection can be GIVEN (``forward``'s
``selection``): a score's 4th and 5th best lie 0.013 apart at the median, so
the program's bfloat16 stream resolves some of them the other way, and a
token then runs through another expert at a quarter of the branch's weight;
the comparison that decides ``correct`` gives the reference the program's
selection and holds every difference to a margin of this module's own cut
(``families/lfm2.py``).  Weights may arrive in a narrower dtype (bfloat16
values are exact in float32); one layer, and within it one expert, is
widened at a time, and attention runs a block of query rows at a time, so
that the published widths fit beside the weights.  The arithmetic is
float32 at the highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_op norm_ffn [d]} and
    conv:    w_in [d, 3d]; taps [d, K]; w_out [d, d]
    attn:    wq [d, H*D]; wk wv [d, KV*D]; wo [H*D, d]; q_gain k_gain [D]
    dense:   w1 w3 [d, F]; w2 [F, d]
    experts: router [d, E]; bias [E]; w1 w3 [E, d, Fe]; w2 [E, Fe, d]
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


def short_conv(u, w):
    """The gated short convolution of u [S, d]: [S, d], before the
    residual."""
    seq = u.shape[0]
    gate_in, gate_out, z = jnp.split(u @ _f32(w["w_in"]), 3, axis=-1)
    taps = _f32(w["taps"])                                    # [d, K]
    width = taps.shape[1]
    gated = jnp.pad(gate_in * z, ((width - 1, 0), (0, 0)))
    conv = sum(taps[:, k] * gated[k:k + seq] for k in range(width))
    return (gate_out * conv) @ _f32(w["w_out"])


def _attention(u, w, *, n_head, n_kv_head, head_dim, eps, theta):
    """Causal grouped-query attention of u [S, d]: [S, d], before the
    residual.  Query head h reads K/V head h // (H / KV); the mask is
    written out, a block of rows at a time."""
    seq = u.shape[0]
    q = (u @ _f32(w["wq"])).reshape(seq, n_head, head_dim)
    k = (u @ _f32(w["wk"])).reshape(seq, n_kv_head, head_dim)
    v = (u @ _f32(w["wv"])).reshape(seq, n_kv_head, head_dim)
    q = _rotate(_rms_norm(q, w["q_gain"], eps), theta)
    k = _rotate(_rms_norm(k, w["k_gain"], eps), theta)
    group = n_head // n_kv_head
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def block(args):
        start, q_rows = args                                  # [rows, H, D]
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k) / math.sqrt(head_dim)
        seen = keys[None, :] <= (start + jnp.arange(rows))[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(blocks) * rows,
                              q.reshape(blocks, rows, n_head, head_dim)))
    mixed = out.reshape(blocks * rows, n_head, head_dim)[:seq]
    return mixed.reshape(seq, n_head * head_dim) @ _f32(w["wo"])


def gates(h, w, top_k, scale, given=None):
    """([S, E]: a token's gate for each expert, zero where it chose
    another; [2]: how a ``given`` selection compares with this one).
    Scores sigmoid(logits); the top_k of score + bias are chosen; a gate
    is the chosen expert's own score over the chosen scores' sum.

    ``given`` [S, k] is a selection made elsewhere (the program's, in its
    own precision): the gates are then of THOSE experts, and the second
    result counts the tokens whose given experts are not the top_k here,
    and how far the worst given expert lies under this selection's cut
    (the top_k-th best score + bias), in units of the score."""
    scores = jax.nn.sigmoid(h @ _f32(w["router"]))
    biased = scores + _f32(w["bias"])
    best, index = jax.lax.top_k(biased, top_k)                 # [S, k]
    compared = jnp.zeros((2,), jnp.float32)
    if given is not None:
        theirs = jnp.take_along_axis(biased, given, axis=-1)
        short = jnp.maximum(best[:, -1:] - theirs, 0.0).max(axis=-1)
        compared = jnp.stack([jnp.sum(short > 0).astype(jnp.float32),
                              jnp.max(short)])
        index = given
    chosen = jnp.sum(jax.nn.one_hot(index, scores.shape[-1]), axis=1)
    picked = scores * chosen
    return (picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
            * scale, compared)


def _experts(h, w, top_k, scale, given=None):
    """One expert at a time over every token: (sum, the comparison of
    :func:`gates`)."""
    weight, compared = gates(h, w, top_k, scale, given)

    def one(total, args):
        w1, w3, w2, gate = args
        hidden = jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))
        return total + gate[:, None] * (hidden @ _f32(w2)), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(h),
                            (w["w1"], w["w3"], w["w2"], weight.T))
    return total, compared


def _layer(x, w, given, *, eps, top_k, scale, **heads):
    """(the layer's output, an expert layer's comparison with ``given``)."""
    u = _rms_norm(x, w["norm_op"], eps)
    if "taps" in w:
        x = x + short_conv(u, w)
    else:
        x = x + _attention(u, w, eps=eps, **heads)
    h = _rms_norm(x, w["norm_ffn"], eps)
    if "router" in w:
        out, compared = _experts(h, w, top_k, scale, given)
        return x + out, compared
    return x + (jax.nn.silu(h @ _f32(w["w1"])) * (h @ _f32(w["w3"]))) \
        @ _f32(w["w2"]), None


def forward(weights: dict, tokens, *, n_head: int, n_kv_head: int,
            head_dim: int, eps: float, theta: float, top_k: int,
            scale: float, selection=None, report=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another; the head a block of
    rows at a time.

    ``selection``, one [B, S, k] array of experts an expert layer, makes
    the experts those (the gates are still this module's, from its own
    scores); ``report`` is then called with [expert layers, B, 2]: the
    tokens whose given experts are not this module's own, and how far
    under this module's cut the worst of them lies."""
    with jax.default_matmul_precision("highest"):
        def one(b):
            x = _f32(weights["embed"][tokens[b]])
            given = iter(selection or ())
            seen = []
            for w in weights["layers"]:
                x, compared = _layer(
                    x, w, next(given)[b] if selection and "router" in w
                    else None, eps=eps, top_k=top_k, scale=scale,
                    n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
                    theta=theta)
                if compared is not None:
                    seen.append(compared)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)]), jnp.stack(seen)

        logits, compared = zip(*(one(b) for b in range(tokens.shape[0])))
        if selection and report is not None:
            report(jnp.stack(compared, axis=1))
        return jnp.stack(logits)


def loss(weights: dict, tokens, **model):
    """(mean next-token cross-entropy, logits): position p predicts token
    p + 1, the last position has no target.  Differentiable."""
    logits = forward(weights, tokens, **model)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked), logits
