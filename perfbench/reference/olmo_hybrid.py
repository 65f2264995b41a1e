"""Olmo Hybrid (Allen AI; ``Olmo-Hybrid-7B``'s ``config.json``, ``model_type``
``olmo_hybrid``) forward pass in plain float32 ``jax.numpy``: no kernels, no
cache, no chunks, no sharing of code with ``models/transformer.py``, ``ops/``
or another reference.  Written from the two public descriptions of its
parts: the gated delta-rule layer under the very keys this config uses
(``linear_num_key_heads``, ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``: transformers' ``Qwen3NextGatedDeltaNet``,
``torch_recurrent_gated_delta_rule`` and ``Qwen3NextRMSNormGated``; Yang,
Kautz, Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464) and the Olmo
block (transformers' ``Olmo3Attention``, ``Olmo3DecoderLayer``).  With x the
residual stream [S, d] (RMS norms with a learned gain, eps 1e-6, no bias
anywhere):

  linear layer (30 heads, keys of 96, values of 192), position t:
    [q | k | v] = silu(conv4([x W_q | x W_k | x W_v]))   one depthwise causal
                                     convolution, 4 taps, zeros before the
                                     first position, no bias
    q = l2norm(q) / sqrt(96)         k = l2norm(k)       1e-6 under the root
    b_t = 2 sigmoid(x_t W_b)         a head
    g_t = -exp(A_log[h]) softplus(x_t W_a + dt_bias[h])  a head, float32
    S'  = exp(g_t) S_{t-1}
    S_t = S' + k_t (b_t (v_t - S'^T k_t))^T              [96, 192] a head
    o_t = S_t^T q_t
    y_t = W_o concat_h(RMSNorm_192(o_t; gain[192]) * silu(x_t W_z))
  a ``lax.scan`` over the positions of exactly this.

  full layer (30 heads of 128 over 30 K/V heads), no rotary:
    q = RMSNorm_3840(x W_q)   k = RMSNorm_3840(x W_k)    over ALL heads'
                                     channels, a gain [3840] each
    v = x W_v
    o = softmax(q k^T / sqrt(128) + causal mask) v, heads concatenated, @ W_o

  block, both kinds (a norm on each branch's OUTPUT):
    h = x + RMSNorm(mixer(x))
    y = h + RMSNorm(W_2 (silu(W_1 h) * (W_3 h)))         3,840 -> 11,008

and logits = RMSNorm(x_L) @ W_head, a head of its own.

Departures from the two public files, each also under ``assumed`` in the
configuration's file:
- no rotary on the full layers: the config's ``rope_parameters.rope_theta``
  is null, so there is no base to rotate by (Olmo3Attention applies one);
- the Olmo block (norms on the branches' outputs) stands around BOTH kinds
  of layer; the Qwen3-Next file norms its layers' inputs;
- q/k norms over all heads' channels at once, as Olmo3Attention's (the
  Qwen3-Next attention norms a head at a time);
- ``beta`` doubled (``linear_allow_neg_eigval``: the flash-linear-attention
  layer this key belongs to multiplies the sigmoid by 2, so that I - b k k^T
  has an eigenvalue in (-1, 1); the Qwen3-Next file has no such key);
- the q, k and v projections, the gate ``W_z``, the decay ``W_a`` and the
  strength ``W_b`` are matrices of their own (the Qwen3-Next file fuses them
  into ``in_proj_qkvz`` / ``in_proj_ba``; the products are the same);
- ``A_log`` and ``dt_bias`` a head ([30]), the output norm's gain a value
  head's [192], as the Qwen3-Next module's shapes.

Weights may arrive in a narrower dtype (bfloat16 values are exact in
float32); one layer's matrices are widened at a time, attention runs a block
of query rows at a time and the head a block of rows at a time.  The
arithmetic is float32 at the highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_mixer norm_ffn [d]; w1 w3 [d, F]; w2 [F, d]} and
    linear:  wq wk [d, H*Dk]; wv wz [d, H*Dv]; conv_q conv_k [4, H*Dk];
             conv_v [4, H*Dv]; wa wb [d, H]; a_log dt_bias [H]; o_gain [Dv];
             wo [H*Dv, d]
    full:    wq wk wv [d, H*D]; q_gain k_gain [H*D]; wo [H*D, d]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 256   # query rows scored, and rows of the head multiplied, at a time
L2_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _conv(x, kernel):
    """Depthwise causal convolution of x [S, C] with ``kernel`` [K, C]: the
    last tap meets the current position, zeros before the first."""
    taps = kernel.shape[0]
    seq = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(_f32(kernel)[j] * padded[j:j + seq] for j in range(taps))


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _bits(x, bits):
    """x at ``bits`` mantissa bits (7: bfloat16's), in float32's range (a
    cast there and back the compiler may drop: this it keeps)."""
    return x if bits is None else jax.lax.reduce_precision(x, 8, bits)


def linear_layer(x, w, *, n_head, key_dim, value_dim, eps, beta_scale=2.0,
                 decay_sign=-1.0, state_bits=None, product_bits=None,
                 states=None):
    """The gated delta-rule mixer of x [S, d]: [S, d], before the block's
    norm and residual.  ``beta_scale``, ``decay_sign``, ``state_bits`` and
    ``product_bits`` exist for the controls of ``families/olmo_hybrid.py``
    (the strength left undoubled; the decay's sign dropped; the state kept
    at that many mantissa bits after every position, 7 for bfloat16's; every
    operand of the rule's three products rounded to that many): the layer is
    the defaults.  ``states``, a list, gains S after the last position
    [H, Dk, Dv]."""
    seq = x.shape[0]
    q = jax.nn.silu(_conv(x @ _f32(w["wq"]), w["conv_q"]))
    k = jax.nn.silu(_conv(x @ _f32(w["wk"]), w["conv_k"]))
    v = jax.nn.silu(_conv(x @ _f32(w["wv"]), w["conv_v"]))
    q = _l2norm(q.reshape(seq, n_head, key_dim)) / math.sqrt(key_dim)
    k = _l2norm(k.reshape(seq, n_head, key_dim))
    v = v.reshape(seq, n_head, value_dim)
    beta = beta_scale * jax.nn.sigmoid(x @ _f32(w["wb"]))        # [S, H]
    fall = decay_sign * jnp.exp(_f32(w["a_log"])) * jax.nn.softplus(
        x @ _f32(w["wa"]) + _f32(w["dt_bias"]))                  # [S, H]

    def rounded(value):
        return _bits(value, product_bits)

    def position(state, args):
        q_t, k_t, v_t, g_t, b_t = args        # [H, Dk] x 2, [H, Dv], [H] x 2
        state = jnp.exp(g_t)[:, None, None] * state
        read = jnp.einsum("hde,hd->he", rounded(state), rounded(k_t))
        write = b_t[:, None] * (v_t - read)
        state = state + rounded(k_t)[:, :, None] * rounded(write)[:, None, :]
        state = _bits(state, state_bits)
        return state, jnp.einsum("hde,hd->he", rounded(state), rounded(q_t))

    last, out = jax.lax.scan(
        position, jnp.zeros((n_head, key_dim, value_dim), jnp.float32),
        (q, k, v, fall, beta))
    if states is not None:
        states.append(last)
    out = _rms_norm(out, w["o_gain"], eps)                      # [S, H, Dv]
    gate = jax.nn.silu(x @ _f32(w["wz"]))
    return (out.reshape(seq, n_head * value_dim) * gate) @ _f32(w["wo"])


def full_layer(x, w, *, n_head, head_dim, eps, scale_dim=None):
    """Causal softmax attention of x [S, d] without rotary, q and k normed
    over all heads at once: [S, d], before the block's norm and residual.
    ``scale_dim`` exists for a control (another width under the scale's
    root)."""
    seq = x.shape[0]
    q = _rms_norm(x @ _f32(w["wq"]), w["q_gain"], eps)
    k = _rms_norm(x @ _f32(w["wk"]), w["k_gain"], eps)
    q = q.reshape(seq, n_head, head_dim)
    k = k.reshape(seq, n_head, head_dim)
    v = (x @ _f32(w["wv"])).reshape(seq, n_head, head_dim)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)
    scale = math.sqrt(scale_dim or head_dim)

    def block(args):
        start, q_rows = args                                  # [rows, H, D]
        scores = jnp.einsum("qhd,shd->hqs", q_rows, k) / scale
        seen = (start + jnp.arange(rows))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(blocks) * rows,
                              q.reshape(blocks, rows, n_head, head_dim)))
    return out.reshape(blocks * rows, n_head * head_dim)[:seq] @ _f32(w["wo"])


def _layer(x, w, *, n_head, head_dim, key_dim, value_dim, eps, faults,
           states):
    if "wa" in w:
        mixed = linear_layer(x, w, n_head=n_head, key_dim=key_dim,
                             value_dim=value_dim, eps=eps, states=states,
                             **faults.get("linear", {}))
    else:
        mixed = full_layer(x, w, n_head=n_head, head_dim=head_dim, eps=eps,
                           **faults.get("full", {}))
    h = x + _rms_norm(mixed, w["norm_mixer"], eps)
    ffn = (jax.nn.silu(h @ _f32(w["w1"])) * (h @ _f32(w["w3"]))) \
        @ _f32(w["w2"])
    return h + _rms_norm(ffn, w["norm_ffn"], eps)


def forward(weights: dict, tokens, *, n_head: int, head_dim: int,
            key_dim: int, value_dim: int, eps: float, faults=None,
            states=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another, layer by layer; the
    head a block of rows at a time.  ``states``, a list, gains every linear
    layer's state after the last position, [B, H, Dk, Dv] a layer in layer
    order.  ``faults`` (the controls of ``families/olmo_hybrid.py``):
    keyword arguments for :func:`linear_layer` (under ``linear``) and
    :func:`full_layer` (under ``full``) that make them something else."""
    with jax.default_matmul_precision("highest"):
        def one(b):
            x = _f32(weights["embed"][tokens[b]])
            last = None if states is None else []
            for w in weights["layers"]:
                x = _layer(x, w, n_head=n_head, head_dim=head_dim,
                           key_dim=key_dim, value_dim=value_dim, eps=eps,
                           faults=faults or {}, states=last)
            if states is not None:
                per_sequence.append(last)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)])

        per_sequence: list = []
        logits = jnp.stack([one(b) for b in range(tokens.shape[0])])
        if states is not None:
            states.extend(jnp.stack(layer) for layer in zip(*per_sequence))
        return logits


def loss(weights: dict, tokens, **model):
    """(mean next-token cross-entropy, logits): position p predicts token
    p + 1, the last position has no target.  Differentiable."""
    logits = forward(weights, tokens, **model)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked), logits
