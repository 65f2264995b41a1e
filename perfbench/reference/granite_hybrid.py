"""Granite 4.0-H (IBM; ``granite-4.0-h-micro``'s ``config.json``,
``model_type`` ``granitemoehybrid``) forward pass in plain float32
``jax.numpy``: no kernels, no cache, no chunks, no sharing of code with
``models/transformer.py``, ``ops/`` or another reference.  Written from the
two public descriptions of its parts: the Mamba-2 layer under the very keys
this config uses (``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_conv_bias``,
``mamba_proj_bias``: transformers' ``GraniteMoeHybridMambaLayer`` and
``GraniteMoeHybridRMSNormGated``; Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060) and the block, the four scalars and the attention layer
(``modeling_granitemoehybrid.py``: ``GraniteMoeHybridDecoderLayer``,
``GraniteMoeHybridAttention``, ``GraniteMoeHybridMLP``).  With x the residual
stream [S, d] (RMS norms with a learned gain, eps ``rms_norm_eps``, no bias
on any projection):

  ssm layer (H heads of P, a state of N, G groups, K taps), position t:
    [z | xBC | dt] = u W_in          u the block's normed input; widths
                                     H P, H P + 2 G N, H
    xBC_t = silu(sum_k w[k] xBC_{t-K+1+k} + b)   one depthwise causal
                                     convolution WITH a bias, zeros before
                                     the first position
    x_t [H, P] | B_t [G, N] | C_t [G, N] = split(xBC_t)
    delta_t = softplus(dt_t + dt_bias)   A = -exp(A_log)   a head, float32
    h_t = exp(delta_t A) h_{t-1} + delta_t x_t (x) B_t     [P, N] a head; a
                                     group's B and C serve all its heads
    y_t = h_t C_t + D x_t            D a head
    out_t = W_out (RMSNorm_group(y_t * silu(z_t)) * g)     the gate BEFORE
                                     the norm; the norm over a group's H P / G
                                     channels, the gain g over all H P
  a ``lax.scan`` over the positions of exactly this.

  attention layer (heads of D over K/V heads of D), no rotary, no bias:
    o = softmax(q k^T * attention_multiplier + causal mask) v, @ W_o

  block, both kinds (a norm on each branch's INPUT):
    h = x + residual_multiplier * mixer(RMSNorm(x))
    y = h + residual_multiplier * W_2 (silu(W_1 RMSNorm(h)) * (W_3 RMSNorm(h)))

  x_0 = embedding_multiplier * E[token];
  logits = RMSNorm(x_L) @ W_head / logits_scaling     (W_head = E^T: tied)

Departures from the public file, each also under ``assumed`` in the
configuration's file:
- the state is float32 (the public layer keeps it in the model's dtype);
- no chunks: ``mamba_chunk_size`` is a kernel's choice, not mathematics;
- ``W_1`` and ``W_3`` are matrices of their own (the public ``input_linear``
  holds them side by side; the products are the same), and the head is
  handed over as a matrix of its own that holds E^T;
- no ``time_step_limit`` clamp on delta (the public default is (0, inf));
- ``num_local_experts`` is 0: the shared MLP is the only feed-forward part.

Weights may arrive in a narrower dtype (bfloat16 values are exact in
float32); one layer's matrices are widened at a time, attention runs a block
of query rows at a time and the head a block of rows at a time.  The
arithmetic is float32 at the highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_mixer norm_ffn [d]; w1 w3 [d, F]; w2 [F, d]} and
    ssm:        w_in [d, 2 H P + 2 G N + H]; conv_w [K, H P + 2 G N];
                conv_b [H P + 2 G N]; dt_bias a_log d_skip [H];
                gate_gain [H P]; w_out [H P, d]
    attention:  wq [d, heads * D]; wk wv [d, kv_heads * D]; wo [heads * D, d]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 256   # query rows scored, and rows of the head multiplied, at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _bits(x, bits):
    """x at ``bits`` mantissa bits (7: bfloat16's), in float32's range (a
    cast there and back the compiler may drop: this it keeps)."""
    return x if bits is None else jax.lax.reduce_precision(x, 8, bits)


def ssm_layer(u, w, *, ssm_heads, ssm_head_dim, ssm_state, ssm_groups, eps,
              skip=True, gate_first=True, conv_bias=True, state_bits=None,
              states=None):
    """The Mamba-2 mixer of the normed input u [S, d]: [S, d], before the
    block's scalar and residual.  ``skip``, ``gate_first``, ``conv_bias`` and
    ``state_bits`` exist for the controls of ``families/granite_hybrid.py``
    (D left out; the norm before the gate; the convolution's bias left out;
    the state kept at that many mantissa bits after every position, 7 for
    bfloat16's): the layer is the defaults.  ``states``, a list, gains h
    after the last position [H, P, N]."""
    seq = u.shape[0]
    inner = ssm_heads * ssm_head_dim
    shared = ssm_groups * ssm_state
    z, xbc, dt = jnp.split(u @ _f32(w["w_in"]),
                           [inner, 2 * inner + 2 * shared], axis=-1)
    kernel = _f32(w["conv_w"])
    taps = kernel.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = sum(kernel[j] * padded[j:j + seq] for j in range(taps))
    if conv_bias:
        xbc = xbc + _f32(w["conv_b"])
    x, b, c = jnp.split(jax.nn.silu(xbc), [inner, inner + shared], axis=-1)
    x = x.reshape(seq, ssm_heads, ssm_head_dim)
    # a group's key and query, once a head of the group
    per_group = ssm_heads // ssm_groups
    b, c = (jnp.repeat(part.reshape(seq, ssm_groups, ssm_state), per_group,
                       axis=1) for part in (b, c))
    delta = jax.nn.softplus(dt + _f32(w["dt_bias"]))            # [S, H]
    rate = -jnp.exp(_f32(w["a_log"]))                           # [H]

    def position(h, args):
        x_t, b_t, c_t, d_t = args            # [H, P], [H, N] x 2, [H]
        h = (jnp.exp(d_t * rate)[:, None, None] * h
             + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = _bits(h, state_bits)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    last, y = jax.lax.scan(
        position, jnp.zeros((ssm_heads, ssm_head_dim, ssm_state),
                            jnp.float32), (x, b, c, delta))
    if states is not None:
        states.append(last)
    if skip:
        y = y + _f32(w["d_skip"])[:, None] * x
    y = y.reshape(seq, ssm_groups, inner // ssm_groups)
    gate = jax.nn.silu(z).reshape(y.shape)

    def normed(v):
        return v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)

    y = normed(y * gate) if gate_first else normed(y) * gate
    return (y.reshape(seq, inner) * _f32(w["gate_gain"])) @ _f32(w["w_out"])


def attention_layer(u, w, *, n_head, n_kv_head, head_dim, scale):
    """Causal grouped-query softmax attention of the normed input u [S, d]
    without rotary, the scores times ``scale``: [S, d], before the block's
    scalar and residual."""
    seq = u.shape[0]
    group = n_head // n_kv_head
    q = (u @ _f32(w["wq"])).reshape(seq, n_kv_head, group, head_dim)
    k = (u @ _f32(w["wk"])).reshape(seq, n_kv_head, head_dim)
    v = (u @ _f32(w["wv"])).reshape(seq, n_kv_head, head_dim)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def block(args):
        start, q_rows = args                              # [rows, KV, G, D]
        scores = jnp.einsum("qhgd,shd->hgqs", q_rows, k) * scale
        seen = (start + jnp.arange(rows))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("hgqs,shd->qhgd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(block, (jnp.arange(blocks) * rows, q.reshape(
        blocks, rows, n_kv_head, group, head_dim)))
    return out.reshape(blocks * rows, n_head * head_dim)[:seq] @ _f32(w["wo"])


def _layer(x, w, *, n_head, n_kv_head, head_dim, attn_scale, ssm_heads,
           ssm_head_dim, ssm_state, ssm_groups, eps, residual, faults,
           states):
    u = _rms_norm(x, w["norm_mixer"], eps)
    if "w_in" in w:
        mixed = ssm_layer(u, w, ssm_heads=ssm_heads,
                          ssm_head_dim=ssm_head_dim, ssm_state=ssm_state,
                          ssm_groups=ssm_groups, eps=eps, states=states,
                          **faults.get("ssm", {}))
    else:
        mixed = attention_layer(u, w, n_head=n_head, n_kv_head=n_kv_head,
                                head_dim=head_dim,
                                **{"scale": attn_scale,
                                   **faults.get("attention", {})})
    h = x + residual * mixed
    u = _rms_norm(h, w["norm_ffn"], eps)
    ffn = (jax.nn.silu(u @ _f32(w["w1"])) * (u @ _f32(w["w3"]))) \
        @ _f32(w["w2"])
    return h + residual * ffn


def _runs(layers: list) -> list:
    """The layers in order, cut where the kind changes: [[ssm x 5],
    [attention], [ssm x 4], ...]."""
    runs: list = []
    for w in layers:
        if runs and set(runs[-1][0]) == set(w):
            runs[-1].append(w)
        else:
            runs.append([w])
    return runs


def forward(weights: dict, tokens, *, n_head: int, n_kv_head: int,
            head_dim: int, attn_scale: float, ssm_heads: int,
            ssm_head_dim: int, ssm_state: int, ssm_groups: int, eps: float,
            embed_scale: float, residual: float, logit_divisor: float,
            faults=None, states=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another, layer by layer (a run
    of like layers as a ``lax.scan`` over them: the compiler then works on
    twelve layer bodies and not forty); the head a block of rows at a
    time.  ``states``, a list, gains every ssm
    layer's state after the last position, [B, H, P, N] a layer in layer
    order.  ``faults`` (the controls of ``families/granite_hybrid.py``):
    keyword arguments for :func:`ssm_layer` (under ``ssm``) and
    :func:`attention_layer` (under ``attention``) that make them something
    else."""
    with jax.default_matmul_precision("highest"):
        layer = functools.partial(
            _layer, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
            attn_scale=attn_scale, ssm_heads=ssm_heads,
            ssm_head_dim=ssm_head_dim, ssm_state=ssm_state,
            ssm_groups=ssm_groups, eps=eps, residual=residual,
            faults=faults or {})

        def run_of(x, run, last):
            """x through consecutive layers of ONE kind: a ``lax.scan``
            over them (its body is the layer, traced and compiled once a
            run and not once a layer; the run's weights side by side are a
            copy that lives as long as the scan)."""
            if len(run) == 1:
                return layer(x, run[0], states=last)

            def body(x, w):
                kept = []
                x = layer(x, w, states=kept)
                return x, tuple(kept)

            x, kept = jax.lax.scan(
                body, x, jax.tree.map(lambda *leaves: jnp.stack(leaves),
                                      *run))
            if last is not None and kept:
                last.extend(kept[0])
            return x

        def one(b):
            x = embed_scale * _f32(weights["embed"][tokens[b]])
            last = None if states is None else []
            for run in _runs(weights["layers"]):
                x = run_of(x, run, last)
            if states is not None:
                per_sequence.append(last)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)]) / logit_divisor

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
