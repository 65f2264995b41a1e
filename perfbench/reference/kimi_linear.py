"""Kimi Linear (Moonshot AI; ``Kimi-Linear-48B-A3B-Instruct``'s
``config.json``, ``model_type`` ``kimi_linear``; the Kimi Linear report,
arXiv:2510.26692, and the model's public ``modeling_kimi.py``) forward pass
in plain float32 ``jax.numpy``: no kernels, no cache, no chunks, no grouped
matmul, no sharing of code with ``models/transformer.py`` or ``ops/``.  A
layer, for the residual stream x [S, d] (RMS norms with a learned gain, eps
1e-5, no bias anywhere), u = RMSNorm_attn(x):

  KDA layer (32 heads of 128, d_k = d_v = 128), position t:
    q = l2norm(silu(conv4(u W_q)))   k = l2norm(silu(conv4(u W_k)))
    v = silu(conv4(u W_v))           depthwise causal, 4 taps, zeros before
                                     the first position
    g_t = -exp(A_log[h]) * softplus(W_fb (W_fa u_t) + dt_bias)   [h, d_k]
    b_t = sigmoid(W_b u_t)                                       [h]
    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T        a [128, 128] state a head
    o_t = S_t^T q_t / sqrt(128)
    y_t = W_o (RMSNorm_head(o_t; gain[128]) * sigmoid(W_gb (W_ga u_t)))
  a ``lax.scan`` over the positions of exactly this.

  MLA layer, NoPE (``mla_use_nope``: no rotary anywhere):
    q = u W_q -> [32, 128 + 64]      [c, k_pe] = u W_kva -> [512 | 64]
    [k_nope, v] = RMSNorm(c) W_kvb -> [32, 128 | 128]
    k = [k_nope, k_pe shared by all heads]
    o = softmax(q k^T / sqrt(192) + causal mask) v, heads concatenated, @ W_o

  h = x + mixer(u)
  dense layer (the first ``first_k_dense_replace``), f = RMSNorm_ffn(h):
    (silu(f W1) * (f W3)) W2                         2,304 -> 9,216
  expert layer:
    s = sigmoid(f W_router)          float32, all 256 experts
    S = top-8 of (s + bias)          bias in the SELECTION only
    g = 2.446 * s[S] / (sum s[S] + 1e-20)
    sum_{e in S and HELD} g_e (silu(f W1_e) * (f W3_e)) W2_e
      + (silu(f Ws1) * (f Ws3)) Ws2  the shared expert, ungated
  y = h + ffn(f)

and logits = RMSNorm(x_L) @ W_head.

``held`` = (first, count) makes the expert layer ONE RANK's of an
expert-parallel stage, as ``reference/k_exaone.py``'s: ``w1`` / ``w3`` /
``w2`` hold the experts first .. first + count - 1 alone, the router, its
bias, the top-8 and the gates' sum stay over all 256, and the routed sum
runs over the chosen experts that are held.  ``shared=False`` leaves the
shared expert out (every rank computes it alike: it counts once when the
ranks' parts are added up).

Departures from the published code, each also under ``assumed`` in the
configuration's file:
- the gates' rank (128), ``A_log`` [32] and ``dt_bias`` [4096] are the
  published module's shapes; the catalog's ``config`` does not give them;
- no bias on the short convolutions (the published layer's
  ``ShortConvolution`` has none by default) and none on the decay gate's
  second projection beside ``dt_bias``;
- l2norm adds 1e-6 under the root, as the published kernel does;
- the output gate is a plain sigmoid of the low-rank projection;
- the gates' sum takes 1e-20 (the deepseek-style routers' constant); the
  program's ``select_experts`` adds 1e-6;
- ``num_expert_group`` = ``topk_group`` = 1: no group-limited selection;
- ``num_nextn_predict_layers`` is 0: nothing is omitted.

Every held expert is computed for every token and weighted by its gate
(zero for the tokens that did not choose it).  The selection can be GIVEN
(``forward``'s ``selection``), as ``reference/k_exaone.py``'s: a near-tie
of the 8th and 9th best of 256 scores falls either way in the program's
bfloat16 stream.  Weights may arrive in a narrower dtype (bfloat16 values
are exact in float32); one layer, and within it one expert, is widened at a
time, and attention runs a block of query rows at a time.  The arithmetic
is float32 at the highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_attn norm_ffn [d]} and
    kda:     wq wk wv [d, H*D]; conv_q conv_k conv_v [4, H*D]; decay_a
             [d, 128]; decay_b [128, H*D]; a_log [H]; dt_bias [H*D];
             gate_a [d, 128]; gate_b [128, H*D]; beta [d, H]; o_gain [D];
             wo [H*D, d]
    mla:     wq [d, H*(D+R)]; wkv_a [d, L+R]; kv_gain [L]; wkv_b
             [L, H*2D]; wo [H*D, d]
    dense:   w1 w3 [d, F]; w2 [F, d]
    experts: router [d, E]; bias [E]; w1 w3 [C, d, Fe]; w2 [C, Fe, d];
             shared_w1 shared_w3 [d, Fs]; shared_w2 [Fs, d]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 256   # query rows scored at a time
GATE_EPS = 1e-20
L2_EPS = 1e-6


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def _conv(x, kernel):
    """Depthwise causal convolution of x [S, C] with ``kernel`` [K, C]:
    the last tap meets the current position, zeros before the first."""
    taps = kernel.shape[0]
    seq = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(_f32(kernel)[j] * padded[j:j + seq] for j in range(taps))


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda(u, w, *, n_head, head_dim, eps, state_bits=None, delta=True,
        states=None):
    """The delta-rule mixer of u [S, d]: [S, d], before the residual.
    ``state_bits`` and ``delta`` exist for the controls of
    ``families/kimi_linear.py`` (a state kept at that many mantissa bits,
    7 for bfloat16's; the correction term dropped): the layer is the
    defaults.  ``states``, a list, gains S after the last position
    [H, d_k, d_v]."""
    seq = u.shape[0]

    def heads(x):
        return x.reshape(seq, n_head, head_dim)

    q = _l2norm(heads(jax.nn.silu(_conv(u @ _f32(w["wq"]), w["conv_q"]))))
    k = _l2norm(heads(jax.nn.silu(_conv(u @ _f32(w["wk"]), w["conv_k"]))))
    v = heads(jax.nn.silu(_conv(u @ _f32(w["wv"]), w["conv_v"])))
    fall = -jnp.exp(_f32(w["a_log"]))[:, None] * heads(jax.nn.softplus(
        (u @ _f32(w["decay_a"])) @ _f32(w["decay_b"]) + _f32(w["dt_bias"])))
    beta = jax.nn.sigmoid(u @ _f32(w["beta"]))                  # [S, H]

    def position(state, args):
        q_t, k_t, v_t, g_t, b_t = args        # [H, D] x 4, [H]
        state = jnp.exp(g_t)[:, :, None] * state
        read = jnp.einsum("hde,hd->he", state, k_t) if delta else 0.0
        state = (state + b_t[:, None, None]
                 * k_t[:, :, None] * (v_t - read)[:, None, :])
        if state_bits is not None:
            # (a cast there and back the compiler may drop: this it keeps)
            state = jax.lax.reduce_precision(state, 8, state_bits)
        return state, jnp.einsum("hde,hd->he", state, q_t)

    last, out = jax.lax.scan(
        position, jnp.zeros((n_head, head_dim, head_dim), jnp.float32),
        (q, k, v, fall, beta))
    if states is not None:
        states.append(last)
    out = _rms_norm(out / math.sqrt(head_dim), w["o_gain"], eps)
    gate = jax.nn.sigmoid((u @ _f32(w["gate_a"])) @ _f32(w["gate_b"]))
    return (out.reshape(seq, n_head * head_dim) * gate) @ _f32(w["wo"])


def mla(u, w, *, n_head, head_dim, shared_dim, latent, eps,
        normed=True, scale_dim=None):
    """The latent attention of u [S, d]: [S, d], before the residual; no
    rotary.  ``normed`` and ``scale_dim`` exist for the controls (the
    latent left un-normed; another width under the scale's root)."""
    seq = u.shape[0]
    q = (u @ _f32(w["wq"])).reshape(seq, n_head, head_dim + shared_dim)
    kv = u @ _f32(w["wkv_a"])
    c, k_shared = kv[:, :latent], kv[:, latent:]
    if normed:
        c = _rms_norm(c, w["kv_gain"], eps)
    up = (c @ _f32(w["wkv_b"])).reshape(seq, n_head, 2 * head_dim)
    k = jnp.concatenate([up[..., :head_dim], jnp.broadcast_to(
        k_shared[:, None, :], (seq, n_head, shared_dim))], axis=-1)
    v = up[..., head_dim:]
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)
    scale = math.sqrt(scale_dim or head_dim + shared_dim)

    def block(args):
        start, q_rows = args                               # [rows, H, D+R]
        scores = jnp.einsum("qhd,shd->hqs", q_rows, k) / scale
        seen = (start + jnp.arange(rows))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (
        jnp.arange(blocks) * rows,
        q.reshape(blocks, rows, n_head, head_dim + shared_dim)))
    return out.reshape(blocks * rows, n_head * head_dim)[:seq] @ _f32(w["wo"])


def gates(h, w, top_k, scale, given=None):
    """([S, E]: a token's gate for each of the router's experts, zero where
    it chose another; [2]: how a ``given`` selection compares with this
    one), as ``reference/k_exaone.py``'s: scores sigmoid(logits); the top_k
    of score + bias are chosen; a gate is the chosen expert's own score
    over the chosen scores' sum, times ``scale``.  ``given`` [S, k] is a
    selection made elsewhere: the gates are then of THOSE experts, and the
    second result counts the tokens whose given experts are not the top_k
    here, and how far the worst given expert lies under this selection's
    cut, in units of the score."""
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
    return (picked / (jnp.sum(picked, axis=-1, keepdims=True) + GATE_EPS)
            * scale, compared)


def expert_layer(h, w, top_k, scale, held=None, shared=True, given=None):
    """The feed-forward of an expert layer on h [S, d]: (the held experts'
    part of the routed sum + the shared expert, the comparison of
    :func:`gates`).  One held expert at a time over every token."""
    weight, compared = gates(h, w, top_k, scale, given)        # [S, E]
    first, count = held if held is not None else (0, weight.shape[1])
    weight = weight[:, first:first + count]

    def one(total, args):
        w1, w3, w2, gate = args
        return total + gate[:, None] * _swiglu(h, w1, w3, w2), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(h),
                            (w["w1"], w["w3"], w["w2"], weight.T))
    if shared:
        total = total + _swiglu(h, w["shared_w1"], w["shared_w3"],
                                w["shared_w2"])
    return total, compared


def _layer(x, w, given, *, eps, top_k, scale, held, n_head, head_dim,
           shared_dim, latent, faults, states=None):
    """(the layer's output, an expert layer's comparison with ``given``)."""
    u = _rms_norm(x, w["norm_attn"], eps)
    if "wkv_a" in w:
        x = x + mla(u, w, n_head=n_head, head_dim=head_dim,
                    shared_dim=shared_dim, latent=latent, eps=eps,
                    **faults.get("mla", {}))
    else:
        x = x + kda(u, w, n_head=n_head, head_dim=head_dim, eps=eps,
                    states=states, **faults.get("kda", {}))
    f = _rms_norm(x, w["norm_ffn"], eps)
    if "router" not in w:
        return x + _swiglu(f, w["w1"], w["w3"], w["w2"]), None
    out, compared = expert_layer(f, w, top_k, scale, held, given=given)
    return x + out, compared


def forward(weights: dict, tokens, *, n_head: int, head_dim: int,
            shared_dim: int, latent: int, eps: float, top_k: int,
            scale: float, held=None, selection=None, report=None,
            faults=None, states=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another; the head a block of
    rows at a time.

    ``selection``, one [B, S, k] array of experts an expert layer, makes
    the experts those (the gates are still this module's, from its own
    scores); ``report`` is then called with [expert layers, B, 2]: the
    tokens whose given experts are not this module's own, and how far
    under this module's cut the worst of them lies.  ``states``, a list,
    gains every KDA layer's state after the last position, [B, H, d_k,
    d_v] a layer in layer order.  ``faults`` (the
    controls of ``families/kimi_linear.py``): keyword arguments for
    :func:`kda` and :func:`mla` that make them something else."""
    with jax.default_matmul_precision("highest"):
        def one(b):
            x = _f32(weights["embed"][tokens[b]])
            given = iter(selection or ())
            seen, last = [], None if states is None else []
            for w in weights["layers"]:
                x, compared = _layer(
                    x, w, next(given)[b] if selection and "router" in w
                    else None, eps=eps, top_k=top_k, scale=scale, held=held,
                    n_head=n_head, head_dim=head_dim, shared_dim=shared_dim,
                    latent=latent, faults=faults or {}, states=last)
                if compared is not None:
                    seen.append(compared)
            if states is not None:
                per_sequence.append(last)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)]), (
                    jnp.stack(seen) if seen else jnp.zeros((0, 2)))

        per_sequence: list = []
        logits, compared = zip(*(one(b) for b in range(tokens.shape[0])))
        if states is not None:
            states.extend(jnp.stack(layer) for layer in zip(*per_sequence))
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
