"""K-EXAONE (LG AI Research; ``K-EXAONE-236B-A23B``'s ``config.json``,
``model_type`` ``exaone_moe``) forward pass in plain float32 ``jax.numpy``:
no kernels, no cache, no grouped matmul, no sharing of code with
``models/transformer.py``.  A layer, for the residual stream x [S, d] (RMS
norms with a learned gain, eps 1e-5, no bias anywhere):

    q, k, v = x Wq, x Wk, x Wv                 64 heads over 8 K/V heads of 128
    q, k = RMSNorm(q), RMSNorm(k)              a gain[128] each, on every head
    sliding layer:  q, k rotated (half-rotation, theta 1e6); query i sees
                    key j where 0 <= i - j < 128
    full layer:     no rotation at all; causal
    o   = softmax(q k^T / sqrt(128) + mask) v, heads concatenated, @ Wo
    h   = x + RMSNorm_attn(o)                  the norm on the branch's OUTPUT
    dense layer (the first ``first_k_dense_replace``):
          f = (silu(h W1) * (h W3)) W2                   6,144 -> 18,432
    expert layer:
          s = sigmoid(h W_router)              float32, all 128 experts
          S = top-8 of (s + bias)              bias in the SELECTION only
          g = 2.5 * s[S] / (sum s[S] + 1e-20)
          f = sum_{e in S and HELD} g_e (silu(h W1_e) * (h W3_e)) W2_e
              + (silu(h Ws1) * (h Ws3)) Ws2    the shared expert, ungated
    y   = h + RMSNorm_ffn(f)

and logits = RMSNorm(x_L) @ W_head.

``held`` = (first, count) makes the expert layer ONE RANK's of an
expert-parallel stage: ``w1`` / ``w3`` / ``w2`` then hold the experts first
.. first + count - 1 alone, the router, its bias, the top-8 and the gates'
sum stay over all 128, and the routed sum runs over the chosen experts that
are held: what the other ranks' experts would add is left out, and that
partial result goes on to the next layer.  ``shared=False`` leaves the
shared expert out (every rank computes it alike: it counts once when the
ranks' parts are added up).

Departures from the published description, each also under ``assumed`` in
the configuration's file:
- the config gives no equations for the residual path, the q/k norms or
  which layers rotate: this is the EXAONE family's (transformers'
  ``exaone4``): norms on the branches' outputs, q/k normed per head before
  the rotation, rotation on the sliding layers only.  ``placement="pre"``
  moves both norms to the branches' inputs (x + f(RMSNorm(x))), should the
  published code say so;
- the gates' sum takes 1e-20 here, the deepseek-style routers' constant;
  the program's ``select_experts`` adds 1e-6 (2.5e-7 of a sum of four);
- ``n_group`` = ``topk_group`` = 1: no group-limited selection is written;
- the multi-token-prediction block is not here (``omitted: ["mtp"]``).

Every held expert is computed for every token and weighted by its gate,
which is zero for the tokens that did not choose it: no routing machinery
to share a fault with the program.  The selection can be GIVEN
(``forward``'s ``selection``), as ``reference/lfm2.py``'s: a near-tie of
the 8th and 9th best of 128 scores falls either way in the program's
bfloat16 stream.  Weights may arrive in a narrower dtype (bfloat16 values
are exact in float32); one layer, and within it one expert, is widened at a
time, and attention runs a block of query rows at a time, so that the
published widths fit beside the weights.  The arithmetic is float32 at the
highest matmul precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_attn norm_ffn [d]; wq [d, H*D]; wk wv
    [d, KV*D]; wo [H*D, d]; q_gain k_gain [D]} and
    dense:   w1 w3 [d, F]; w2 [F, d]
    experts: router [d, E]; bias [E]; w1 w3 [C, d, Fe]; w2 [C, Fe, d] (C the
             experts held: E where ``held`` is None); shared_w1 shared_w3
             [d, Fs]; shared_w2 [Fs, d]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 256   # query rows scored at a time
GATE_EPS = 1e-20


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


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def attention(u, w, *, n_head, n_kv_head, head_dim, eps, theta, window,
              rotary):
    """Grouped-query attention of u [S, d]: [S, d], before the norm and the
    residual.  Query head h reads K/V head h // (H / KV); ``window`` W > 0
    hides keys W or more positions back; the mask is written out, a block
    of rows at a time."""
    seq = u.shape[0]
    group = n_head // n_kv_head
    q = (u @ _f32(w["wq"])).reshape(seq, n_head, head_dim)
    k = (u @ _f32(w["wk"])).reshape(seq, n_kv_head, head_dim)
    v = (u @ _f32(w["wv"])).reshape(seq, n_kv_head, head_dim)
    q, k = _rms_norm(q, w["q_gain"], eps), _rms_norm(k, w["k_gain"], eps)
    if rotary:
        q, k = _rotate(q, theta), _rotate(k, theta)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def block(args):
        start, q_rows = args                           # [rows, KV, G, D]
        scores = jnp.einsum("qkgd,skd->kgqs", q_rows, k) / math.sqrt(head_dim)
        back = (start + jnp.arange(rows))[:, None] - keys[None, :]
        seen = back >= 0
        if window:
            seen &= back < window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(block, (
        jnp.arange(blocks) * rows,
        q.reshape(blocks, rows, n_kv_head, group, head_dim)))
    mixed = out.reshape(blocks * rows, n_head * head_dim)[:seq]
    return mixed @ _f32(w["wo"])


def gates(h, w, top_k, scale, given=None):
    """([S, E]: a token's gate for each of the router's experts, zero where
    it chose another; [2]: how a ``given`` selection compares with this
    one).  Scores sigmoid(logits); the top_k of score + bias are chosen; a
    gate is the chosen expert's own score over the chosen scores' sum,
    times ``scale``.

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
    return (picked / (jnp.sum(picked, axis=-1, keepdims=True) + GATE_EPS)
            * scale, compared)


def expert_layer(h, w, top_k, scale, held=None, shared=True, given=None):
    """The feed-forward of an expert layer on h [S, d], before the norm and
    the residual: (the held experts' part of the routed sum + the shared
    expert, the comparison of :func:`gates`).  One held expert at a time
    over every token."""
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


def _layer(x, w, given, *, eps, top_k, scale, held, placement, **heads):
    """(the layer's output, an expert layer's comparison with ``given``)."""
    pre = placement == "pre"

    def branch(x, gain, run):
        """x + the branch ``run`` with its norm where ``placement`` says"""
        if pre:
            return x + run(_rms_norm(x, gain, eps))
        return x + _rms_norm(run(x), gain, eps)

    x = branch(x, w["norm_attn"], lambda u: attention(u, w, eps=eps, **heads))
    seen = []

    def ffn(h):
        if "router" not in w:
            return _swiglu(h, w["w1"], w["w3"], w["w2"])
        out, compared = expert_layer(h, w, top_k, scale, held, given=given)
        seen.append(compared)
        return out

    x = branch(x, w["norm_ffn"], ffn)
    return x, seen[0] if seen else None


def forward(weights: dict, tokens, *, n_head: int, n_kv_head: int,
            head_dim: int, eps: float, theta: float, windows, rotary,
            top_k: int, scale: float, held=None, placement: str = "post",
            selection=None, report=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  ``windows`` and ``rotary`` give each layer's window (0:
    full) and whether it rotates.  Sequences run one after another; the
    head a block of rows at a time.

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
            for w, window, turns in zip(weights["layers"], windows, rotary):
                x, compared = _layer(
                    x, w, next(given)[b] if selection and "router" in w
                    else None, eps=eps, top_k=top_k, scale=scale, held=held,
                    placement=placement, n_head=n_head, n_kv_head=n_kv_head,
                    head_dim=head_dim, theta=theta, window=window,
                    rotary=turns)
                if compared is not None:
                    seen.append(compared)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)]), (
                    jnp.stack(seen) if seen else jnp.zeros((0, 2)))

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
