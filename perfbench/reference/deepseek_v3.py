"""DeepSeek-V3 (DeepSeek-AI; ``DeepSeek-V3``'s ``config.json``, ``model_type``
``deepseek_v3``; the DeepSeek-V3 Technical Report, arXiv:2412.19437, section
2.1; DeepSeek-V2, arXiv:2405.04434, section 2.1, for latent attention; the
public ``modeling_deepseek_v3.py`` and ``modeling_rope_utils.py`` of the
``transformers`` library) forward pass in plain float32 ``jax.numpy``: the
EXPANDED form of the attention only (every position's K and V from its row,
a masked softmax), no cache, no kernel, no absorbed form, no grouped matmul,
no sharing of code with ``models/transformer.py``, ``ops/`` or another
reference.  A layer, for the residual stream x [S, d] (RMS norms with a
learned gain, eps 1e-6, no bias anywhere), u = RMSNorm_attn(x), position t:

  latent attention (128 heads; a head's own part 128, the rotary part 64):
    c_q = RMSNorm_1536(u W_qa)        q = c_q W_qb -> [128, 128 | 64]
    [c | k_r] = u W_kva -> [512 | 64] c = RMSNorm_512(c)
    q_r = rope_t(q_r)  a head         k_r = rope_t(k_r)  ONE for all heads
    [k_n, v] = c W_kvb -> [128, 128 | 128]   a head's key part, then its value
    k = [k_n, k_r shared by all heads]
    o = softmax(s q k^T + causal mask) v, heads concatenated, @ W_o
    s = 192^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1

  rope_t (YaRN), over the 32 pairs (i, i + 32) of the 64 channels:
    f_i = theta^(-2 i / 64)
    low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
      d(b) = 64 ln(original_max / (2 pi b)) / (2 ln theta)
    r_i = clip((i - low) / (high - low), 0, 1)
    w_i = f_i (1 - r_i) + (f_i / factor) r_i
    (a, b) -> g (a cos(t w_i) - b sin(t w_i), a sin(t w_i) + b cos(t w_i))
    g = (0.1 mscale ln(factor) + 1) / (0.1 mscale_all_dim ln(factor) + 1)

  h = x + attention(u)
  dense layer (the first ``first_k_dense_replace``), f = RMSNorm_ffn(h):
    (silu(f W1) * (f W3)) W2                         7,168 -> 18,432
  expert layer:
    s = sigmoid(f W_router)          float32, all 256 experts
    c = s + bias                     bias in the SELECTION only
    a group (32 neighbours) scores the sum of its two best c; outside the
    4 best of the 8 groups c counts as 0; S = top-8 of what is left
    g = 2.5 * s[S] / (sum s[S] + 1e-20)
    sum_{e in S and HELD} g_e (silu(f W1_e) * (f W3_e)) W2_e
      + (silu(f Ws1) * (f Ws3)) Ws2  the shared expert, ungated
  y = h + ffn(f)

and logits = RMSNorm(x_L) @ W_head.

``held`` = (first, count) makes the expert layer ONE RANK's of an
expert-parallel stage: ``w1`` / ``w3`` / ``w2`` hold the experts first ..
first + count - 1 alone, the router, its bias, the groups, the top-8 and
the gates' sum stay over all 256, and the routed sum runs over the chosen
experts that are held.  ``shared=False`` leaves the shared expert out
(every rank computes it alike: it counts once when the ranks' parts are
added up).

Departures from the two public files, each also under ``assumed`` in the
configuration's file:
- the rotary pairs are (i, i + 32), halves; the checkpoint stores them
  interleaved (2i, 2i + 1) and ``apply_rotary_pos_emb_interleave`` permutes
  them to halves before it turns them: a permutation of ``W_qb``'s and
  ``W_kva``'s columns, which seeded weights do not see;
- ``topk`` there is called with ``sorted=False``: the chosen SET is the
  same, its order is not defined there; here it is descending;
- the multi-token-prediction module (``num_nextn_predict_layers`` 1) is
  left out: the main model runs without it;
- the selection can be GIVEN (``forward``'s ``selection``): a near-tie of
  the 8th and 9th best score, or of the 4th and 5th best group, falls
  either way in the program's bfloat16 stream.

Every held expert is computed for every token and weighted by its gate
(zero for the tokens that did not choose it).  Weights may arrive in a
narrower dtype (bfloat16 values are exact in float32); one layer, and
within it one expert, is widened at a time, and attention runs a block of
query rows at a time.  The arithmetic is float32 at the highest matmul
precision throughout.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm_attn norm_ffn [d]} and
    attention: wq_a [d, Rq]; q_gain [Rq]; wq_b [Rq, H*(D+R)]; wkv_a
               [d, L+R]; kv_gain [L]; wkv_b [L, H*2D]; wo [H*D, d]
    dense:     w1 w3 [d, F]; w2 [F, d]
    experts:   router [d, E]; bias [E]; w1 w3 [C, d, Fe]; w2 [C, Fe, d];
               shared_w1 shared_w3 [d, Fs]; shared_w2 [Fs, d]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 128   # query rows scored at a time
GATE_EPS = 1e-20


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ _f32(w1)) * (h @ _f32(w3))) @ _f32(w2)


def _mscale(factor: float, weight: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * weight * math.log(factor) + 1.0


def softmax_scale(width: int, rope: dict) -> float:
    """``s``: 1 / sqrt(the keys' width), times the square of YaRN's
    all-dimensions gain where the configuration states one."""
    gain = _mscale(rope["factor"], rope["mscale_all_dim"]) \
        if rope.get("mscale_all_dim") else 1.0
    return width ** -0.5 * gain * gain


def yarn_frequencies(dim: int, rope: dict):
    """(w [dim / 2]: the pairs' angles a position; g: the gain on cos and
    sin), YaRN as the header writes it."""
    theta, factor = rope["theta"], rope["factor"]

    def pair_of(turns):
        return (dim * math.log(rope["original_max"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    if rope.get("mscale") and rope.get("mscale_all_dim"):
        gain = (_mscale(factor, rope["mscale"])
                / _mscale(factor, rope["mscale_all_dim"]))
    else:
        gain = _mscale(factor, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp, gain


def rotate(x, rope: dict):
    """x [S, ..., R] at positions 0 .. S - 1, the pairs (i, i + R / 2)."""
    half = x.shape[-1] // 2
    freq, gain = yarn_frequencies(x.shape[-1], rope)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = gain * jnp.cos(angle), gain * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(u, w, *, n_head, head_dim, rope_dim, latent, eps, rope,
              q_normed=True, rows_turned=True, gained=True):
    """The latent attention of u [S, d]: [S, d], before the residual.
    ``q_normed``, ``rows_turned`` and ``gained`` exist for the controls of
    ``families/deepseek_v3.py`` (the norm inside the query pair left out;
    the rotary left off the rows' shared key part; YaRN's gain left out of
    the scale): the layer is the defaults."""
    seq = u.shape[0]
    low = u @ _f32(w["wq_a"])
    if q_normed:
        low = _rms_norm(low, w["q_gain"], eps)
    q = (low @ _f32(w["wq_b"])).reshape(seq, n_head, head_dim + rope_dim)
    kv = u @ _f32(w["wkv_a"])
    c, k_rot = _rms_norm(kv[:, :latent], w["kv_gain"], eps), kv[:, latent:]
    q = jnp.concatenate([q[..., :head_dim], rotate(q[..., head_dim:], rope)],
                        axis=-1)
    if rows_turned:
        k_rot = rotate(k_rot, rope)
    up = (c @ _f32(w["wkv_b"])).reshape(seq, n_head, 2 * head_dim)
    k = jnp.concatenate([up[..., :head_dim], jnp.broadcast_to(
        k_rot[:, None, :], (seq, n_head, rope_dim))], axis=-1)
    v = up[..., head_dim:]
    scale = (softmax_scale(head_dim + rope_dim, rope) if gained
             else (head_dim + rope_dim) ** -0.5)
    rows = min(ROWS, seq)
    blocks = -(-seq // rows)
    q = jnp.pad(q, ((0, blocks * rows - seq), (0, 0), (0, 0)))
    keys = jnp.arange(seq)

    def block(args):
        start, q_rows = args                               # [rows, H, D+R]
        scores = jnp.einsum("qhd,shd->hqs", q_rows, k) * scale
        seen = (start + jnp.arange(rows))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (
        jnp.arange(blocks) * rows,
        q.reshape(blocks, rows, n_head, head_dim + rope_dim)))
    return out.reshape(blocks * rows, n_head * head_dim)[:seq] @ _f32(w["wo"])


def limited(choice, groups: int, groups_kept: int):
    """(``choice`` [S, E] with 0 outside each token's ``groups_kept`` best
    groups; every group's score [S, groups]: the sum of its two best)."""
    by_group = choice.reshape(choice.shape[0], groups, -1)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    cut = jax.lax.top_k(score, groups_kept)[0][:, -1:]
    # (ties at the cut keep more than groups_kept groups: measure zero on
    # float scores, and the tests draw scores without ties)
    kept = score >= cut
    return jnp.where(kept[:, :, None], by_group, 0.0).reshape(choice.shape), \
        score


def gates(h, w, top_k, scale, groups=1, groups_kept=1, given=None):
    """([S, E]: a token's gate for each of the router's experts, zero where
    it chose another; [3]: how a ``given`` selection compares with this
    one).  Scores sigmoid(logits); the top_k of score + bias under the
    group limit are chosen; a gate is the chosen expert's own score over
    the chosen scores' sum, times ``scale``.  ``given`` [S, k] is a
    selection made elsewhere: the gates are then of THOSE experts, and the
    second result holds the tokens whose given experts are not the top_k
    here; how far the worst given expert lies under the cut (the top_k-th
    best score + bias) among the groups the given experts lie in, in units
    of the score; and how far the worst given expert's GROUP lies under
    this selection's last kept group, in units of a group's score."""
    scores = jax.nn.sigmoid(h @ _f32(w["router"]))
    biased = scores + _f32(w["bias"])
    choice, group_score = (limited(biased, groups, groups_kept)
                           if groups > 1 else (biased, None))
    _, index = jax.lax.top_k(choice, top_k)                    # [S, k]
    compared = jnp.zeros((3,), jnp.float32)
    if given is not None:
        theirs = jnp.take_along_axis(biased, given, axis=-1)
        group_short = jnp.zeros(())
        among = biased
        if groups > 1:
            size = biased.shape[-1] // groups
            in_group = jax.nn.one_hot(given // size, groups).max(axis=1)
            among = jnp.where(jnp.repeat(in_group, size, axis=-1) > 0,
                              biased, 0.0)
            cut = jax.lax.top_k(group_score, groups_kept)[0][:, -1:]
            group_short = jnp.max(jnp.where(
                in_group > 0, jnp.maximum(cut - group_score, 0.0), 0.0))
        best = jax.lax.top_k(among, top_k)[0]
        short = jnp.maximum(best[:, -1:] - theirs, 0.0).max(axis=-1)
        differ = jnp.any(jnp.sort(given, axis=-1)
                         != jnp.sort(index, axis=-1), axis=-1)
        compared = jnp.stack([jnp.sum(differ).astype(jnp.float32),
                              jnp.max(short), group_short])
        index = given
    chosen = jnp.sum(jax.nn.one_hot(index, scores.shape[-1]), axis=1)
    picked = scores * chosen
    return (picked / (jnp.sum(picked, axis=-1, keepdims=True) + GATE_EPS)
            * scale, compared)


def expert_layer(h, w, top_k, scale, groups=1, groups_kept=1, held=None,
                 shared=True, given=None):
    """The feed-forward of an expert layer on h [S, d]: (the held experts'
    part of the routed sum + the shared expert, the comparison of
    :func:`gates`).  One held expert at a time over every token."""
    weight, compared = gates(h, w, top_k, scale, groups, groups_kept, given)
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


def _layer(x, w, given, *, eps, top_k, scale, groups, groups_kept, held,
           n_head, head_dim, rope_dim, latent, rope, faults):
    """(the layer's output, an expert layer's comparison with ``given``)."""
    u = _rms_norm(x, w["norm_attn"], eps)
    x = x + attention(u, w, n_head=n_head, head_dim=head_dim,
                      rope_dim=rope_dim, latent=latent, eps=eps, rope=rope,
                      **faults.get("attention", {}))
    f = _rms_norm(x, w["norm_ffn"], eps)
    if "router" not in w:
        return x + _swiglu(f, w["w1"], w["w3"], w["w2"]), None
    if not faults.get("group_limit", True):
        groups = groups_kept = 1
    out, compared = expert_layer(f, w, top_k, scale, groups, groups_kept,
                                 held, given=given)
    return x + out, compared


def forward(weights: dict, tokens, *, n_head: int, head_dim: int,
            rope_dim: int, latent: int, eps: float, top_k: int,
            scale: float, groups: int, groups_kept: int, rope: dict,
            held=None, selection=None, report=None, faults=None):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32).  Sequences run one after another, a layer at a time; the
    head a block of rows at a time.

    ``rope``: ``theta``, ``factor``, ``original_max``, ``beta_fast``,
    ``beta_slow``, ``mscale``, ``mscale_all_dim``.  ``selection``, one
    [B, S, k] array of experts an expert layer, makes the experts those
    (the gates are still this module's, from its own scores); ``report``
    is then called with [expert layers, B, 3] (:func:`gates`).  ``faults``
    (the controls of ``families/deepseek_v3.py``): ``attention``, keyword
    arguments that make :func:`attention` something else, and
    ``group_limit`` False, a plain top-k of all the experts."""
    with jax.default_matmul_precision("highest"):
        def one(b):
            x = _f32(weights["embed"][tokens[b]])
            given = iter(selection or ())
            seen = []
            for w in weights["layers"]:
                x, compared = _layer(
                    x, w, next(given)[b] if selection and "router" in w
                    else None, eps=eps, top_k=top_k, scale=scale,
                    groups=groups, groups_kept=groups_kept, held=held,
                    n_head=n_head, head_dim=head_dim, rope_dim=rope_dim,
                    latent=latent, rope=rope, faults=faults or {})
                if compared is not None:
                    seen.append(compared)
            x = _rms_norm(x, weights["final_norm"], eps)
            head = _f32(weights["head"])
            return jnp.concatenate([
                x[start:start + ROWS] @ head
                for start in range(0, x.shape[0], ROWS)]), (
                    jnp.stack(seen) if seen else jnp.zeros((0, 3)))

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
