"""MiniCPM-SALA (openbmb; ``MiniCPM-SALA`` config.json) forward pass in plain
float32 ``jax.numpy``: no kernels, no cache, no chunking of the recurrence,
no sharing of code with ``models/`` or ``ops/``.  The residual stream starts
at ``scale_emb * E[token]``; a layer, with r = scale_depth / sqrt(published
depth), is

    x  = x + r * Mixer(RMSNorm(x))
    x  = x + r * W_down(silu(W_gate u) * (W_up u)),   u = RMSNorm(x)

and logits = W_head(RMSNorm(x_L) / (hidden_size / dim_model_base)).

``lightning-attn`` (Lightning Attention, arXiv:2401.04658), h the normed
input, every head of 128 with a fixed decay lam = exp(-s):

    q, k, v = h Wq, h Wk, h Wv;  q, k = RMSNorm_128(q), RMSNorm_128(k)
    q, k rotated (half-rotation)
    S_t = lam S_{t-1} + k_t v_t^T;   o_t = q_t^T S_t / sqrt(128)
    y   = (RMSNorm_128(o) * sigmoid(h Wg)) Wo

run as written, one position after another.

``minicpm4`` (InfLLM v2 block selection, arXiv:2506.07900 section 2.2):
grouped-query attention without rotary, q and k normed, the output gated as
above.  A query with a context of n keys attends all of them while
n < dense_len; from there on, per K/V head (its query heads share one
selection): compressed keys c_i = mean(k[stride i : stride i + kernel]) for
every COMPLETE kernel; per query head p = softmax_i(q . c_i / sqrt(128));
P = sum over the group; a block's score the largest P_i among the kernels
that overlap it; selected the first ``init_blocks`` blocks, the
``window / block`` blocks ending at the query's own and the ``topk``
highest-scoring of the rest (ties to the lower block); softmax attention
over the causal positions of the selected blocks.  Selection is done
naively per query, a block of query rows at a time so that 12,288
positions fit.

``selection`` replaces the reference's own choice of blocks by a given one
(the program's, for the comparison that must not see a near-tie flip as an
error); ``report`` then receives, per sparse layer, how the given choice
differs from the reference's own.

Weights are a dict in this module's own names:
  embed [V, d]   head [d, V]   final_norm [d]
  layers: a list, each {norm1 norm2 [d]; wq wo wg [d, H*D] / [H*D, d];
    wk wv [d, KV*D]; q_norm k_norm [D]; o_norm [D] (lightning-attn only);
    w_gate w_up [d, F]; w_down [F, d]}
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROWS = 256   # query rows scored at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(gain)


def _rotate(x, theta):
    seq, _, dim = x.shape
    inverse = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def slopes(heads: int):
    """s_h = 2^(-8 (h + 1) / H): head h keeps exp(-s_h) of its state a
    position."""
    return 2.0 ** (-8.0 * (jnp.arange(heads, dtype=jnp.float32) + 1.0)
                   / heads)


def lightning(q, k, v):
    """q, k, v [S, H, D] -> o [S, H, D], the recurrence step by step."""
    _, heads, dim = q.shape
    keep = jnp.exp(-slopes(heads))[:, None, None]

    def step(state, args):
        q_t, k_t, v_t = args                                   # [H, D]
        state = keep * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    _, out = jax.lax.scan(step, jnp.zeros((heads, dim, dim), jnp.float32),
                          (q, k, v))
    return out / math.sqrt(dim)


def block_selected(q, k, v, sparse, selection=None):
    """q [S, H, D], k/v [S, KV, D] -> (o [S, H, D], comparison or None).
    ``selection`` [KV, S, NB]: the blocks to attend in place of the
    reference's own where a query selects at all."""
    seq, heads, dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    kernel, stride, block = (sparse[name] for name in
                             ("kernel_size", "kernel_stride", "block_size"))
    blocks = -(-seq // block)
    kernels = max(0, (seq - kernel) // stride + 1)
    compressed = jnp.stack([k[stride * i:stride * i + kernel].mean(axis=0)
                            for i in range(kernels)]) if kernels else None
    # kernel i overlaps block b
    starts = stride * jnp.arange(kernels)
    overlap = ((starts[None, :] < block * (jnp.arange(blocks)[:, None] + 1))
               & (starts[None, :] + kernel > block * jnp.arange(blocks)[:, None]))
    window_blocks = sparse["window_size"] // block
    rows = min(ROWS, seq)
    pad = -seq % rows
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    given = None if selection is None else jnp.pad(
        selection, ((0, 0), (0, pad), (0, 0)))
    keys = jnp.arange(seq)
    k_all = jnp.repeat(k, group, axis=1)
    v_all = jnp.repeat(v, group, axis=1)

    def some_rows(args):
        first, q_rows, given_rows = args                # [rows, H, D]
        at = first + jnp.arange(rows)
        own = at // block
        every = jnp.arange(blocks)[None, :] <= own[:, None]   # [rows, NB]
        chosen = jnp.broadcast_to(every[None], (kv_heads, rows, blocks))
        differ = jnp.zeros((3,), jnp.float32)
        if kernels:
            scores = jnp.einsum("qhd,nhd->hqn", q_rows,
                                jnp.repeat(compressed, group, axis=1))
            complete = (starts + kernel)[None, :] <= (at + 1)[:, None]
            scores = jnp.where(complete[None], scores / math.sqrt(dim),
                               -jnp.inf)
            prob = jnp.where(complete[None],
                             jax.nn.softmax(scores, axis=-1), 0.0)
            prob = prob.reshape(kv_heads, group, rows, kernels).sum(axis=1)
            score = jnp.max(jnp.where(overlap[None, None], prob[:, :, None],
                                      0.0), axis=-1)       # [KV, rows, NB]
            forced = (jnp.arange(blocks)[None, :] < sparse["init_blocks"]) | (
                every & (jnp.arange(blocks)[None, :]
                         > (own - window_blocks)[:, None]))
            rest = every & ~forced
            ranked = jnp.argsort(jnp.where(rest[None], -score, 1.0),
                                 axis=-1, stable=True)
            rank = jnp.argsort(ranked, axis=-1, stable=True)
            mine = forced[None] | (rest[None] & (rank < sparse["topk"]))
            selects = (at + 1 >= sparse["dense_len"])[None, :, None]
            if given_rows is not None:
                # the score at the cut: the last one the reference took
                cut = jnp.min(jnp.where(mine & rest[None], score, jnp.inf),
                              axis=-1, keepdims=True)
                apart = jnp.where(
                    (given_rows != mine) & selects,
                    jnp.abs(score - cut) / jnp.maximum(cut, 1e-30), 0.0)
                real = at < seq
                differ = jnp.stack([
                    jnp.sum(jnp.any(apart > 0, axis=(0, 2)) & real),
                    jnp.sum(jnp.any(selects, axis=(0, 2)) & real),
                    jnp.max(jnp.where(real[None, :, None], apart, 0.0))])
                mine = given_rows
            chosen = jnp.where(selects, mine, chosen)
        allowed = jnp.repeat(chosen, block, axis=-1)[..., :seq]
        seen = allowed & (keys[None, None, :] <= at[None, :, None])
        seen = jnp.repeat(seen, group, axis=0)             # [H, rows, S]
        scores = jnp.einsum("qhd,khd->hqk", q_rows, k_all) / math.sqrt(dim)
        scores = jnp.where(seen, scores, -jnp.inf)
        return (jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1),
                           v_all), differ)

    count = (seq + pad) // rows
    args = (jnp.arange(count) * rows, q.reshape(count, rows, heads, dim),
            None if given is None else jnp.moveaxis(
                given.reshape(kv_heads, count, rows, blocks), 1, 0))
    out, differ = jax.lax.map(some_rows, args)
    compared = None if selection is None else jnp.stack(
        [differ[:, 0].sum(), differ[:, 1].sum(), differ[:, 2].max()])
    return out.reshape(count * rows, heads, dim)[:seq], compared


def _layer(x, w, kind, selection, *, n_head, kv_heads, head_dim, eps, theta,
           residual, sparse):
    seq = x.shape[0]
    h = _rms_norm(x, w["norm1"], eps)
    kv = kv_heads[kind]
    q = (h @ _f32(w["wq"])).reshape(seq, n_head, head_dim)
    k = (h @ _f32(w["wk"])).reshape(seq, kv, head_dim)
    v = (h @ _f32(w["wv"])).reshape(seq, kv, head_dim)
    q = _rms_norm(q, w["q_norm"], eps)
    k = _rms_norm(k, w["k_norm"], eps)
    compared = None
    if kind == "lightning-attn":
        mixed = lightning(_rotate(q, theta), _rotate(k, theta), v)
        mixed = _rms_norm(mixed, w["o_norm"], eps)
    else:
        mixed, compared = block_selected(q, k, v, sparse, selection)
    mixed = mixed.reshape(seq, n_head * head_dim)
    mixed = mixed * jax.nn.sigmoid(h @ _f32(w["wg"]))
    x = x + residual * (mixed @ _f32(w["wo"]))
    u = _rms_norm(x, w["norm2"], eps)
    hidden = jax.nn.silu(u @ _f32(w["w_gate"])) * (u @ _f32(w["w_up"]))
    return x + residual * (hidden @ _f32(w["w_down"])), compared


def forward(weights: dict, tokens, *, mixers, scale_emb, logit_divisor,
            selection=None, report=None, **model):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has.  ``selection``: a list, one
    [B, KV, S, NB] mask a ``minicpm4`` layer in layer order, attended in
    place of the reference's own selection; ``report(compared)`` then
    receives [layers, B, 3]: queries whose blocks differ from the
    reference's own, queries that select, and the farthest that a
    differing block's score lies from the reference's cut (as a share of
    the cut)."""
    eps = model["eps"]
    with jax.default_matmul_precision("highest"):
        def one(b):
            x = scale_emb * _f32(weights["embed"][tokens[b]])
            compared, sparse_seen = [], 0
            for w, kind in zip(weights["layers"], mixers):
                given = None
                if kind == "minicpm4" and selection:
                    given = selection[sparse_seen][b]
                    sparse_seen += 1
                x, differ = _layer(x, w, kind, given, **model)
                if differ is not None:
                    compared.append(differ)
            x = _rms_norm(x, weights["final_norm"], eps) / logit_divisor
            return x @ _f32(weights["head"]), compared

        outs = [one(b) for b in range(tokens.shape[0])]
    if report is not None and outs[0][1]:
        report(jnp.stack([jnp.stack(c) for _, c in outs], axis=1))
    return jnp.stack([logits for logits, _ in outs])
