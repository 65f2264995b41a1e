"""A model written as a layer PATTERN (window and full attention mixed,
rotary on some layers and no position signal on others, dropless experts
routed before attention) against the benchmark's plain reference
(``perfbench/reference/smallthinker.py``), on seeded random weights at a
small size: the full forward, prefill + decode through the cache past the
point where a window layer's ring wraps, the same through ``DecodeServer``
with a prefix longer than the window reused from the prefix tree, dropless
routing under total imbalance, the two kinds of layer told apart, and GPT-2
through the same pattern and cache.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from parameter_server_distributed_tpu.models import (generation, mixers, moe,
                                                     serving)
from parameter_server_distributed_tpu.models import transformer as tr
from parameter_server_distributed_tpu.models.serving import (DecodeServer,
                                                             _bucket)
from parameter_server_distributed_tpu.ops.blockwise_attention import (
    blockwise_attention)
from parameter_server_distributed_tpu.ops.sparse_attention import SparseSpec
from perfbench.families import smallthinker as family
from perfbench.reference import smallthinker as reference

# 8 layers = two periods of (full without positions, window, window,
# window); width 64, 4 heads of 32 over 2 K/V heads (4 x 32 = 128 != 64),
# 8 experts top-3 of width 48, window 8, vocabulary 512
CONFIG = {
    "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8,
    "moe_ffn_hidden_size": 48, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_layout": [0, 1, 1, 1] * 2,
    "sliding_window_size": 8, "rope_theta": 1500000, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "vocab_size": 512,
    "max_position_embeddings": 128,
    "assumed": {"dtype": "float32", "scan_layers": False, "remat": False,
                "remat_policy": "full", "loss_chunk": 32}}
TOLERANCE = 2e-5    # float32 against float32: rounding order only


@pytest.fixture(scope="module")
def built():
    model = family.model(CONFIG)
    params = family.make_weights(model, 11)
    return model, params, family.reference_weights(CONFIG, params)


def _reference_logits(weights, tokens):
    # eager on purpose: the benchmark's plain reference, run as the
    # benchmark runs it (a hundred small programs for the first length)
    return np.asarray(family.reference_forward(CONFIG, weights,
                                               jnp.asarray(tokens)))


def _tokens(seed, batch, seq):
    return np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], (batch, seq)).astype(np.int32)


def test_the_pattern_is_one_period_and_the_head_size_is_its_own(built):
    model, params, _ = built
    c = model.config
    assert len(c.period) == 4 and c.n_layers == 8
    assert [s.window for s in c.period] == [0, 8, 8, 8]
    assert [s.rope for s in c.period] == [False, True, True, True]
    assert all(s.ffn == "experts" for s in c.period)
    assert c.head_dim == 32 and c.attn_dim == 128 != c.d_model
    assert params["layer0/attn/wq"].shape == (64, 128)
    assert params["layer0/attn/wo"].shape == (128, 64)
    assert params["layer3/moe/w3"].shape == (8, 64, 48)


@pytest.mark.parametrize("blockwise_from", [2048, 16],
                         ids=["dense", "blockwise"])
def test_full_forward_agrees_with_the_reference(built, blockwise_from,
                                                monkeypatch):
    model, params, weights = built
    model = tr.Transformer(model.config)
    monkeypatch.setattr(tr.Transformer, "BLOCKWISE_FROM", blockwise_from)
    tokens = _tokens(0, 2, 40)
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    np.testing.assert_allclose(got, _reference_logits(weights, tokens),
                               atol=TOLERANCE)


def test_scanning_whole_periods_agrees_with_the_unrolled_layers(built):
    model, params, weights = built
    scanned = family.model(CONFIG, scan_layers=True)
    shapes = scanned.param_shapes()
    assert shapes["blocks/moe/w1"] == (8, 8, 64, 48)
    stacked = tr.stack_layers(params, 8)
    tokens = _tokens(1, 2, 24)
    np.testing.assert_allclose(
        np.asarray(jax.jit(scanned.apply)(stacked, tokens)),
        _reference_logits(weights, tokens), atol=TOLERANCE)
    # and the cached decode reads a layer out of the stacks
    logits, cache = jax.jit(
        lambda p, t: generation.prefill(scanned, p, t, 32))(stacked,
                                                           tokens[:, :20])
    step, _ = jax.jit(lambda p, t, c: generation.decode_step(
        scanned, p, t, c))(stacked, tokens[:, 20], cache)
    np.testing.assert_allclose(
        np.asarray(step), _reference_logits(weights, tokens[:, :21])[:, 20],
        atol=TOLERANCE)


def test_scan_needs_whole_periods():
    config = family.transformer_config(CONFIG, n_layers=6, scan_layers=True)
    with pytest.raises(ValueError, match="whole periods"):
        tr.Transformer(config)


def test_decode_through_the_cache_past_a_wrapped_ring(built):
    model, params, weights = built
    tokens = _tokens(2, 2, 40)
    want = _reference_logits(weights, tokens)
    logits, cache = jax.jit(
        lambda p, t: generation.prefill(model, p, t, 64))(params,
                                                         tokens[:, :12])
    # a part per layer: 2 full layers hold 64 positions, 6 window layers
    # 8, and both K/V heads of 32 share a row
    assert [part.shape for part in cache.k] == [(2, 64, 1, 64)] * 2
    assert [part.shape for part in cache.wk] == [(2, 8, 1, 64)] * 6
    assert cache.ring_layers == (1, 2, 3, 5, 6, 7)
    np.testing.assert_allclose(np.asarray(logits), want[:, 11],
                               atol=TOLERANCE)
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for at in range(12, 40):      # the rings wrap at 16, 24, 32
        logits, cache = step(params, tokens[:, at], cache)
        np.testing.assert_allclose(np.asarray(logits), want[:, at],
                                   atol=TOLERANCE)


def test_a_block_against_a_ring_writes_only_its_real_positions(built):
    """Ragged rows, blocks of 3 with pad positions: what a pad position
    would overwrite in a ring is still needed, so it is not written."""
    model, params, weights = built
    tokens = _tokens(3, 2, 30)
    want = _reference_logits(weights, tokens)
    _, cache = jax.jit(
        lambda p, t: generation.prefill(model, p, t, 64))(params,
                                                         tokens[:, :10])
    lengths = jnp.asarray([10, 10], jnp.int32)
    block = jax.jit(lambda p, t, c, n, k: generation.decode_block(
        model, p, t, c, lengths=n, counts=k))
    at = np.array([10, 10])
    for counts in ([3, 1], [2, 3], [3, 3], [1, 2], [3, 3], [3, 3]):
        rows = np.stack([np.pad(tokens[b, at[b]:at[b] + n], (0, 3 - n))
                         for b, n in enumerate(counts)])
        logits, cache = block(params, rows, cache, jnp.asarray(at, jnp.int32),
                              jnp.asarray(counts, jnp.int32))
        for b, n in enumerate(counts):
            np.testing.assert_allclose(
                np.asarray(logits)[b, :n], want[b, at[b]:at[b] + n],
                atol=TOLERANCE)
        at = at + np.asarray(counts)


def test_a_block_longer_than_the_ring_is_refused(built):
    model, params, _ = built
    cache = generation.init_cache(model, 1, 64)
    with pytest.raises(ValueError, match="does not go through a ring"):
        generation.decode_block(model, params, jnp.zeros((1, 9), jnp.int32),
                                cache)


@pytest.mark.parametrize("blockwise_queries", [128, 4],
                         ids=["dense", "blockwise"])
def test_the_server_reuses_a_prefix_longer_than_the_window(
        built, blockwise_queries, monkeypatch):
    """A shared prefix of 37 tokens (window 8) is prefilled once; a user
    turn then extends it from the prefix tree (the window taken as a mask
    against the row stored by position), the slot takes the last ring's
    worth, and the decode rounds wrap the rings again: every token is the
    reference's argmax over the whole sequence."""
    model, params, weights = built
    model = tr.Transformer(model.config)
    monkeypatch.setattr(tr.Transformer, "BLOCKWISE_FROM", 32)
    monkeypatch.setattr(generation, "_BLOCKWISE_QUERIES", blockwise_queries)
    rng = np.random.default_rng(4)
    prefix = rng.integers(0, 512, 37).astype(np.int32)
    server = DecodeServer(model, params, slots=3, max_len=96,
                          prompt_cache=8, prefix_cache_bytes=1 << 24)
    server.submit(prefix, max_new_tokens=1)
    server.run_to_completion()
    turns = [rng.integers(0, 512, n).astype(np.int32) for n in (5, 9, 17)]
    ids = [server.submit(np.concatenate([prefix, turn]), max_new_tokens=14)
           for turn in turns]
    served = server.run_to_completion()
    stats = server.stats
    assert stats["prefix_hits"] == 3
    assert stats["prefill_tokens"] == 37 + 5 + 9 + 17
    # bytes by kind: 2 full layers x 96 positions, 6 window layers x 8
    position = 2 * 2 * 32 * 4
    assert stats["cache_full_bytes"] == 3 * 2 * 96 * position
    assert stats["cache_window_bytes"] == 3 * 6 * 8 * position
    assert stats["moe_assignments"] > 0
    for rid, turn in zip(ids, turns):
        sequence = np.concatenate([prefix, turn, served[rid]])[None]
        logits = _reference_logits(weights, sequence)[0]
        rows = logits[37 + len(turn) - 1:-1]
        np.testing.assert_array_equal(np.argmax(rows, axis=-1), served[rid])


def test_speculative_serving_refuses_a_model_with_rings(built):
    model, params, _ = built
    with pytest.raises(ValueError, match="cannot be rolled back"):
        DecodeServer(model, params, slots=2, max_len=64, draft=model,
                     draft_params=params)


def test_dropless_under_total_imbalance(built, monkeypatch):
    """A router rigged so that every token picks the same 3 experts (the
    same logits whatever the input, in the program and in the reference):
    the capacity path would drop most of them, the dropless path none."""
    model, params, weights = built
    tokens = _tokens(5, 2, 24)

    def rigged(x):
        logits = jnp.zeros(x.shape[:-1] + (8,), jnp.float32)
        return logits.at[..., jnp.asarray([1, 4, 6])].set(
            jnp.asarray([3.0, 2.0, 1.0]))

    model = tr.Transformer(model.config)
    model.router_logits = lambda params, prefix, x: rigged(x)
    routed: list = []
    h, _, _ = model._forward(params, tokens, collect_kv=False,
                             route_stats=routed)
    got = np.asarray(model.final_logits(params, h))
    assert len(routed) == 8
    for loads in routed:
        assert np.asarray(loads).tolist() == [0, 48, 0, 0, 48, 0, 48, 0]
    # the capacity layer, sized as in training, would keep this many of
    # the 48 rows of each chosen expert
    capacity = moe.MoELayer(moe.MoEConfig(num_experts=8, top_k=3)
                            ).capacity(48 * 3)
    assert capacity < 48
    experts = reference._experts
    monkeypatch.setattr(
        reference, "_experts",
        lambda h, router_logits, w, top_k: experts(h, rigged(h), w, top_k))
    np.testing.assert_allclose(got, _reference_logits(weights, tokens),
                               atol=TOLERANCE)


def test_dropless_experts_builds_nothing_of_size_tokens_by_experts_by_capacity():
    """The jaxpr of the routed layer holds no array with as many elements
    as tokens x experts x capacity (capacity = tokens when dropless)."""
    n, d, e, f, k = 96, 16, 8, 24, 2
    rng = np.random.default_rng(6)
    args = (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, e)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda *a: moe.dropless_experts(
        *a, top_k=k, act="reglu"))(*args)
    largest = max(int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                  for v in eqn.outvars)
    assert largest < n * e * n
    assert largest <= max(n * k * max(d, f), e * d * f)
    out, loads = moe.dropless_experts(*args, top_k=k, act="reglu")
    assert int(loads.sum()) == n * k
    # against the plain sum over every token's chosen experts
    x, logits, w1, w2, w3 = (np.asarray(a) for a in args)
    want = np.zeros((n, d), np.float32)
    for t in range(n):
        chosen = np.argsort(-logits[t], kind="stable")[:k]
        gates = np.exp(logits[t, chosen] - logits[t, chosen].max())
        gates /= gates.sum()
        for g, ex in zip(gates, chosen):
            want[t] += g * (np.maximum(x[t] @ w1[ex], 0) * (x[t] @ w3[ex])
                            ) @ w2[ex]
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("held", [None, (2, 3)])
def test_past_the_sort_limit_the_rows_are_ordered_by_counting(held,
                                                              monkeypatch):
    """More assignments than the TPU compiler sorts quickly (16,384: a
    4,096-token prefill at top-8 is 32,768) are put in order by counting:
    the same permutation, so the same rows, loads and sum, with and
    without a held share."""
    n, d, e, f, k = 96, 16, 8, 24, 2
    rng = np.random.default_rng(16)
    keys = jnp.asarray(rng.integers(0, 5, 400), jnp.int32)
    order, place = moe._sorted_by_group(keys, 5)
    monkeypatch.setattr(moe, "_SORT_LIMIT", 64)
    counted = moe._sorted_by_group(keys, 5)
    assert np.array_equal(counted[0], order)
    assert np.array_equal(counted[1], place)
    assert np.array_equal(np.asarray(order),
                          np.argsort(np.asarray(keys), kind="stable"))
    count = e if held is None else held[1]
    args = (jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, e)), jnp.float32),
            jnp.asarray(rng.normal(size=(count, d, f)), jnp.float32),
            jnp.asarray(rng.normal(size=(count, f, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(count, d, f)), jnp.float32))
    got = moe.dropless_experts(*args, top_k=k, act="swiglu", held=held)
    monkeypatch.setattr(moe, "_SORT_LIMIT", 16384)
    want = moe.dropless_experts(*args, top_k=k, act="swiglu", held=held)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert float(jnp.max(jnp.abs(want[0]))) > 0.1


def test_a_nope_layer_ignores_positions_and_a_rotary_layer_sees_distances(
        built):
    model, params, _ = built
    c = model.config
    h = jnp.asarray(np.random.default_rng(7).normal(size=(1, 12, 64)),
                    jnp.float32)
    at = jnp.arange(12, dtype=jnp.int32)[None]
    nope, rotary = c.layer_spec(0), c.layer_spec(1)

    def scores(spec, layer, positions):
        q, k, _ = model.qkv(params, f"layer{layer}", h, positions, spec)
        return np.asarray(jnp.einsum("bqhd,bkhd->bhqk", q,
                                     tr.repeat_kv(k, c.kv_groups)))

    # no position signal: q and k do not change with the positions at all
    np.testing.assert_array_equal(scores(nope, 0, at),
                                  scores(nope, 0, at + 1000))
    # rotary: q and k change, their products depend on distances only
    q0, _, _ = model.qkv(params, "layer1", h, at, rotary)
    q1, _, _ = model.qkv(params, "layer1", h, at + 1000, rotary)
    assert not np.allclose(np.asarray(q0), np.asarray(q1), atol=1e-3)
    np.testing.assert_allclose(scores(rotary, 1, at),
                               scores(rotary, 1, at + 1000), atol=2e-3)
    # and a layer's kind, not its weights, decides: the same layer read as
    # the other kind gives the other behaviour
    assert not np.allclose(scores(rotary, 0, at), scores(nope, 0, at),
                           atol=1e-3)


# (window, start, queries, keys, batch, query heads, K/V heads, head size):
# an extension of a cached prefix under every window, then whole sequences
# without one (grouped heads three ways; a length the blocks do not divide)
BLOCKWISE_CASES = [
    (window, start, t, m, batch, 4, 2, 8)
    for batch in (1, 2) for window in (0, 5, 16)
    for start, t, m in ((0, 24, 24), (9, 7, 16), (13, 11, 29))
] + [(0, 0, 64, 64, 2, heads, kv, 16)
     for heads, kv in ((4, 4), (8, 2), (4, 1))
] + [(0, 0, 30, 30, 2, 4, 2, 8)]


@pytest.mark.parametrize("window,start,t,m,batch,heads,kv,d", BLOCKWISE_CASES)
def test_blockwise_attention_is_the_masked_dense_product(window, start, t, m,
                                                         batch, heads, kv, d):
    """Blocks of 4 queries by 4 keys, an M that does not divide, a start
    inside the keys (one row: the scan is as short as the window allows;
    two rows at different starts: it covers every block): the dense
    product under the same mask."""
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(batch, t, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(batch, m, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(batch, m, kv, d)), jnp.float32)
    starts = jnp.asarray([start, max(0, start - 3)][:batch], jnp.int32)
    got = blockwise_attention(q, k, v, starts, window=window, block_q=4,
                              block_k=4)
    kk, vv = (np.repeat(np.asarray(a), heads // kv, axis=2) for a in (k, v))
    scores = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kk) / np.sqrt(d)
    at = np.asarray(starts)[:, None] + np.arange(t)[None]        # [B, T]
    keys = np.arange(m)[None, None]
    seen = keys <= at[:, :, None]
    if window:
        seen &= at[:, :, None] - keys < window
    scores = np.where(seen[:, None], scores, -np.inf)
    scores = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd",
                     scores / scores.sum(-1, keepdims=True), vv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    if not window and t == m:
        # a whole sequence: the einsum reference itself
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(tr.causal_attention(q, k, v)),
            atol=2e-5)


@pytest.mark.parametrize("window,seq,heads,kv,block", [
    (6, 16, 2, 2, 4), (0, 32, 8, 2, 8)], ids=["window", "whole-grouped"])
def test_blockwise_attention_differentiates(window, seq, heads, kv, block):
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, seq, heads, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, seq, kv, 8)), jnp.float32)
            for _ in range(2))
    starts = jnp.zeros((1,), jnp.int32)

    def dense(q, k, v):
        return jnp.sum(tr.causal_attention(q, k, v, window=window) ** 2)

    def blocked(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, starts, window=window,
                                           block_q=block,
                                           block_k=block) ** 2)

    for a, b in zip(jax.grad(dense, (0, 1, 2))(q, k, v),
                    jax.grad(blocked, (0, 1, 2))(q, k, v)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_a_callers_attention_is_refused_where_a_window_binds(built):
    model, params, _ = built
    other = tr.Transformer(model.config, attention_fn=tr.causal_attention)
    with pytest.raises(ValueError, match="window of 8 binds"):
        other.apply(params, _tokens(10, 1, 16))
    # where it does not bind (a sequence inside the window) it runs
    other.apply(params, _tokens(10, 1, 8))


def test_long_rows_are_bucketed_finely():
    assert [_bucket(n) for n in (1, 16, 17, 1000, 2048)] == [
        16, 16, 32, 1024, 2048]
    assert [_bucket(n) for n in (2049, 6144, 12288, 12289)] == [
        4096, 6144, 12288, 14336]


def test_gpt2_is_the_patterns_simplest_member():
    """Learned positions, one full-attention MLP layer as the whole
    period, and a cache of one part a layer, all by position."""
    config = tr.TransformerConfig(
        vocab=128, d_model=32, n_heads=4, n_layers=3, d_ff=64, max_seq=64,
        dtype=jnp.float32, pos_emb="learned", norm="layernorm", bias=True)
    assert config.period == (tr.LayerSpec(),) and config.head_dim == 8
    model = tr.Transformer(config)
    cache = generation.init_cache(model, batch=2, max_len=16)
    assert [part.shape for part in cache.k] == [(2, 16, 1, 32)] * 3
    assert cache.wk == () and cache.ring_layers == ()
    assert cache.nbytes_by_kind() == {"full": 2 * 3 * cache.k[0].nbytes,
                                      "window": 0, "state": 0, "latent": 0}
    assert jax.tree.structure(cache).num_leaves == 2 * 3 + 1
    # moe_every still says where the capacity-dropping layers are
    moe_lm = tr.TransformerConfig(n_layers=4, moe_every=2)
    assert [moe_lm.layer_spec(i).ffn for i in range(4)] == [
        "mlp", "moe", "mlp", "moe"]
    with pytest.raises(ValueError, match="not both"):
        tr.TransformerConfig(moe_every=2, pattern=(tr.LayerSpec(),))


# ---------------------------------------------------------------- the cache
# Two models over the one cache: full layers only at head size 64 (GPT-2's
# shape: two heads share a row of the 128 lanes) and one period of full +
# window layers at head size 128 (SmallThinker's: a head is a row).
WIDE_HEADS = dict(CONFIG, head_dim=128, num_attention_heads=2,
                  num_key_value_heads=1, num_hidden_layers=4,
                  rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1])


@pytest.fixture(scope="module", params=["full_hd64", "pattern_hd128"])
def cached(request):
    if request.param == "full_hd64":
        model = tr.Transformer(tr.TransformerConfig(
            vocab=512, d_model=128, n_heads=2, n_layers=3, d_ff=64,
            max_seq=64, dtype=jnp.float32, pos_emb="learned",
            norm="layernorm", bias=True))
        return model, model.init_params(5)
    model = family.model(WIDE_HEADS)
    return model, family.make_weights(model, 5)


def _cache_parts(cache):
    return cache.k + cache.v + cache.wk + cache.wv


def test_a_row_of_the_cache_is_as_many_heads_as_fit_the_lanes(cached):
    model, _ = cached
    c = model.config
    cache = generation.init_cache(model, 3, 32)
    pack = generation.heads_per_row(c.kv_heads, c.head_dim)
    assert pack == 128 // c.head_dim
    rings = generation.ring_layers_of(model, 32)
    assert len(cache.k) == len(cache.v) == c.n_layers - len(rings)
    assert len(cache.wk) == len(cache.wv) == len(rings)
    assert {part.shape for part in cache.k + cache.v} == {
        (3, 32, c.kv_heads // pack, 128)}
    assert {part.shape for part in cache.wk + cache.wv} <= {
        (3, 8, c.kv_heads // pack, 128)}
    kinds = cache.nbytes_by_kind()
    assert kinds["full"] == 2 * len(cache.k) * 3 * 32 * c.kv_heads \
        * c.head_dim * 4
    assert kinds["window"] == 2 * len(rings) * 3 * 8 * c.kv_heads \
        * c.head_dim * 4


def _big_operations(text, at_least):
    """(operation, name, elements) of the ENTRY computation's copies and
    slices whose result holds ``at_least`` elements or more."""
    import re

    found = []
    for line in text.split("\nENTRY")[1].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\][^ ]* "
                     r"(copy|slice|dynamic-slice|transpose)\(", line)
        if m:
            size = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
            if size >= at_least:
                found.append((m.group(3), m.group(1), size))
    return found


@pytest.mark.parametrize("program", ["step", "splice"])
def test_the_compiled_round_updates_every_part_where_it_lies(cached,
                                                             program):
    """The decode round (and an admission's splice) take the cache
    donated: every part is aliased from argument to result, and no
    operation copies or slices out something as large as a layer stored
    by position.  (XLA's CPU backend copies a RING that is read and then
    written; the chip's compiler does not: tests/test_chip_compile.py
    holds every part, rings too, in the program compiled for the chip.)"""
    import re

    from parameter_server_distributed_tpu.models import serving

    model, params = cached
    slots, max_len = 3, 32
    cache = generation.init_cache(model, slots, max_len)
    parts = _cache_parts(cache)
    if program == "step":
        lowered = serving._step_runner(model, slots, 0, 0.0, "native").lower(
            params, jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots,), jnp.int32), cache,
            jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.float32),
            jax.random.key(0))
    else:
        _, row, _ = serving._prefill_runner(model, 16, "native")(
            params, jnp.zeros((1, 16), jnp.int32), jnp.asarray(9, jnp.int32))
        lowered = serving._splice_runner(model, 16, "native").lower(
            cache, row, jnp.asarray(1, jnp.int32), jnp.asarray(9, jnp.int32))
    text = lowered.compile().as_text()
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry",
                        text.splitlines()[0]).group(1)
    assert aliased.count("alias)") >= len(parts)
    assert _big_operations(text, cache.k[0].size) == []


def test_the_check_for_big_operations_sees_a_layer_sliced_out():
    """The check itself, on lines as the compiler writes them: a layer
    sliced out of a cache stacked over layers is found, small operations
    and those inside a fusion are not."""
    text = """HloModule jit_run
%fused_computation (p: f32[2,4,16,8]) -> f32[1,4,16,8] {
  %slice.9 = f32[1,4,16,8]{3,2,1,0} slice(%p), slice={[1:2], [0:4], [0:16], [0:8]}
}

ENTRY %main.1 (cache: f32[2,4,16,8]) -> f32[4,16] {
  %slice.1 = f32[1,4,16,8]{3,2,1,0} slice(%cache), slice={[1:2], [0:4], [0:16], [0:8]}
  %copy.2 = f32[4,8]{1,0} copy(%q)
  ROOT %copy.3 = f32[2,4,16,8]{3,2,1,0:T(8,128)} copy(%fusion.1)
}"""
    assert _big_operations(text, 4 * 16 * 8) == [
        ("slice", "slice.1", 512), ("copy", "copy.3", 1024)]


def test_rows_read_back_are_the_layers_own_by_position(cached):
    """Prefill -> splice -> ragged decode rounds, then every part read
    back against K and V of the plain forward over the whole sequence: a
    by-position part holds position j at index j (the overshooting writes
    of a row already at the cache's end are dropped, not clamped onto its
    last position), a ring the last window's worth at p % W."""
    from parameter_server_distributed_tpu.models import serving

    model, params = cached
    c = model.config
    slots, max_len, rounds = 3, 32, 6
    prompt = np.array([5, 12, 30])          # row 2 runs past the end
    tokens = _tokens(21, slots, max_len + rounds)
    pack = generation.heads_per_row(c.kv_heads, c.head_dim)
    cache = generation.init_cache(model, slots, max_len)
    for slot, n in enumerate(prompt):
        bucket = 16 if n <= 16 else 32
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = tokens[slot, :n]
        _, row, _ = serving._prefill_runner(model, bucket, "native")(
            params, jnp.asarray(padded), jnp.asarray(n, jnp.int32))
        assert row[0].shape == (c.n_layers, bucket, c.kv_heads // pack,
                                pack * c.head_dim)
        cache = serving._splice_runner(model, bucket, "native")(
            cache, row, jnp.asarray(slot, jnp.int32),
            jnp.asarray(n, jnp.int32))
    step = jax.jit(lambda p, t, cache, n: generation.decode_block(
        model, p, t, cache, lengths=n), donate_argnums=(2,))
    lengths = prompt.copy()
    for _ in range(rounds):
        fed = tokens[np.arange(slots), lengths][:, None]
        _, cache = step(params, jnp.asarray(fed), cache,
                        jnp.asarray(lengths, jnp.int32))
        lengths += 1
    _, kvs = model.apply_collect_kv(params, jnp.asarray(tokens))
    for layer, (k, v) in enumerate(kvs):      # each [B, S, KV, D]
        ring, i = cache.place(layer)
        for want, part in ((k, (cache.wk if ring else cache.k)[i]),
                           (v, (cache.wv if ring else cache.v)[i])):
            want = np.asarray(generation.pack_heads(want, pack))
            part = np.asarray(part)
            for slot, n in enumerate(lengths):
                if ring and n <= max_len:
                    window = part.shape[1]
                    held = [p for p in range(n - window, n) if p >= 0]
                    np.testing.assert_allclose(
                        part[slot, [p % window for p in held]],
                        want[slot, held], atol=TOLERANCE)
                elif not ring:
                    upto = min(n, max_len)
                    np.testing.assert_allclose(
                        part[slot, :upto], want[slot, :upto],
                        atol=TOLERANCE)


# ------------------------------------------------- the table of layer kinds
# What the functions that READ a kind (state_shape, block_shapes,
# flops_per_sample, _builds_few, _prefills_whole) answered for a tiny model
# of each at PR 58's commit, before the kinds stood in one table
# (models/mixers.py): a record that says something else than the chains did
# fails here.  ``block``: suffix:shape in the store's order.
PINNED = {
    "softmax": {
        "state": [],
        "block": ("ln1/scale:32 attn/wq:32x32 attn/wk:32x32 attn/wv:32x32 "
                  "attn/wo:32x32 ln2/scale:32 mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 10285056.0, "few": False,
        "whole": [True, True, True, False, True, True]},
    "sparse": {
        "state": [],
        "block": ("ln1/scale:32 attn/wq:32x32 attn/wk:32x32 attn/wv:32x32 "
                  "attn/wo:32x32 ln2/scale:32 mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 10285056.0, "few": False,
        "whole": [True, True, True, False, True, True]},
    "linear": {
        "state": [[((4, 8, 8), "float32")]] * 2,
        "block": ("ln1/scale:32 attn/wq:32x32 attn/wk:32x32 attn/wv:32x32 "
                  "attn/wo:32x32 ln2/scale:32 mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 10285056.0, "few": False,
        "whole": [True, True, True, False, True, True]},
    "conv": {
        "state": [[((2, 32), "float32")]] * 2,
        "block": ("ln1/scale:32 conv/in_proj:32x96 conv/kernel:3x32 "
                  "conv/out_proj:32x32 ln2/scale:32 mlp/w1:32x48 "
                  "mlp/w2:48x32"),
        "flops": 10358784.0, "few": False,
        "whole": [True, True, True, False, True, True]},
    "kda": {
        "state": [[((3, 96), "float32"), ((4, 8, 8), "float32")]] * 2,
        "block": ("ln1/scale:32 attn/wq:32x32 attn/wk:32x32 attn/wv:32x32 "
                  "attn/conv_q:4x32 attn/conv_k:4x32 attn/conv_v:4x32 "
                  "attn/decay/wa:32x8 attn/decay/wb:8x32 attn/decay/a_log:4 "
                  "attn/decay/dt_bias:32 attn/gate/wa:32x8 "
                  "attn/gate/wb:8x32 attn/beta/w:32x4 attn/o_norm/scale:8 "
                  "attn/wo:32x32 ln2/scale:32 mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 8942592.0, "few": True,
        "whole": [True, False, False, False, True, False]},
    "latent": {
        "state": [],
        "block": ("ln1/scale:32 attn/wq_a:32x12 attn/q_norm/scale:12 "
                  "attn/wq_b:12x64 attn/wkv_a:32x32 attn/kv_norm/scale:24 "
                  "attn/wkv_b:24x64 attn/wo:32x32 ln2/scale:32 mlp/w1:32x48 "
                  "mlp/w2:48x32"),
        "flops": 12377088.0, "few": True,
        "whole": [True, False, False, False, True, True]},
    "gdn": {
        "state": [[((3, 96), "float32"), ((4, 6, 12), "float32")]] * 2,
        "block": ("ln1/scale:32 attn/wq:32x24 attn/wk:32x24 attn/wv:32x48 "
                  "attn/conv_q:4x24 attn/conv_k:4x24 attn/conv_v:4x48 "
                  "attn/decay/w:32x4 attn/decay/a_log:4 "
                  "attn/decay/dt_bias:4 attn/beta/w:32x4 attn/wz:32x48 "
                  "attn/o_norm/scale:12 attn/wo:48x32 ln2/scale:32 "
                  "mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 9882624.0, "few": True,
        "whole": [True, False, False, False, True, False]},
    "ssm": {
        "state": [[((3, 112), "float32"), ((6, 8, 16), "float32")]] * 2,
        "block": ("ln1/scale:32 ssm/in_proj:32x166 ssm/conv/kernel:4x112 "
                  "ssm/conv/bias:112 ssm/decay/a_log:6 ssm/decay/dt_bias:6 "
                  "ssm/skip:6 ssm/norm/scale:48 ssm/out_proj:48x32 "
                  "ln2/scale:32 mlp/w1:32x48 mlp/w2:48x32"),
        "flops": 10913280.0, "few": True,
        "whole": [True, False, False, False, True, False]},
}

# what each kind's tiny model needs beside the common sizes, and the ONE
# size that makes its branch the widest thing a token meets (98,304 to
# 102,064 channels: a prompt of 1,024 is then forwarded whole and one of
# 2,048 is not)
KIND_FIELDS = {
    "sparse": dict(sparse=SparseSpec(kernel=8, stride=4, block=8, window=16,
                                     topk=2, dense_len=32)),
    "conv": dict(conv_kernel=3), "kda": dict(conv_kernel=4),
    "gdn": dict(conv_kernel=4, delta_key_dim=6, delta_value_dim=12,
                delta_neg_eigval=True),
    "ssm": dict(conv_kernel=4, ssm_heads=6, ssm_head_dim=8, ssm_state=16,
                ssm_groups=2),
    "latent": dict(kv_latent=24, qk_shared=8, q_latent=12)}
WIDE = {"ssm": dict(ssm_heads=6000)}


def _model_of(kind, **changes):
    fields = {"d_model": 32, "n_heads": 4, "head_dim": 8, "d_ff": 48,
              **KIND_FIELDS.get(kind, {}), **changes}
    return tr.Transformer(tr.TransformerConfig(
        vocab=64, n_layers=2, max_seq=64, dtype=jnp.float32,
        pattern=(tr.LayerSpec(mixer=kind),), **fields))


def _answers(kind):
    """What the functions that read a kind answer for its tiny model (and
    for one whose branch is the widest thing a token meets)."""
    model = _model_of(kind)
    wide = _model_of(kind, **WIDE.get(kind, dict(n_heads=4096)))
    return {
        "state": [[(shape, jnp.dtype(dtype).name) for shape, dtype in layer]
                  for layer in generation.state_shape(model)],
        "block": " ".join(
            f"{suffix}:{'x'.join(map(str, shape))}" for suffix, shape
            in model.block_shapes(model.config.pattern[0]).items()),
        "flops": model.flops_per_sample(),
        "few": serving._builds_few(model),
        # (tiny at 2,048, 4,096, 2**21 and 2**22; wide at 1,024 and 2,048)
        "whole": [serving._prefills_whole(m, bucket) for m, buckets in (
            (model, (2048, 4096, 1 << 21, 1 << 22)), (wide, (1024, 2048)))
            for bucket in buckets]}


def test_the_names_are_read_off_the_table():
    assert tr.MIXER_KINDS == tuple(mixers.MIXERS) == tuple(PINNED)
    assert tr.STATE_MIXERS == ("linear", "conv", "kda", "gdn", "ssm")
    assert tr.RECURRENT_MIXERS == ("kda", "gdn", "ssm")


@pytest.mark.parametrize("kind", tr.MIXER_KINDS)
def test_a_kinds_record_is_whole_and_answers_as_the_chains_did(kind):
    mixer = mixers.MIXERS[kind]
    model = _model_of(kind)
    c = model.config
    # a whole record: every field says something the readers can use
    assert mixer.keeps in ("kv", "state", "latent")
    assert (mixer.state is not None) == (mixer.keeps == "state")
    assert (kind in tr.STATE_MIXERS) == (mixer.keeps == "state")
    states = mixer.state(c) if mixer.state else ()
    assert mixer.matrix == any(len(shape) == 3 and dtype == jnp.float32
                               for shape, dtype in states)
    assert mixer.recurrent == (len(states) == 2)
    assert mixer.residual is None or callable(getattr(model, mixer.residual))
    assert mixer.products(c, c.max_seq) > 0 and mixer.widest(c) >= 0
    assert set(mixer.shapes(c, c.pattern[0])) >= {"ln1/scale", "ln2/scale"}
    if mixer.round_kernel is not None:
        module = importlib.import_module(
            "parameter_server_distributed_tpu.ops.pallas."
            + mixer.round_kernel.module)
        assert callable(module.fits)
        assert tr.round_arm(kind, (2, 1, 4, 8), (2, 64, 4, 8)) \
            == mixer.round_kernel.plain                      # no TPU
        # (the sentence a refusal is made of names what the module has)
        assert "{" not in mixer.round_kernel.refusal.format(kernel=module)
    assert (c.layers_keeping(mixer.keeps), c.layers_of(kind)) == ((0, 1),) * 2
    # ... and what reads it answers what it answered before the table
    assert _answers(kind) == PINNED[kind]
