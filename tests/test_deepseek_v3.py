"""DeepSeek-V3's layers through the model, the cache and the server, against
the plain reference (``perfbench/reference/deepseek_v3.py``), at a small
size in float32 on the CPU, LOGITS and not tokens: latent attention in every
layer with a low-rank query (a norm inside the pair), rotary on the shared
parts under YaRN (the cached row is kept ROTATED, at its absolute
position), absorbed in a round and expanded a key block at a time in an
extension, over one chip's share of experts chosen under a group limit,
beside a shared one.
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from parameter_server_distributed_tpu.models import (  # noqa: E402
    generation, moe, serving)
from parameter_server_distributed_tpu.models.transformer import (  # noqa: E402
    LayerSpec, RopeScaling, Transformer, TransformerConfig, rope,
    transformer_rule)
from parameter_server_distributed_tpu.ops.blockwise_attention import (  # noqa: E402
    blockwise_attention)
from perfbench import correct  # noqa: E402
from perfbench.families import deepseek_v3, kimi_linear  # noqa: E402
from perfbench.reference import deepseek_v3 as reference  # noqa: E402

SEQ = 72
CLOSE = 5e-5    # float32 logits of the program against the reference's
FILE = os.path.join(ROOT, "perfbench", "configs",
                    "deepseek-v3-5l-ep16.json")
PUBLISHED = dict(theta=10000.0, factor=40.0, original_max=4096,
                 beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)


def _published() -> dict:
    with open(FILE) as handle:
        return json.load(handle)


def _configuration(**changes) -> dict:
    config = deepseek_v3.tiny(_published())
    config.update(changes)
    return config


def _small(**changes):
    """(configuration, model, weights, the reference's weights)."""
    config = _configuration(**changes)
    model = deepseek_v3.model(config)
    params = deepseek_v3.make_weights(model, 3)
    return config, model, params, deepseek_v3.reference_weights(config,
                                                                params)


@pytest.fixture(scope="module")
def small():
    return _small()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, SEQ)).astype(
        np.int32)


def _expected(small, tokens):
    config, _, _, weights = small
    return np.asarray(jax.jit(lambda w, t: deepseek_v3.reference_forward(
        config, w, t))(weights, tokens))


@pytest.fixture(scope="module")
def expected(small, tokens):
    return _expected(small, tokens)


def _reference_logits(small, sequence):
    return _expected(small, np.asarray(sequence, np.int32)[None])[0]


# --------------------------------------------------------------- the model
def test_the_model_is_latent_attention_in_every_layer(small):
    config, model, params, _ = small
    c = model.config
    assert [spec.mixer for spec in c.prologue + c.pattern] == ["latent"] * 2
    assert [c.layer_spec(i).ffn for i in range(3)] == ["mlp", "experts",
                                                       "experts"]
    assert (c.q_latent, c.kv_latent, c.qk_shared, c.latent_rope) == (
        24, 32, 8, True)
    assert (c.moe_groups, c.moe_groups_kept, c.moe_held) == (4, 2, (2, 2))
    assert params["layer0/attn/wq_a"].shape == (64, 24)
    assert params["layer0/attn/q_norm/scale"].shape == (24,)
    assert params["layer0/attn/wq_b"].shape == (24, 4 * 24)
    assert "layer0/attn/wq" not in params
    assert params["layer1/moe/w1"].shape == (2, 64, 32)
    assert model.num_params() == deepseek_v3.param_count(config)
    # a configuration without the new options builds Kimi Linear's leaves
    plain = Transformer(dataclasses.replace(
        c, q_latent=0, latent_rope=False, rope_scaling=None))
    assert plain.block_shapes(c.pattern[0])["attn/wq"] == (64, 4 * 24)


def test_the_published_cut_counts_its_parameters_and_its_bytes():
    """The bytes table of ISSUE 54 against the program's own store and
    cache."""
    config = _published()
    model = deepseek_v3.model(config)
    attention = 187_107_328
    assert deepseek_v3._attention_params(config) == attention
    assert deepseek_v3.layer_params(config, 0) == 583_483_392
    assert deepseek_v3.layer_params(config, 1) == 937_640_192
    assert model.num_params() == deepseek_v3.param_count(config) \
        == config["parameters"] == 4_565_721_088
    shapes = model.param_shapes()
    assert sum(math.prod(shape) for name, shape in shapes.items()
               if name.startswith("layer3/attn/")) == attention
    cache = jax.eval_shape(lambda: generation.init_cache(model, 32, 16384))
    assert [x.shape for x in cache.latent] == [(32, 16384, 640)] * 5
    held = sum(math.prod(x.shape) * x.dtype.itemsize for x in cache.latent)
    assert held == 32 * deepseek_v3.slot_bytes(config, 16384)["latent"] \
        == 32 * 16384 * 6400 == 3_355_443_200
    assert deepseek_v3.latent_attn_bytes(config, 1.0) == 1152
    assert deepseek_v3.latent_attn_flops(config, 1.0) == 2 * 128 * 1088
    # the router keeps its published width; the held experts are half a group
    assert shapes["layer1/moe/router/w"] == (7168, 256)
    assert model.config.moe_experts // model.config.moe_groups == 2 * 16


def test_forward_against_the_reference(small, tokens, expected):
    _, model, params, _ = small
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    assert np.max(np.abs(got - expected)) < CLOSE
    assert float(np.std(expected)) > 0.5


@pytest.mark.parametrize("prompt", [1, 3, 17, 40])
def test_prefill_then_decode_through_the_cache(small, tokens, expected,
                                               prompt):
    """Every position's logits: the prompt whole, then a token a round
    against the rotated rows (the absorbed form)."""
    _, model, params, _ = small
    logits, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, SEQ))(params, tokens[:, :prompt])
    assert np.max(np.abs(logits - expected[:, prompt - 1])) < CLOSE
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for i in range(prompt, SEQ):
        logits, cache = step(params, tokens[:, i], cache)
        assert np.max(np.abs(logits - expected[:, i])) < CLOSE, i
    kinds = cache.nbytes_by_kind()
    assert kinds["latent"] == 2 * 3 * SEQ * 128 * 4
    assert kinds["state"] == kinds["full"] == kinds["window"] == 0


def test_rounds_through_the_kernel_at_128_heads(monkeypatch):
    """A prefill, then rounds through ops/pallas/latent_decode.py
    (interpreted here) at a head count of 128, against the reference's
    logits: the rule takes the kernel for a round's token at these shapes,
    and the rotated rows it reads are the reference's keys."""
    from parameter_server_distributed_tpu.models import transformer

    config, model, params, weights = small = _small(
        num_attention_heads=128, num_key_value_heads=128,
        qk_nope_head_dim=8, v_head_dim=8, kv_lora_rank=120,
        num_hidden_layers=2, max_position_embeddings=1024)
    assert model.config.latent_row == 128
    tokens = np.random.default_rng(4).integers(0, 512, (2, 18)).astype(
        np.int32)
    expected = _expected(small, tokens)
    logits, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, 1024))(params, tokens[:, :12])
    assert np.max(np.abs(logits - expected[:, 11])) < CLOSE
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert transformer.round_arm("latent", (2, 1, 128, 128),
                                 (2, 1024, 128)) == "kernel"
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for i in range(12, 18):
        logits, cache = step(params, tokens[:, i], cache)
        assert np.max(np.abs(logits - expected[:, i])) < CLOSE, i


# ---------------------------------------------------------- rotary and YaRN
def test_yarns_frequencies_and_scale_are_the_published_numbers():
    scaling = deepseek_v3.rope_scaling(_published())
    assert scaling.ramp_ends(64, 10000.0) == (10, 23)
    got = scaling.frequencies(64, 10000.0)
    pair = np.arange(32)
    plain = 10000.0 ** (-2.0 * pair / 64)
    slowed = np.clip((pair - 10) / 13, 0, 1)
    want = plain * (1 - slowed) + plain / 40 * slowed
    assert got.shape == (32,) and np.allclose(got, want, rtol=1e-6)
    assert np.allclose(got[:11], plain[:11], rtol=1e-6)
    assert np.allclose(got[23:], plain[23:] / 40, rtol=1e-6)
    m = 0.1 * math.log(40) + 1
    assert abs(m - 1.36889) < 1e-5
    assert abs(scaling.softmax_gain - m * m) < 1e-12
    assert scaling.rotary_gain == 1.0
    assert abs(192 ** -0.5 * scaling.softmax_gain - 0.135234) < 1e-6
    assert abs(reference.softmax_scale(192, PUBLISHED) - 0.135234) < 1e-6
    theirs, gain = reference.yarn_frequencies(64, PUBLISHED)
    assert np.allclose(np.asarray(theirs), want, rtol=1e-6) and gain == 1.0
    # no scaling: the rotary every other family has
    assert RopeScaling(1.0, 4096).softmax_gain == 1.0


def test_yarn_against_the_public_description():
    pytest.importorskip("torch")
    utils = pytest.importorskip("transformers.modeling_rope_utils")

    class Config:
        rope_theta = 10000.0
        head_dim = 64
        hidden_size, num_attention_heads = 7168, 128
        max_position_embeddings = 163840
        rope_scaling = {"factor": 40, "beta_fast": 32, "beta_slow": 1,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 4096,
                        "type": "yarn", "rope_type": "yarn"}

    theirs, factor = utils._compute_yarn_parameters(Config(), "cpu")
    ours = deepseek_v3.rope_scaling(_published())
    assert np.allclose(ours.frequencies(64, 10000.0), theirs.numpy(),
                       rtol=1e-6)
    assert abs(factor - ours.rotary_gain) < 1e-12


def test_the_programs_rotation_is_the_references():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(9, 8)), jnp.float32)
    yarn = dict(PUBLISHED, factor=4.0, original_max=32, mscale=0.7)
    scaling = RopeScaling(4.0, 32, mscale=0.7, mscale_all_dim=1.0)
    ours = rope(x[None, :, None, :], jnp.arange(9)[None], 10000.0, scaling)
    assert np.max(np.abs(ours[0, :, 0] - reference.rotate(x, yarn))) < 1e-6
    assert scaling.rotary_gain != 1.0


# ---------------------------------------------------- the two forms, by block
def test_absorbed_decode_is_expansion_by_key_block(small, tokens, expected,
                                                   monkeypatch):
    """One block of 16 tokens against a cached prefix of 40, both ways,
    and both the reference's logits: the rows read as they lie with the
    expansion absorbed, and K and V expanded a key block at a time inside
    the blockwise loop (which a long block against a long cache takes)."""
    _, model, params, _ = small
    _, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, SEQ))(params, tokens[:, :40])

    def block():
        return np.asarray(jax.jit(lambda p, t, c: generation.decode_block(
            model, p, t, c)[0])(params, tokens[:, 40:56], cache))

    absorbed = block()
    monkeypatch.setattr(generation, "_BLOCKWISE_QUERIES", 8)
    monkeypatch.setattr(Transformer, "BLOCKWISE_FROM", 16)
    expanded = block()
    assert np.max(np.abs(absorbed - expanded)) < CLOSE
    assert np.max(np.abs(absorbed - expected[:, 40:56])) < CLOSE


@pytest.mark.parametrize("start,blocks", [(0, 8), (37, 16), (80, 64)])
def test_expansion_by_key_block_is_the_whole_rows_expansion(small, start,
                                                            blocks):
    """``blockwise_attention(expand=...)`` against the same attention over
    K and V expanded whole, blocks that divide the row and blocks that do
    not, a block of queries that starts anywhere."""
    _, model, params, _ = small
    c = model.config
    rng = np.random.default_rng(start)
    rows = jnp.asarray(rng.normal(size=(2, 100, c.latent_row)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 20, c.n_heads,
                                     c.head_dim + c.qk_shared)), jnp.float32)
    starts = jnp.asarray([start, max(start - 3, 0)], jnp.int32)
    k, v = model.latent_expand(params, "layer1", rows)
    whole = blockwise_attention(q, k, v, starts, block_q=8, block_k=blocks)
    by_block = blockwise_attention(
        q, rows, None, starts, block_q=8, block_k=blocks,
        expand=lambda part: model.latent_expand(params, "layer1", part,
                                                wide_values=False))
    assert by_block.shape == (2, 20, c.n_heads, c.head_dim)
    assert np.max(np.abs(by_block - whole[..., :c.head_dim])) < 1e-5
    assert float(jnp.max(jnp.abs(by_block))) > 0.1


# -------------------------------------------------------------- serving
def _server(small, max_len=128, slots=4):
    _, model, params, _ = small
    return serving.DecodeServer(model, params, slots=slots, max_len=max_len,
                                prompt_cache=8, prefix_cache_bytes=1 << 24)


def test_a_restored_row_is_rotated_at_its_absolute_positions(small):
    """A resident document, then the document + a question twice: alone
    (slot 0) and behind two other requests (another slot).  The admission
    restores the three layers' ROTATED rows at the node's end and forwards
    only the question; whatever the slot, every served token is the
    reference's argmax over the uncut sequence and the first token's logits
    are the reference's."""
    rng = np.random.default_rng(5)
    system = rng.integers(0, 512, 50)
    turns = [np.concatenate([system, rng.integers(0, 512, n)])
             for n in (21, 9, 13)]
    srv = _server(small)
    rid = srv.submit(system, max_new_tokens=1)
    srv.run_to_completion()
    rid = srv.submit(turns[0], max_new_tokens=12)
    alone = srv.run_to_completion()[rid]
    assert srv.stats["prefix_hits"] == 1
    assert srv.stats["prefill_tokens"] == 50 + 21
    node, matched, _ = srv._prefix_tree.lookup(tuple(turns[0].tolist()))
    assert matched == 71
    row = node.handle.row
    # no layer keeps K/V; three latent layers' rows in one leaf; no state.
    # A latent model's smallest suffix bucket (256) does not fit this lane
    # of 128 beside the prefix's 64, so the turn takes its own
    assert serving._suffix_floor(small[1]) == 256
    assert row[0].shape == (0, 64 + 32, 1, 1) and len(row) == 3
    assert row[2].shape == (3, 64 + 32, 128)
    logits = _reference_logits(small, np.concatenate([turns[0], alone]))
    assert alone == np.argmax(logits[70:82], -1).tolist()
    assert np.max(np.abs(np.asarray(node.last) - logits[70])) < CLOSE
    # the same question again, now in a slot behind two others
    fresh = np.concatenate([system, turns[0][50:], [7]])
    ids = [srv.submit(turn, max_new_tokens=12)
           for turn in (turns[1], turns[2], fresh)]
    results = srv.run_to_completion()
    for rid, turn in zip(ids, (turns[1], turns[2], fresh)):
        served = results[rid]
        logits = _reference_logits(small, np.concatenate([turn, served]))
        at = len(turn) - 1
        assert served == np.argmax(logits[at:at + 12], -1).tolist()
    assert srv.stats["cache_latent_bytes"] == 4 * 3 * 128 * 128 * 4
    assert "cache_state_bytes" not in srv.stats


def test_every_turn_shares_the_program_built_beside_the_prefill(small):
    """In a lane that holds it, a latent model's suffix bucket is 256
    whatever the turn: ONE extension program a prefix bucket, started on a
    thread of its own when the prefill puts the document into the tree;
    it runs blockwise and expands by key block, and every served token is
    the reference's argmax over the uncut sequence."""
    rng = np.random.default_rng(15)
    system = rng.integers(0, 512, 50)
    _, model, _, _ = small
    assert serving._builds_few(model)
    srv = _server(small, max_len=512)
    srv.submit(system, max_new_tokens=1)
    srv.run_to_completion()
    assert list(srv._ahead) == [(64, 256)]
    for n in (5, 40):
        turn = np.concatenate([system, rng.integers(0, 512, n)])
        rid = srv.submit(turn, max_new_tokens=6)
        served = srv.run_to_completion()[rid]
        logits = _reference_logits(small, np.concatenate([turn, served]))
        at = len(turn) - 1
        assert served == np.argmax(logits[at:at + 6], -1).tolist()
        node, _, _ = srv._prefix_tree.lookup(tuple(turn.tolist()))
        assert node.handle.row[2].shape == (3, 64 + 256, 128)
    assert list(srv._ahead) == [(64, 256)]
    assert srv.stats["prefix_hits"] == 2


def test_a_prompt_prefilled_in_chunks_keeps_rotated_rows(small, monkeypatch):
    """Chunks of 32 positions against the row so far (the path every
    document of 4,096 tokens or more takes at the published widths): a
    chunk's rows are rotated at their absolute positions, not the chunk's
    own."""
    monkeypatch.setattr(serving, "_PREFILL_CHUNK", 32)
    rng = np.random.default_rng(25)
    prompt = rng.integers(0, 512, 75)
    srv = _server(small)
    rid = srv.submit(prompt, max_new_tokens=8)
    served = srv.run_to_completion()[rid]
    logits = _reference_logits(small, np.concatenate([prompt, served]))
    assert served == np.argmax(logits[74:82], -1).tolist()


def test_the_counters_count_rank_places_and_live_rows(small):
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    def counters():
        return dict(obs_stats.REGISTRY.snapshot()["counters"])

    before = counters()
    rng = np.random.default_rng(35)
    srv = _server(small)
    rid = srv.submit(rng.integers(0, 512, 30), max_new_tokens=10)
    srv.run_to_completion()[rid]
    after = counters()

    def moved(name):
        return after.get(name, 0) - before.get(name, 0)

    tokens, places = (moved("serve.moe.tokens_routed"),
                      moved("serve.moe.rank_places"))
    # two expert layers; 3 choices a token; 2 of 4 groups of 2 ranks each
    assert tokens * 3 == moved("serve.moe.assignments_routed")
    assert tokens <= places <= tokens * min(3, 2 * 2)
    assert moved("serve.latent.positions_read") > 0
    assert moved("serve.latent.positions_cached") > moved(
        "serve.latent.positions_read")


# ------------------------------------------------------------- the experts
def _scores(rng, tokens, experts):
    """Router logits whose scores, and whose groups' scores, tie nowhere."""
    return jnp.asarray(rng.permutation(tokens * experts).reshape(
        tokens, experts) / (tokens * experts) * 6 - 3, jnp.float32)


@pytest.mark.parametrize("groups,kept,top_k", [(8, 4, 8), (4, 2, 3),
                                               (8, 1, 4), (2, 2, 5)])
def test_the_group_limit_against_the_reference(groups, kept, top_k):
    rng = np.random.default_rng(groups * 10 + kept)
    experts = 64
    logits = _scores(rng, 50, experts)
    bias = jnp.asarray(rng.normal(size=(experts,)) * 0.01, jnp.float32)
    gates, chosen = moe.select_experts(logits, top_k, "sigmoid", bias, 2.5,
                                       groups, kept)
    w = {"router": jnp.eye(experts), "bias": bias}
    want, _ = reference.gates(logits, w, top_k, 2.5, groups, kept)
    got = jnp.zeros((50, experts)).at[jnp.arange(50)[:, None], chosen].set(
        gates)
    assert np.max(np.abs(got - want)) < 1e-5
    # the chosen lie in at most ``kept`` groups
    size = experts // groups
    assert max(len(set(row)) for row in np.asarray(chosen) // size) <= kept


def test_one_group_is_the_selection_there_was():
    """``groups=1`` takes the path every other family takes, bit for bit:
    PR 53's function, written out."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(32,)) * 0.01, jnp.float32)
    gates, chosen = moe.select_experts(logits, 4, "sigmoid", bias, 2.446)
    scores = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(scores + bias, 4)
    picked = jnp.take_along_axis(scores, top_idx, axis=-1)
    want = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * 2.446
    assert np.array_equal(np.asarray(chosen), np.asarray(top_idx))
    assert np.array_equal(np.asarray(gates), np.asarray(want))
    # kept == groups limits nothing
    same = moe.select_experts(logits, 4, "sigmoid", bias, 2.446, 4, 4)
    assert np.array_equal(np.asarray(same[1]), np.asarray(chosen))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: 32 outputs, sixteen ranks of two
    experts (half a group of four), top-4 under 4 of 8 groups, the shared
    expert counted once: the ranks' parts add up to the uncut reference's
    layer, and a token's choices lie on at most 4 x 2 ranks."""
    rng = np.random.default_rng(8)
    d, width, experts, ranks, tokens = 24, 12, 32, 16, 40
    w = {"x": rng.normal(size=(tokens, d)),
         "router": rng.normal(size=(d, experts)) / np.sqrt(d),
         "bias": rng.normal(size=(experts,)) * 0.005,
         "w1": rng.normal(size=(experts, d, width)) / np.sqrt(d),
         "w3": rng.normal(size=(experts, d, width)) / np.sqrt(d),
         "w2": rng.normal(size=(experts, width, d)) / np.sqrt(width),
         "shared_w1": rng.normal(size=(d, width)) / np.sqrt(d),
         "shared_w3": rng.normal(size=(d, width)) / np.sqrt(d),
         "shared_w2": rng.normal(size=(width, d)) / np.sqrt(width)}
    w = {name: jnp.asarray(value, jnp.float32) for name, value in w.items()}
    count = experts // ranks
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.expert_layer(w["x"], w, 4, 2.5, 8, 4)
        shared = reference._swiglu(w["x"], w["shared_w1"], w["shared_w3"],
                                   w["shared_w2"])
        total, places = 0.0, set()
        for rank in range(ranks):
            first = rank * count
            part, loads = moe.dropless_experts(
                w["x"], w["x"] @ w["router"],
                w["w1"][first:first + count], w["w2"][first:first + count],
                w["w3"][first:first + count], top_k=4, act="swiglu",
                score="sigmoid", bias=w["bias"], scale=2.5,
                held=(first, count), groups=8, groups_kept=4)
            assert loads.shape == (count + 2,)
            assert int(loads[:count + 1].sum()) == tokens * 4
            places.add(int(loads[-1]))
            total = total + part
    assert np.max(np.abs(total + shared - whole)) < 1e-4
    assert float(jnp.max(jnp.abs(whole))) > 0.5
    # every rank counts the same places: the selection is over all experts
    assert len(places) == 1 and tokens <= places.pop() <= tokens * 4
    # and without the limit the parts would NOT be this layer
    unlimited, _ = reference.expert_layer(w["x"], w, 4, 2.5)
    assert float(jnp.max(jnp.abs(unlimited - whole))) > 0.05


def test_the_reference_under_the_programs_selection(small, tokens, expected):
    config, model, params, weights = small
    logits, compared = jax.jit(lambda w, t: deepseek_v3.reference_readings(
        config, w, t))(weights, tokens)
    assert compared.shape == (2, 2, 3)
    assert float(np.max(np.asarray(compared))) == 0.0
    assert np.max(np.abs(np.asarray(logits) - expected)) < CLOSE


@pytest.mark.parametrize("control,faults", [
    ("rows_unturned", {"attention": {"rows_turned": False}}),
    ("gain_left_out", {"attention": {"gained": False}}),
    ("q_norm_left_out", {"attention": {"q_normed": False}}),
    ("group_limit_left_out", {"group_limit": False}),
])
def test_the_controls_are_far_from_the_reference(small, tokens, expected,
                                                 control, faults):
    """Each control's fault in the REFERENCE moves the logits, or the
    selection's margin, far past the limits, so ``reference_forward`` is
    not a number."""
    config, _, _, weights = small
    logits, compared = jax.jit(lambda w, t: deepseek_v3.reference_readings(
        config, w, t, faults))(weights, tokens)
    apart = np.max(np.abs(np.asarray(logits) - expected))
    farthest = float(np.max(np.asarray(compared)[..., 1:]))
    assert farthest > 2 * deepseek_v3.SELECTION_MARGIN, control
    if control != "group_limit_left_out":
        assert apart > 0.1, control
    judged = jax.jit(lambda w, t: deepseek_v3.reference_forward(
        config, w, t, faults))(weights, tokens)
    assert not np.any(np.isfinite(np.asarray(judged)))


def test_the_loss_and_its_gradient_against_the_reference(small, tokens):
    config, model, params, weights = small
    tokens = tokens[:, :64]
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, _), ref_grads = correct.reference_backward(config)(
        weights, tokens)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-5
    error, cosine = correct.gradient_errors(
        jax.tree.map(np.asarray, deepseek_v3.reference_weights(config,
                                                               grads)),
        ref_grads)
    assert error < 2e-3 and cosine > 0.99999
    for name in ("layer0/attn/wq_a", "layer0/attn/q_norm/scale",
                 "layer1/attn/wq_b", "layer1/attn/wkv_a",
                 "layer2/attn/wkv_b", "layer2/attn/kv_norm/scale",
                 "layer1/moe/router/w", "layer2/moe/shared/w1"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name


# ------------------------------------------------------- rules and refusals
def test_kimi_linears_round_is_the_program_it_was():
    """Kimi Linear's configuration (a full-rank query, no rotary, one
    group) builds the decode round it built before the latent mixer's new
    options and the group limit: the round's jaxpr at the tiny size, 4
    lanes x 128, is PR 53's but for names (which a jaxpr's text does not
    hold).  Where a later change means to alter that round, print the text
    on both sides, read the difference, and replace the digest."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "kimi-linear-48b-a3b-12l-ep8.json")) as handle:
        config = kimi_linear.tiny(json.load(handle))
    model = kimi_linear.model(config)
    params = jax.eval_shape(lambda: kimi_linear.make_weights(model, 3))
    cache = jax.eval_shape(lambda: generation.init_cache(model, 4, 128))

    def lanes(dtype):
        return jax.ShapeDtypeStruct((4,), dtype)

    def round_(params, tokens, cache, lengths, temps, rng):
        return serving._decode_round(model, 0, 0.0, params, tokens, cache,
                                     lengths, temps, rng)

    text = str(jax.make_jaxpr(round_)(
        params, lanes(jnp.int32), cache, lanes(jnp.int32),
        lanes(jnp.float32), jax.eval_shape(lambda: jax.random.key(0))))
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "0bed0faf864c2c87fc253710f89892e8a8c4e775f1b058f9197ad83451422da3")


def test_the_new_leaves_have_a_sharding_rule():
    from jax.sharding import PartitionSpec

    from parameter_server_distributed_tpu.parallel.mesh import (
        MeshConfig, build_mesh)

    mesh = build_mesh(MeshConfig(expert=2, fsdp=2, tensor=2))
    rule = transformer_rule(mesh)
    # by head along the outputs, like wq
    assert rule("layer0/attn/wq_b", (24, 96)) == rule("layer0/attn/wq",
                                                      (24, 96))
    for name, shape in (("layer0/attn/wq_a", (64, 24)),
                        ("layer0/attn/q_norm/scale", (24,))):
        assert rule(name, shape) == PartitionSpec(), name


@pytest.mark.parametrize("fields,message", [
    (dict(q_latent=8), "latent layer's"),
    (dict(latent_rope=True), "latent layer's"),
    (dict(pattern=(LayerSpec(mixer="latent"),), kv_latent=8, qk_shared=3,
          latent_rope=True), "pairs"),
    (dict(rope_scaling=(40.0, 4096)), "RopeScaling"),
    (dict(rope_scaling=RopeScaling(40.0, 4096, mscale_all_dim=1.0)),
     "latent layer's"),
    (dict(moe_groups=3, moe_groups_kept=2, moe_experts=8,
          moe_score="sigmoid"), "groups"),
    (dict(moe_groups=4, moe_groups_kept=5, moe_experts=8,
          moe_score="sigmoid"), "kept"),
    (dict(moe_groups=4, moe_groups_kept=2, moe_experts=8), "sigmoid"),
])
def test_configurations_the_program_refuses(fields, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(d_model=32, n_heads=4, **fields)
