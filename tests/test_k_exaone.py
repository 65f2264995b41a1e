"""K-EXAONE's layers through the model, the cache and the server, against the
plain reference (``perfbench/reference/k_exaone.py``), at a small size in
float32 on the CPU, LOGITS and not tokens: one chip's share of the routed
experts (the router over all of them), a shared expert beside them, the
norms on the branches' outputs, rings of a window shorter than a user turn
three to one with full attention that has no rotary.
"""

import dataclasses
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from parameter_server_distributed_tpu.models import (  # noqa: E402
    generation, moe, serving)
from parameter_server_distributed_tpu.models.transformer import (  # noqa: E402
    LayerSpec, Transformer, TransformerConfig, transformer_rule)
from perfbench import correct  # noqa: E402
from perfbench.families import k_exaone  # noqa: E402
from perfbench.reference import k_exaone as reference  # noqa: E402

SEQ = 72
WINDOW = 8
CLOSE = 2e-5    # float32 logits of the program against the reference's


def _configuration(**changes) -> dict:
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "k-exaone-236b-a23b-8l-ep8.json")) as handle:
        config = k_exaone.tiny(json.load(handle))
    config.update(changes)
    return config


def _small(**changes):
    """(configuration, model, weights, the reference's weights)."""
    config = _configuration(**changes)
    model = k_exaone.model(config)
    params = k_exaone.make_weights(model, 3)
    return config, model, params, k_exaone.reference_weights(config, params)


@pytest.fixture(scope="module")
def small():
    return _small()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, SEQ)).astype(
        np.int32)


def _expected(small, tokens):
    config, _, _, weights = small
    return np.asarray(jax.jit(lambda w, t: k_exaone.reference_forward(
        config, w, t))(weights, tokens))


@pytest.fixture(scope="module")
def expected(small, tokens):
    return _expected(small, tokens)


def _reference_logits(small, sequence):
    return _expected(small, np.asarray(sequence, np.int32)[None])[0]


def test_the_model_is_a_share_of_the_experts_beside_a_shared_one(small):
    config, model, params, _ = small
    c = model.config
    assert [(s.window, s.rope, s.ffn) for s in c.prologue] == [
        (WINDOW, True, "mlp")]
    assert [(c.layer_spec(i).window, c.layer_spec(i).rope,
             c.layer_spec(i).ffn) for i in range(6)] == [
        (WINDOW, True, "mlp"), (WINDOW, True, "experts"),
        (WINDOW, True, "experts"), (0, False, "experts"),
        (WINDOW, True, "experts"), (WINDOW, True, "experts")]
    assert all(c.layer_spec(i).qk_norm for i in range(6))
    assert (c.moe_experts, c.moe_held, c.held_experts, c.moe_top_k) == (
        16, (4, 4), (4, 4), 3)
    assert (c.moe_shared_experts, c.norm_placement, c.moe_score) == (
        1, "post", "sigmoid")
    # the router keeps its width and its bias; the weights are the share
    assert params["layer1/moe/router/w"].shape == (64, 16)
    assert params["layer1/moe/router/bias"].shape == (16,)
    assert params["layer1/moe/w1"].shape == (4, 64, 32)
    assert params["layer1/moe/w2"].shape == (4, 32, 64)
    assert params["layer1/moe/shared/w1"].shape == (64, 32)
    assert params["layer1/moe/shared/w2"].shape == (32, 64)
    assert params["layer0/mlp/w1"].shape == (64, 96)
    assert "layer0/moe/shared/w1" not in params
    assert model.num_params() == k_exaone.param_count(config)
    # the depth's scale is the gain of a layer's two output norms, the
    # attention branch's at a third of it
    assert np.allclose(params["layer2/ln2/scale"], 1 / np.sqrt(12))
    assert np.allclose(params["layer2/ln1/scale"], 1 / np.sqrt(12) / 3)
    assert np.allclose(params["final_ln/scale"], 1.0)


def test_the_published_cut_counts_its_parameters():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "k-exaone-236b-a23b-8l-ep8.json")) as handle:
        config = json.load(handle)
    model = k_exaone.model(config)
    assert model.num_params() == k_exaone.param_count(config) \
        == 5_979_349_888
    shapes = model.param_shapes()
    assert shapes["layer1/moe/w1"] == (16, 6144, 2048)
    assert shapes["layer1/moe/router/w"] == (6144, 128)
    assert shapes["layer1/moe/shared/w3"] == (6144, 2048)
    assert shapes["layer0/mlp/w1"] == (6144, 18432)
    assert shapes["lm_head/w"] == (6144, 19200)
    rings = generation.ring_layers_of(model, 4096)
    assert rings == (0, 1, 2, 4, 5, 6)
    # ACTIVE and HELD: a token meets one held expert of eight chosen
    per_token = model.flops_per_sample() / model.config.max_seq
    attention = 12.0 * 8 * 6144 * model.config.max_seq
    held_active = (model.num_params() - 7 * 15 * 3 * 6144 * 2048)
    assert per_token == pytest.approx(6.0 * held_active + attention)


@pytest.mark.parametrize("placement", ["post", "pre"])
def test_forward_against_the_reference_in_both_norm_placements(
        tokens, placement):
    config = _configuration()
    config["assumed"]["norm_placement"] = placement
    model = k_exaone.model(config)
    params = k_exaone.make_weights(model, 3)
    weights = k_exaone.reference_weights(config, params)
    want = np.asarray(jax.jit(partial(
        reference.forward, **k_exaone._reference_arguments(config)))(
            weights, tokens))
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    assert np.max(np.abs(got - want)) < CLOSE
    assert np.std(want) > 0.05
    # the other placement is another model: the comparison is not blind
    other = dict(k_exaone._reference_arguments(config),
                 placement="pre" if placement == "post" else "post")
    far = np.asarray(jax.jit(partial(reference.forward, **other))(
        weights, tokens))
    assert np.max(np.abs(got - far)) > 1000 * CLOSE


def _prefill_then_decode(model, params, tokens, prompt: int, max_len: int):
    """(the prompt's last logits, every later position's through the cache
    a token at a time, the cache at the end)."""
    @jax.jit
    def run(params, tokens):
        logits, cache = generation.prefill(model, params, tokens[:, :prompt],
                                           max_len)

        def body(cache, token):
            step, cache = generation.decode_step(model, params, token, cache)
            return cache, step

        cache, steps = jax.lax.scan(body, cache, tokens[:, prompt:].T)
        return logits, jnp.moveaxis(steps, 0, 1), cache

    return run(params, tokens)


@pytest.mark.parametrize("prompt", [1, 7, 17, 40])
def test_prefill_then_decode_through_rings_that_wrap(small, tokens, expected,
                                                     prompt):
    """From a prompt shorter than the window, and from prompts that have
    wrapped the ring already; up to eight wraps while decoding."""
    _, model, params, _ = small
    first, rest, cache = _prefill_then_decode(model, params, tokens, prompt,
                                              80)
    assert cache.ring_layers == (0, 1, 2, 4, 5)
    assert [x.shape for x in cache.wk] == [(2, WINDOW, 1, 32)] * 5
    assert [x.shape for x in cache.k] == [(2, 80, 1, 32)]
    assert np.max(np.abs(first - expected[:, prompt - 1])) < CLOSE
    assert np.max(np.abs(rest - expected[:, prompt:])) < CLOSE


def test_the_published_window_wraps_three_times_while_decoding():
    """Rings of 128 as published, 430 positions decoded after a prompt of
    50: every ring slot is overwritten three times."""
    sized = _small(sliding_window=128, max_position_embeddings=512,
                   sliding_windows=[128, 128, 128, 0, 128, 128])
    _, model, params, _ = sized
    sequence = np.random.default_rng(4).integers(0, 512, (1, 480)).astype(
        np.int32)
    want = _expected(sized, sequence)
    first, rest, cache = _prefill_then_decode(model, params, sequence, 50,
                                              512)
    assert [x.shape[1] for x in cache.wk] == [128] * 5
    assert np.max(np.abs(first - want[:, 49])) < CLOSE
    assert np.max(np.abs(rest - want[:, 50:])) < 2 * CLOSE


def _experts(seed=5, n=40, d=32, f=16, experts=16):
    keys = jax.random.split(jax.random.key(seed), 9)
    normal = jax.random.normal
    return dict(
        x=normal(keys[0], (n, d)), router=normal(keys[1], (d, experts)),
        bias=0.05 * normal(keys[2], (experts,)),
        w1=normal(keys[3], (experts, d, f)) / np.sqrt(d),
        w3=normal(keys[4], (experts, d, f)) / np.sqrt(d),
        w2=normal(keys[5], (experts, f, d)) / np.sqrt(f),
        shared_w1=normal(keys[6], (d, f)) / np.sqrt(d),
        shared_w3=normal(keys[7], (d, f)) / np.sqrt(d),
        shared_w2=normal(keys[8], (f, d)) / np.sqrt(f))


def _share(w, first, count, top_k=3, scale=2.5):
    """One rank's routed part by the program."""
    return jax.jit(lambda x, w1, w2, w3: moe.dropless_experts(
        x, x @ w["router"], w1, w2, w3, top_k=top_k, act="swiglu",
        score="sigmoid", bias=w["bias"], scale=scale,
        held=(first, count)))(
            w["x"], *(w[name][first:first + count]
                      for name in ("w1", "w2", "w3")))


@pytest.mark.parametrize("ranks", [8, 4, 2, 1])
def test_the_shares_add_up_to_the_whole_layer(ranks):
    """The routed parts of all the ranks (8: two experts each, fewer than
    a token's three choices) plus the shared expert counted once are the
    uncut reference's whole layer; a rank's part is the reference's of the
    same share; a rank's loads are its experts' and what went elsewhere."""
    w = _experts()
    count = 16 // ranks
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.expert_layer(w["x"], w, 3, 2.5)
        shared = reference._swiglu(w["x"], w["shared_w1"], w["shared_w3"],
                                   w["shared_w2"])
        _, all_loads = moe.dropless_experts(
            w["x"], w["x"] @ w["router"], w["w1"], w["w2"], w["w3"], top_k=3,
            act="swiglu", score="sigmoid", bias=w["bias"], scale=2.5)
        total = 0.0
        for rank in range(ranks):
            first = rank * count
            part, loads = _share(w, first, count)
            mine = {name: w[name][first:first + count] if name in
                    ("w1", "w2", "w3") else w[name] for name in w}
            want, _ = reference.expert_layer(w["x"], mine, 3, 2.5,
                                             held=(first, count),
                                             shared=False)
            assert np.max(np.abs(part - want)) < 1e-4
            assert loads.shape == (count + 1,)
            assert np.array_equal(loads[:count],
                                  all_loads[first:first + count])
            assert int(loads.sum()) == 40 * 3
            total = total + part
    assert np.max(np.abs(total + shared - whole)) < 1e-4
    assert float(jnp.max(jnp.abs(whole))) > 1.0


def _parents_dropless_experts(x, router_logits, w1, w2, w3, *, top_k, act,
                              score, bias, scale):
    """``dropless_experts`` as PR 39's tree had it, line for line: what
    the two older MoE cells ran."""
    n, d = x.shape
    experts = w1.shape[0]
    gates, top_idx = moe.select_experts(router_logits, top_k, score, bias,
                                        scale)
    flat = top_idx.reshape(n * top_k)
    order = jnp.argsort(flat, stable=True)
    loads = jnp.zeros((experts,), jnp.int32).at[flat].add(1)
    rows = x[order // top_k]
    dot = partial(jax.lax.ragged_dot, group_sizes=loads,
                  preferred_element_type=jnp.float32)
    hidden = dot(rows, w1).astype(x.dtype)
    if act == "gelu":
        hidden = jax.nn.gelu(hidden)
    else:
        gate = jax.nn.silu if act == "swiglu" else jax.nn.relu
        hidden = gate(hidden) * dot(rows, w3).astype(x.dtype)
    out = dot(hidden, w2)
    out = out[jnp.argsort(order)].reshape(n, top_k, d)
    return jnp.sum(out * gates[..., None], axis=1), loads


@pytest.mark.parametrize("act,score,dtype", [
    ("swiglu", "sigmoid", jnp.float32), ("reglu", "softmax", jnp.bfloat16),
    ("gelu", "softmax", jnp.float32)])
def test_holding_every_expert_is_the_parents_layer_bit_for_bit(act, score,
                                                               dtype):
    w = jax.tree.map(lambda a: a.astype(dtype), _experts(seed=6))
    logits = (w["x"] @ w["router"]).astype(jnp.float32)
    args = (w["x"], logits, w["w1"], w["w2"],
            None if act == "gelu" else w["w3"])
    how = dict(top_k=3, act=act, score=score, scale=1.0,
               bias=w["bias"] if score == "sigmoid" else None)
    want, want_loads = jax.jit(partial(_parents_dropless_experts, **how))(
        *args)
    got, loads = jax.jit(partial(moe.dropless_experts, **how))(*args)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    assert np.array_equal(loads, want_loads)
    # ... and so is a share that happens to be all of them
    all_held, held_loads = jax.jit(partial(
        moe.dropless_experts, held=(0, 16), **how))(*args)
    assert np.array_equal(np.asarray(all_held, np.float32),
                          np.asarray(want, np.float32))
    assert np.array_equal(held_loads[:16], want_loads) \
        and int(held_loads[16]) == 0


def test_a_model_without_a_share_keeps_its_shapes_and_its_loads():
    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=48, d_expert=16,
        moe_experts=8, moe_top_k=2, mlp_act="swiglu",
        pattern=(LayerSpec(ffn="experts"),))
    model = Transformer(config)
    assert config.moe_held == () and config.held_experts == (0, 8)
    assert model.param_shapes()["layer0/moe/w1"] == (8, 32, 16)
    assert "layer0/moe/shared/w1" not in model.param_shapes()
    routed: list = []
    model._forward(model.init_params(0), jnp.zeros((1, 8), jnp.int32),
                   collect_kv=False, route_stats=routed)
    assert [r.shape for r in routed] == [(8,), (8,)]


def _fresh_server(seen: list, monkeypatch, **kwargs):
    """A server of a model of its own (the runners are cached by model),
    with every block that goes through a ring noted in ``seen``."""
    small = _small()
    through_ring = generation._ring_attention

    def noting(c, q, *rest):
        seen.append(q.shape[1])
        return through_ring(c, q, *rest)

    monkeypatch.setattr(generation, "_ring_attention", noting)
    return small, serving.DecodeServer(small[1], small[2], slots=4,
                                       max_len=128, **kwargs)


def test_a_turn_longer_than_the_window_behind_a_resident_prefix(monkeypatch):
    """The extension path: a turn of 21 tokens (bucket 32, four windows)
    after a system prompt in the tree goes against the row BY POSITION,
    the rings are spliced from the row afterwards, and the logits and the
    decoded tokens are the reference's over the whole sequence.  No block
    longer than one token ever meets a ring."""
    through_ring: list = []
    small, warm = _fresh_server(through_ring, monkeypatch, prompt_cache=8,
                                prefix_cache_bytes=1 << 24)
    rng = np.random.default_rng(6)
    system = rng.integers(0, 512, 50).astype(np.int32)
    turn = rng.integers(0, 512, 21).astype(np.int32)
    prompt = np.concatenate([system, turn])
    warm.submit(system, max_new_tokens=1)
    rid = warm.submit(prompt, max_new_tokens=20)
    assert warm.stats["prefix_hits"] == 1
    assert warm.stats["prefill_tokens"] == 50 + 21
    node, matched, _ = warm._prefix_tree.lookup(tuple(prompt.tolist()))
    assert matched == 71
    served = warm.run_to_completion()[rid]
    logits = _reference_logits(small, np.concatenate([prompt, served]))
    assert np.max(np.abs(np.asarray(node.last) - logits[70])) < CLOSE
    assert served == np.argmax(logits[70:90], -1).tolist()
    assert through_ring and set(through_ring) == {1}
    # the row keeps EVERY layer by position; the slot five rings and one
    assert node.handle.row[0].shape == (6, 64 + 32, 1, 32)
    kinds = warm._cache.nbytes_by_kind()
    assert kinds == {"full": 4 * 2 * 128 * 32 * 4,
                     "window": 4 * 5 * 2 * WINDOW * 32 * 4, "state": 0,
                     "latent": 0}


def test_a_prompt_prefilled_in_chunks_longer_than_the_window(monkeypatch):
    """Chunks of 48 positions, six windows each, against the row so far."""
    monkeypatch.setattr(serving, "_PREFILL_WHOLE", 32 * 96)
    monkeypatch.setattr(serving, "_PREFILL_CHUNK", 48)
    through_ring: list = []
    small, chunked = _fresh_server(through_ring, monkeypatch)
    prompt = np.random.default_rng(8).integers(0, 512, 110).astype(np.int32)
    assert not serving._prefills_whole(small[1], 128)
    last, _ = chunked._prefill_in_chunks(np.pad(prompt, (0, 18))[None], 110)
    logits = _reference_logits(small, prompt)
    assert np.max(np.abs(np.asarray(last) - logits[-1])) < CLOSE
    rid = chunked.submit(prompt, max_new_tokens=12)
    served = chunked.run_to_completion()[rid]
    after = _reference_logits(small, np.concatenate([prompt, served]))
    assert served == np.argmax(after[109:121], -1).tolist()
    assert set(through_ring) == {1}


def test_a_block_longer_than_its_ring_is_refused_by_name(small):
    """What the admission paths must never reach."""
    _, model, params, _ = small
    cache = generation.init_cache(model, 1, 64)
    with pytest.raises(ValueError, match="does not go through a ring"):
        generation.decode_block(model, params,
                                jnp.zeros((1, WINDOW + 1), jnp.int32), cache)


def test_the_counters_count_the_held_experts_and_the_rows_computed(
        monkeypatch):
    _, server = _fresh_server([], monkeypatch)
    counters = server._obs_moe
    before = {name: c.value for name, c in counters.items()}
    rid = server.submit(np.arange(1, 41, dtype=np.int32), max_new_tokens=6)
    assert len(server.run_to_completion()[rid]) == 6
    moved = {name: c.value - before[name] for name, c in counters.items()}
    rounds = moved["layer_rounds"] / 5
    # every forward routes its REAL tokens x 3 in each of 5 expert layers:
    # the admission's 40 of a bucket of 64, and of every fetched round's 4
    # lanes the one that holds the request (PR 60)
    assert moved["assignments_routed"] == 5 * 3 * (40 + 1 * rounds)
    assert moved["round_assignments"] == 5 * 3 * rounds
    assert moved["round_assignment_places"] == 5 * 3 * 4 * rounds
    assert 0 < moved["assignments"] < 0.6 * moved["assignments_routed"]
    assert server.stats["moe_assignments"] >= moved["assignments"]
    # places: the 4 HELD experts of a layer, not the router's 16
    assert moved["expert_places"] == 4 * 5 * rounds
    assert 0 < moved["experts_touched"] <= moved["expert_places"]
    assert moved["admit_experts_touched"] <= 4 * 5
    assert np.isfinite(moved["load_max_over_mean"])
    # a layer and round in which no held expert saw a token counts zero
    server._count_routing(np.asarray([0, 0, 0, 0, 12] * 5))
    assert np.isfinite(counters["load_max_over_mean"].value)


def test_the_reference_under_the_programs_selection(small, tokens, expected,
                                                    capfd):
    config, model, params, weights = small
    chosen = jax.jit(model.expert_selections)(params, tokens)
    assert [c.shape for c in chosen] == [(2, SEQ, 3)] * 5
    # chosen among ALL the router's experts, not the held ones
    assert max(int(c.max()) for c in chosen) > 7
    capfd.readouterr()
    logits = jax.jit(lambda w, t: k_exaone.reference_forward(config, w, t))(
        weights, tokens)
    assert np.max(np.abs(np.asarray(logits) - expected)) < 1e-6
    line = json.loads(next(l for l in capfd.readouterr().out.splitlines()
                           if "selection_check" in l))
    assert line["tokens_with_another_expert"] == [0.0] * 5
    seen = []
    wrong = [(c + 1) % 16 for c in chosen]
    moved = reference.forward(weights, tokens, selection=wrong,
                              report=seen.append,
                              **k_exaone._reference_arguments(config))
    assert seen[0].shape == (5, 2, 2)
    assert float(jnp.max(seen[0][..., 1])) > 10 * k_exaone.SELECTION_MARGIN
    assert np.max(np.abs(np.asarray(moved) - expected)) > 0.01
    again = k_exaone.program_weights(config, weights)
    assert set(again) == set(params)
    assert all(np.array_equal(again[name], params[name]) for name in params)


@pytest.mark.parametrize("control", ["no_shared_expert", "every_expert_held",
                                     "no_window"])
def test_the_controls_are_far_from_the_reference(small, tokens, expected,
                                                 control):
    """The shared expert left out, a share that claims experts it does not
    hold, a window layer that sees every earlier position: each is far."""
    config, model, params, _ = small
    if control == "no_shared_expert":
        params = {name: jnp.zeros_like(value) if "/moe/shared/" in name
                  else value for name, value in params.items()}
    elif control == "every_expert_held":
        model = k_exaone.model(config, moe_held=(0, 4))
    else:
        unwindowed = dataclasses.replace(
            model.config,
            prologue=tuple(dataclasses.replace(s, window=0)
                           for s in model.config.prologue),
            pattern=tuple(dataclasses.replace(s, window=0)
                          for s in model.config.pattern))
        model = Transformer(unwindowed)
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    error, _ = correct.logits_errors(got, expected)
    assert error > 0.02, error


def test_the_loss_and_its_gradient_against_the_reference(small, tokens):
    config, model, params, weights = small
    tokens = tokens[:, :64]
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, _), ref_grads = correct.reference_backward(config)(
        weights, tokens)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-5
    error, cosine = correct.gradient_errors(
        jax.tree.map(np.asarray, k_exaone.reference_weights(config, grads)),
        ref_grads)
    assert error < 1e-3 and cosine > 0.99999
    for name in ("layer0/mlp/w3", "layer3/moe/w3", "layer3/moe/shared/w1",
                 "layer3/moe/router/w", "layer2/ln1/scale"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name


def test_a_shared_experts_matrices_shard_like_the_dense_mlps():
    from parameter_server_distributed_tpu.parallel.mesh import (
        MeshConfig, build_mesh)

    mesh = build_mesh(MeshConfig(expert=2, fsdp=2, tensor=2))
    rule = transformer_rule(mesh)
    for ours, dense in (("moe/shared/w1", "mlp/w1"),
                        ("moe/shared/w3", "mlp/w3")):
        assert rule(f"layer1/{ours}", (64, 32)) == rule(
            f"layer0/{dense}", (64, 32))
    assert rule("layer1/moe/shared/w2", (32, 64)) == rule(
        "layer0/mlp/w2", (32, 64))
    assert rule("layer1/moe/shared/w1", (64, 32)) != rule(
        "layer1/moe/w1", (4, 64, 32))


@pytest.mark.parametrize("fields,message", [
    (dict(moe_held=(14, 4), moe_experts=16), "first, count"),
    (dict(moe_held=(0, 0), moe_experts=16), "first, count"),
    (dict(norm_placement="sandwich"), "norm_placement must be"),
    (dict(moe_every=2, moe_shared_experts=1), "not ``moe``"),
    (dict(moe_every=2, norm_placement="post"), "not ``moe``"),
])
def test_configurations_the_program_refuses(fields, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(d_model=32, n_heads=4, **fields)


def test_a_draft_beside_rings_is_still_refused(small):
    _, model, params, _ = small
    with pytest.raises(ValueError, match="cannot be rolled back"):
        serving.DecodeServer(model, params, slots=2, max_len=64,
                             draft=model, draft_params=params)
