"""LFM2's layers through the model, the cache and the server, against the
plain reference (``perfbench/reference/lfm2.py``), at a small size in
float32 on the CPU, LOGITS and not tokens: two leading conv layers with a
dense SwiGLU of one width, then attention, conv, conv, conv with experts of
another, routed by sigmoid score + a stored bias; a conv layer's decode
state (two columns) beside the K/V of the one attention layer, its snapshot
in the prefix tree and in a prompt prefilled in chunks.
"""

import dataclasses
import json
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
    LayerSpec, Transformer, TransformerConfig)
from parameter_server_distributed_tpu.ops.short_conv import (  # noqa: E402
    gated_short_conv)
from perfbench import correct  # noqa: E402
from perfbench.families import lfm2  # noqa: E402
from perfbench.reference import lfm2 as reference  # noqa: E402

SEQ = 72
CLOSE = 1e-5    # float32 logits of the program against the reference's


@pytest.fixture(scope="module")
def small():
    """(configuration, model, weights, the reference's weights)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b-10l.json")) as handle:
        config = lfm2.tiny(json.load(handle))
    model = lfm2.model(config)
    params = lfm2.make_weights(model, 3)
    return config, model, params, lfm2.reference_weights(config, params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, SEQ)).astype(
        np.int32)


@pytest.fixture(scope="module")
def expected(small, tokens):
    config, _, _, weights = small
    return np.asarray(jax.jit(lambda w, t: lfm2.reference_forward(
        config, w, t))(weights, tokens))


def _reference_logits(small, sequence):
    config, _, _, weights = small
    return np.asarray(lfm2.reference_forward(
        config, weights, np.asarray(sequence, np.int32)[None]))[0]


def test_the_pattern_is_a_prologue_and_a_period_of_two_widths(small):
    config, model, params, _ = small
    c = model.config
    assert [(s.mixer, s.ffn) for s in c.prologue] == [("conv", "mlp")] * 2
    assert [(s.mixer, s.ffn, s.qk_norm) for s in c.pattern] == [
        ("softmax", "experts", True)] + [("conv", "experts", False)] * 3
    assert [c.layer_spec(i).mixer for i in range(6)] == [
        "conv", "conv", "softmax", "conv", "conv", "conv"]
    assert c.state_layers == (0, 1, 3, 4, 5) and c.layers_of("conv") \
        == c.state_layers
    # the prologue's dense width and the experts' width differ
    assert params["layer0/mlp/w1"].shape == (64, 96)
    assert params["layer2/moe/w1"].shape == (8, 64, 32)
    assert params["layer2/moe/router/bias"].shape == (8,)
    assert params["layer0/conv/in_proj"].shape == (64, 192)
    assert params["layer0/conv/kernel"].shape == (3, 64)
    assert "layer0/attn/wq" not in params and "layer2/conv/kernel" \
        not in params
    assert params["layer2/attn/q_norm/scale"].shape == (16,)
    assert (c.moe_router_input, c.moe_score, c.moe_expert_bias) == (
        "ffn", "sigmoid", True)
    # the tied head is a second matrix in the program's store
    assert model.num_params() == lfm2.param_count(config) + 512 * 64
    assert np.array_equal(params["lm_head/w"], params["embed/tok"].T)


def test_forward_against_the_reference(small, tokens, expected):
    _, model, params, _ = small
    got = jax.jit(model.apply)(params, tokens)
    assert np.max(np.abs(np.asarray(got) - expected)) < CLOSE
    assert np.std(expected) > 0.05


@pytest.mark.parametrize("prompt", [1, 2, 17, 40])
def test_prefill_then_decode_through_the_cache(small, tokens, expected,
                                               prompt):
    """From a prompt shorter than the kernel (the state still holds a
    zero column), of its length less one, and of many positions."""
    _, model, params, _ = small

    @jax.jit
    def run(params, tokens):
        logits, cache = generation.prefill(model, params, tokens[:, :prompt],
                                           80)

        def body(cache, token):
            step, cache = generation.decode_step(model, params, token, cache)
            return cache, step

        _, steps = jax.lax.scan(body, cache, tokens[:, prompt:].T)
        return logits, jnp.moveaxis(steps, 0, 1)

    first, rest = run(params, tokens)
    assert np.max(np.abs(first - expected[:, prompt - 1])) < CLOSE
    assert np.max(np.abs(rest - expected[:, prompt:])) < CLOSE


@pytest.mark.parametrize("fault", ["zeroed", "stale"])
def test_a_wrong_conv_state_is_seen_in_the_logits(small, tokens, expected,
                                                  fault):
    """The comparison above is not blind to the state: a round that reads
    zeros, or the state of one position earlier, is far from the
    reference."""
    _, model, params, _ = small
    prompt = 30

    @jax.jit
    def run(params, tokens):
        _, cache = generation.prefill(model, params, tokens[:, :prompt], 80)
        _, early = generation.prefill(model, params, tokens[:, :prompt - 1],
                                      80)
        state = (jax.tree.map(jnp.zeros_like, cache.state)
                 if fault == "zeroed" else early.state)
        broken = dataclasses.replace(cache, state=state)
        return generation.decode_step(model, params, tokens[:, prompt],
                                      broken)[0]

    got = run(params, tokens)
    assert np.max(np.abs(got - expected[:, prompt])) > 1000 * CLOSE


def test_the_conv_block_by_block_is_the_whole_sequence():
    """A block against the state before it, pads that stay out of the
    state, and a decode round's single token."""
    rng = np.random.default_rng(1)
    b, c, x = (jnp.asarray(rng.normal(size=(2, 50, 8)), jnp.float32)
               for _ in range(3))
    kernel = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    want = jnp.stack([reference.short_conv(
        jnp.concatenate([b[i], c[i], x[i]], -1),
        {"w_in": jnp.eye(24), "taps": kernel.T, "w_out": jnp.eye(8)})
        for i in range(2)])
    whole, state = gated_short_conv(b, c, x, kernel)
    assert np.allclose(whole, want, atol=1e-5)
    assert np.allclose(state, (b * x)[:, -2:], atol=1e-6)
    head, mid = gated_short_conv(b[:, :30], c[:, :30], x[:, :30], kernel)
    padded = [jnp.pad(v[:, 30:], ((0, 0), (0, 12), (0, 0)),
                      constant_values=7.0) for v in (b, c, x)]
    tail, end = gated_short_conv(*padded, kernel, state=mid,
                                 counts=jnp.asarray([20, 20]))
    assert np.allclose(jnp.concatenate([head, tail[:, :20]], 1), want,
                       atol=1e-5)
    assert np.allclose(end, state, atol=1e-6)
    # one real token of a padded block: the register shifts by one
    _, one = gated_short_conv(*(v[:, 30:34] for v in (b, c, x)), kernel,
                              state=mid, counts=jnp.asarray([1, 1]))
    assert np.allclose(one, (b * x)[:, 29:31], atol=1e-6)
    step, after = gated_short_conv(b[:, 30:31], c[:, 30:31], x[:, 30:31],
                                   kernel, state=mid)
    assert np.allclose(step, want[:, 30:31], atol=1e-5)
    assert np.allclose(after, one, atol=1e-6)


def test_the_bias_changes_the_selection_and_not_the_gates():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(400, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=16) * 0.1, jnp.float32)
    plain_gates, plain = moe.select_experts(logits, 4, "sigmoid")
    gates, chosen = moe.select_experts(logits, 4, "sigmoid", bias, 1.0)
    moved = np.any(np.sort(chosen, -1) != np.sort(plain, -1), axis=-1)
    assert 0.2 < moved.mean() < 0.9
    # a gate is the chosen expert's own score over the chosen scores' sum:
    # the bias is nowhere in it
    scores = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(scores, np.asarray(chosen), -1)
    assert np.allclose(gates, picked / (picked.sum(-1, keepdims=True)
                                        + 1e-6), atol=1e-6)
    # the chosen are the top 4 of score + bias
    want = np.argsort(-(scores + np.asarray(bias)), -1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    # where the selection did not move, neither did the gates
    same = ~moved
    assert np.allclose(np.sort(gates, -1)[same],
                       np.sort(plain_gates, -1)[same], atol=1e-6)
    # the reference's gates, written another way ([tokens, experts], zero
    # where a token chose another), are the program's
    weight = np.asarray(reference.gates(
        logits, {"router": jnp.eye(16), "bias": bias}, 4, 1.0)[0])
    assert np.allclose(np.take_along_axis(weight, np.asarray(chosen), -1),
                       gates, atol=1e-6)
    assert np.all((weight > 0).sum(-1) == 4)


@pytest.mark.parametrize("control,far", [
    ("no_bias", True), ("softmax_gates", True), ("sound", False)])
def test_the_router_controls_fail_the_comparison(small, tokens, expected,
                                                 control, far, monkeypatch):
    """What the chip's controls do, at the tiny size: the bias left out of
    the selection, and the gates taken as the softmax over the chosen
    logits, are each far from the reference; the sound program is not."""
    config, _, params, _ = small
    if control == "no_bias":
        params = {name: jnp.zeros_like(value)
                  if name.endswith("moe/router/bias") else value
                  for name, value in params.items()}
    if control == "softmax_gates":
        sound = moe.select_experts

        def softmax_gates(logits, top_k, score, bias, scale, *limit):
            _, chosen = sound(logits, top_k, score, bias, scale, *limit)
            return jax.nn.softmax(jnp.take_along_axis(
                logits.astype(jnp.float32), chosen, -1), -1), chosen

        monkeypatch.setattr(moe, "select_experts", softmax_gates)
    got = np.asarray(jax.jit(lfm2.model(config).apply)(params, tokens))
    error, worst = correct.logits_errors(got, expected)
    assert (error > 0.02) == far, (error, worst)
    assert far or error < 1e-5


def test_the_reference_under_the_programs_selection(small, tokens, expected,
                                                    capfd):
    """The comparison of the chip's ``correct``: the program's chosen
    experts given to the reference, which reports where they are not its
    own.  In float32 they are; a selection moved to other experts is far
    under the reference's cut and its logits are not a number."""
    config, model, params, weights = small
    chosen = jax.jit(model.expert_selections)(params, tokens)
    assert [c.shape for c in chosen] == [(2, SEQ, 3)] * 4
    logits = jax.jit(lambda w, t: lfm2.reference_forward(config, w, t))(
        weights, tokens)
    assert np.max(np.abs(np.asarray(logits) - expected)) < 1e-6
    line = json.loads(next(l for l in capfd.readouterr().out.splitlines()
                           if "selection_check" in l))
    assert line["tokens_with_another_expert"] == [0.0] * 4
    assert line["farthest_from_the_cut"] == [0.0] * 4
    seen = []
    wrong = [(c + 1) % 8 for c in chosen]
    moved = reference.forward(weights, tokens, selection=wrong,
                              report=seen.append,
                              **lfm2._reference_arguments(config))
    assert seen[0].shape == (4, 2, 2)
    assert float(jnp.min(seen[0][..., 0])) > 0.5 * SEQ
    assert float(jnp.max(seen[0][..., 1])) > 10 * lfm2.SELECTION_MARGIN
    assert np.max(np.abs(np.asarray(moved) - expected)) > 0.01
    # the program's names and back
    again = lfm2.program_weights(config, weights)
    assert set(again) == set(params)
    assert all(np.array_equal(again[name], params[name]) for name in params)


def test_the_loss_and_its_gradient_against_the_reference(small, tokens):
    config, model, params, weights = small
    tokens = tokens[:, :64]     # (two of the tiny copy's loss chunks)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, _), ref_grads = correct.reference_backward(config)(
        weights, tokens)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-5
    error, cosine = correct.gradient_errors(
        jax.tree.map(np.asarray, lfm2.reference_weights(config, grads)),
        ref_grads)
    assert error < 1e-3 and cosine > 0.99999
    # every kind of weight has a gradient: the conv kernel, the router, its
    # experts, the dense layers' SwiGLU
    for name in ("layer0/conv/kernel", "layer0/mlp/w3", "layer3/moe/w3",
                 "layer3/moe/router/w", "layer2/attn/q_norm/scale"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name
    # the stored bias enters the selection only, which has no gradient
    assert float(jnp.max(jnp.abs(grads["layer3/moe/router/bias"]))) == 0


def _server(small, **kwargs):
    _, model, params, _ = small
    return serving.DecodeServer(model, params, slots=4, max_len=128,
                                **kwargs)


def _rows_close(a, b, n):
    """Two rows (k, v, a state each of five conv layers) agree on their
    first n positions."""
    for x, y in zip(a[:2], b[:2]):
        assert np.max(np.abs(np.asarray(x[:, :n]) - np.asarray(y[:, :n]))) \
            < 1e-5
    assert len(a) == len(b) == 2 + 5
    for x, y in zip(a[2:], b[2:]):
        assert x.shape == y.shape == (2, 64)
        assert np.max(np.abs(np.asarray(x) - np.asarray(y))) < 1e-5


def test_a_prefix_hit_restores_row_and_conv_snapshot(small):
    """A system prompt resident in the tree, then a turn after it: the
    extension starts from the K/V row AND the snapshot of the five conv
    states, and its logits are the reference's over the whole prompt."""
    rng = np.random.default_rng(6)
    system = rng.integers(0, 512, 50).astype(np.int32)
    turn = rng.integers(0, 512, 9).astype(np.int32)
    prompt = np.concatenate([system, turn])
    warm = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    warm.submit(system, max_new_tokens=1)
    rid = warm.submit(prompt, max_new_tokens=10)
    assert warm.stats["prefix_hits"] == 1
    assert warm.stats["prefill_tokens"] == 50 + 9
    node, matched, _ = warm._prefix_tree.lookup(tuple(prompt.tolist()))
    assert matched == 59 and node.handle.state_at == 59
    _, cold_row, _ = serving._prefill_runner(small[1], 64, "native")(
        small[2], jnp.asarray(np.pad(prompt, (0, 5))[None]),
        jnp.asarray(59, jnp.int32))
    _rows_close(node.handle.row, cold_row, 59)
    served = warm.run_to_completion()[rid]
    logits = _reference_logits(small, np.concatenate([prompt, served]))
    assert np.max(np.abs(np.asarray(node.last) - logits[58])) < CLOSE
    assert served == np.argmax(logits[58:68], -1).tolist()
    # an identical prompt again: the row, the snapshot and the logits replay
    again = warm.submit(prompt, max_new_tokens=10)
    assert warm.stats["prompt_cache_hits"] == 1
    assert warm.run_to_completion()[again] == served


def test_a_match_into_an_edge_falls_back_to_the_snapshot(small):
    """Two turns that share their first tokens split the first turn's
    edge: the split node has K/V and no snapshot, so the second turn
    extends from the system prompt's end, and is right."""
    rng = np.random.default_rng(7)
    system = rng.integers(0, 512, 40).astype(np.int32)
    shared = rng.integers(0, 512, 6).astype(np.int32)
    first = np.concatenate([system, shared, rng.integers(0, 512, 5)]
                           ).astype(np.int32)
    second = np.concatenate([system, shared, rng.integers(0, 512, 7)]
                            ).astype(np.int32)
    warm = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    warm.submit(system, max_new_tokens=1)
    warm.submit(first, max_new_tokens=1)
    before = warm.stats["prefill_tokens"]
    rid = warm.submit(second, max_new_tokens=8)
    # 13 tokens forwarded (from the system prompt's end), not 7
    assert warm.stats["prefill_tokens"] - before == len(second) - 40
    assert warm._prefix_tree.splits == 1
    served = warm.run_to_completion()[rid]
    logits = _reference_logits(small, np.concatenate([second, served]))
    assert served == np.argmax(logits[52:60], -1).tolist()


def test_a_prompt_prefilled_in_chunks_across_a_boundary(small, monkeypatch):
    """Chunks of 48: the second chunk's first positions read the conv
    state the first chunk left, and its attention the first chunk's K/V."""
    monkeypatch.setattr(serving, "_PREFILL_WHOLE", 32 * 96)
    monkeypatch.setattr(serving, "_PREFILL_CHUNK", 48)
    prompt = np.random.default_rng(8).integers(0, 512, 110).astype(np.int32)
    assert not serving._prefills_whole(small[1], 128)
    chunked = _server(small)
    last, row = chunked._prefill_in_chunks(np.pad(prompt, (0, 18))[None], 110)
    want_last, want_row, _ = serving._prefill_runner(
        small[1], 128, "native")(small[2], jnp.asarray(np.pad(
            prompt, (0, 18))[None]), jnp.asarray(110, jnp.int32))
    _rows_close(row, want_row, 110)
    logits = _reference_logits(small, prompt)
    assert np.max(np.abs(np.asarray(last) - logits[-1])) < CLOSE
    assert np.max(np.abs(np.asarray(want_last) - logits[-1])) < CLOSE
    rid = chunked.submit(prompt, max_new_tokens=6)
    served = chunked.run_to_completion()[rid]
    after = _reference_logits(small, np.concatenate([prompt, served]))
    assert served == np.argmax(after[109:115], -1).tolist()


def test_the_cache_by_kind_counts_the_states(small):
    server = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    kinds = server._cache.nbytes_by_kind()
    # 4 slots: one attention layer's K and V of 128 positions x 2 heads of
    # 16 (one row of 32 lanes) x 4 B; five conv layers x 2 columns x 64
    assert [x.shape for x in server._cache.k] == [(4, 128, 1, 32)]
    assert [[x.shape for x in layer] for layer in server._cache.state] \
        == [[(4, 2, 64)]] * 5
    assert kinds == {"full": 4 * 2 * 128 * 2 * 16 * 4, "window": 0,
                     "state": 4 * 5 * 2 * 64 * 4, "latent": 0}
    assert server.stats["cache_state_bytes"] == kinds["state"]
    rid = server.submit(np.arange(1, 41, dtype=np.int32), max_new_tokens=6)
    # the row in the tree: K and V of a 64-position bucket and the snapshot
    assert server._prefix_tree.bytes == 2 * 64 * 2 * 16 * 4 + 5 * 2 * 64 * 4
    assert len(server.run_to_completion()[rid]) == 6


@pytest.mark.parametrize("feature", ["draft", "int8", "speculative"])
def test_features_that_cannot_hold_a_state_refuse_the_model(small, feature):
    _, model, params, _ = small
    if feature == "draft":
        with pytest.raises(ValueError, match="cannot be rolled back"):
            serving.DecodeServer(model, params, slots=2, max_len=64,
                                 draft=model, draft_params=params)
    elif feature == "int8":
        with pytest.raises(ValueError, match="native cache"):
            serving.DecodeServer(model, params, slots=2, max_len=64,
                                 cache_dtype="int8")
    else:
        with pytest.raises(ValueError, match="cannot be rolled back"):
            generation.speculative_generate(
                model, params, model, params, jnp.zeros((1, 8), jnp.int32), 4)


@pytest.mark.parametrize("fields,message", [
    (dict(pattern=(LayerSpec(mixer="latent"),)), "kv_latent"),
    (dict(prologue=(LayerSpec(),), scan_layers=True), "run unrolled"),
    (dict(pattern=(LayerSpec(mixer="conv"),), bias=True), "no bias"),
    (dict(moe_expert_bias=True), "moe_score='sigmoid'"),
    (dict(moe_score="tanh"), "moe_score must be"),
    (dict(moe_router_input="embedding"), "moe_router_input must be"),
])
def test_configurations_the_program_refuses(fields, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(d_model=32, n_heads=4, **fields)


def test_a_conv_layer_has_no_heads():
    with pytest.raises(ValueError, match="no heads"):
        LayerSpec(mixer="conv", qk_norm=True)


def test_a_prologue_shifts_the_period_and_an_experts_width_is_its_own():
    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=5, d_ff=48, d_expert=16,
        moe_experts=4, mlp_act="swiglu",
        prologue=(LayerSpec(mixer="conv"),),
        pattern=(LayerSpec(ffn="experts"), LayerSpec(mixer="conv",
                                                     ffn="experts")))
    assert [config.layer_spec(i).mixer for i in range(5)] == [
        "conv", "softmax", "conv", "softmax", "conv"]
    assert config.expert_width == 16 and config.state_layers == (0, 2, 4)
    shapes = Transformer(config).param_shapes()
    assert shapes["layer0/mlp/w1"] == (32, 48)
    assert shapes["layer1/moe/w1"] == (4, 32, 16)
    # no width of its own: the experts take d_ff's, as they always have
    assert dataclasses.replace(config, d_expert=0).expert_width == 48
