"""Kimi Linear's layers through the model, the cache and the server, against
the plain reference (``perfbench/reference/kimi_linear.py``), at a small size
in float32 on the CPU, LOGITS and not tokens: a gated delta rule with a decay
a key channel behind short convolutions (two states a layer), latent
attention without rotary whose cache keeps one row a position (absorbed in a
round, expanded in a prefill), three to one, over one chip's share of the
routed experts beside a shared one.
"""

import dataclasses
import functools
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
    LayerSpec, Transformer, TransformerConfig, transformer_rule)
from parameter_server_distributed_tpu.ops.delta_attention import (  # noqa: E402
    gated_delta_rule)
from perfbench import correct  # noqa: E402
from perfbench.families import kimi_linear  # noqa: E402
from perfbench.reference import kimi_linear as reference  # noqa: E402

SEQ = 72
CLOSE = 5e-5    # float32 logits of the program against the reference's
FILE = os.path.join(ROOT, "perfbench", "configs",
                    "kimi-linear-48b-a3b-12l-ep8.json")


def _configuration(**changes) -> dict:
    with open(FILE) as handle:
        config = kimi_linear.tiny(json.load(handle))
    config.update(changes)
    return config


def _small(**changes):
    """(configuration, model, weights, the reference's weights)."""
    config = _configuration(**changes)
    model = kimi_linear.model(config)
    params = kimi_linear.make_weights(model, 3)
    return config, model, params, kimi_linear.reference_weights(config,
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
    return np.asarray(jax.jit(lambda w, t: kimi_linear.reference_forward(
        config, w, t))(weights, tokens))


@pytest.fixture(scope="module")
def expected(small, tokens):
    return _expected(small, tokens)


def _reference_logits(small, sequence):
    return _expected(small, np.asarray(sequence, np.int32)[None])[0]


# ------------------------------------------------------------ the model
def test_the_model_is_kda_three_to_one_with_latent_attention(small):
    config, model, params, _ = small
    c = model.config
    assert [(s.mixer, s.ffn) for s in c.prologue] == [("kda", "mlp")]
    assert [(c.layer_spec(i).mixer, c.layer_spec(i).ffn)
            for i in range(6)] == [
        ("kda", "mlp"), ("kda", "experts"), ("kda", "experts"),
        ("latent", "experts"), ("kda", "experts"), ("kda", "experts")]
    assert c.state_layers == (0, 1, 2, 4, 5)
    assert c.layers_of("latent") == (3,)
    assert (c.kv_latent, c.qk_shared, c.conv_kernel, c.latent_row) == (
        32, 8, 4, 128)
    assert (c.moe_experts, c.moe_held, c.moe_top_k, c.moe_shared_experts,
            c.moe_score, c.moe_route_scale) == (16, (4, 4), 3, 1, "sigmoid",
                                                2.446)
    for name, shape in {
            "layer0/attn/wq": (64, 64), "layer0/attn/conv_k": (4, 64),
            "layer0/attn/decay/wa": (64, 16),
            "layer0/attn/decay/wb": (16, 64),
            "layer0/attn/decay/a_log": (4,),
            "layer0/attn/decay/dt_bias": (64,),
            "layer0/attn/gate/wb": (16, 64), "layer0/attn/beta/w": (64, 4),
            "layer0/attn/o_norm/scale": (16,), "layer0/mlp/w1": (64, 96),
            "layer3/attn/wq": (64, 4 * 24), "layer3/attn/wkv_a": (64, 40),
            "layer3/attn/kv_norm/scale": (32,),
            "layer3/attn/wkv_b": (32, 128), "layer3/attn/wo": (64, 64),
            "layer3/moe/w1": (4, 64, 32),
            "layer3/moe/router/w": (64, 16)}.items():
        assert params[name].shape == shape, name
    assert "layer3/attn/wk" not in params
    assert model.num_params() == kimi_linear.param_count(config)
    # the decays a seed draws lie where a trained model's do
    rate = np.exp(np.asarray(params["layer1/attn/decay/a_log"]))
    step = np.log1p(np.exp(np.asarray(params["layer1/attn/decay/dt_bias"])))
    assert 0.5 <= rate.min() and rate.max() <= 1.0
    assert 0.0019 < step.min() and step.max() < 0.101


def test_the_published_cut_counts_its_parameters():
    with open(FILE) as handle:
        config = json.load(handle)
    model = kimi_linear.model(config)
    assert model.num_params() == kimi_linear.param_count(config) \
        == 3_176_867_744 == config["parameters"]
    shapes = model.param_shapes()
    assert shapes["layer1/moe/w1"] == (32, 2304, 1024)
    assert shapes["layer1/moe/router/w"] == (2304, 256)
    assert shapes["layer3/attn/wkv_b"] == (512, 8192)
    assert shapes["layer0/attn/conv_q"] == (4, 4096)
    assert generation.state_shape(model)[0] == (
        ((3, 12288), jnp.bfloat16), ((32, 128, 128), jnp.float32))
    assert len(generation.state_shape(model)) == 9
    assert model.config.latent_row == 640
    assert model.flops_per_sample() > 0


def test_forward_against_the_reference(small, tokens, expected):
    _, model, params, _ = small
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    assert np.max(np.abs(got - expected)) < CLOSE
    assert float(np.std(expected)) > 0.5


@pytest.mark.parametrize("prompt", [1, 3, 17, 40])
def test_prefill_then_decode_through_the_cache(small, tokens, expected,
                                               prompt):
    """Every position's logits: the prompt whole, then a token a round
    against both states and the latent rows (the absorbed form)."""
    _, model, params, _ = small
    logits, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, SEQ))(params, tokens[:, :prompt])
    assert np.max(np.abs(logits - expected[:, prompt - 1])) < CLOSE
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for i in range(prompt, SEQ):
        logits, cache = step(params, tokens[:, i], cache)
        assert np.max(np.abs(logits - expected[:, i])) < CLOSE, i
    kinds = cache.nbytes_by_kind()
    assert kinds["latent"] == 2 * SEQ * 128 * 4
    assert kinds["state"] == 2 * 5 * (3 * 192 + 4 * 16 * 16) * 4
    assert kinds["full"] == kinds["window"] == 0


# -------------------------------------------------------- the delta rule
def _recurrence(q, k, v, g, beta, state):
    """The delta rule a position at a time, in float64 numpy."""
    batch, t, heads, _ = q.shape
    state = np.array(state, np.float64)
    out = np.zeros(v.shape, np.float64)
    for b in range(batch):
        for h in range(heads):
            for i in range(t):
                fallen = np.exp(g[b, i, h])[:, None] * state[b, h]
                u = v[b, i, h] - fallen.T @ k[b, i, h]
                state[b, h] = fallen + beta[b, i, h] * np.outer(k[b, i, h], u)
                out[b, i, h] = state[b, h].T @ q[b, i, h]
    return out, state


def _delta_inputs(t, low, high, seed=0):
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(2, t, 3, 8)) for _ in range(2))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    return (q, k, rng.normal(size=(2, t, 3, 8)),
            rng.uniform(low, high, (2, t, 3, 8)),
            rng.uniform(0, 1, (2, t, 3)), rng.normal(size=(2, 3, 8, 8)))


@pytest.mark.parametrize("chunk", [1, 4, 16, 64])
@pytest.mark.parametrize("decays", ["trained", "near_one", "near_zero"])
def test_the_chunked_delta_rule_is_the_recurrence(chunk, decays):
    """Across chunk edges (37 positions), against a state that came in,
    for channels that keep nearly everything and channels that forget
    everything in a position (log-decays of -30: a cumulative decay would
    underflow to 0 and dividing by it overflow; differences do neither)."""
    low, high = {"trained": (-0.13, -0.0007), "near_one": (-1e-4, -1e-6),
                 "near_zero": (-30.0, -5.0)}[decays]
    q, k, v, g, beta, state = _delta_inputs(37, low, high)
    want, after = _recurrence(q, k, v, g, beta, state)
    got, kept = gated_delta_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                                 jnp.asarray(state, jnp.float32),
                                 chunk=chunk)
    assert np.max(np.abs(got - want)) < 2e-6
    assert np.max(np.abs(kept - after)) < 2e-6
    assert np.all(np.isfinite(got))


@pytest.mark.parametrize("counts", [(20, 37), (13, 10), (3, 1)])
def test_pads_stay_out_of_the_state(counts):
    """Pads inside a chunk, and (13, 10 and 3, 1 of 37 in chunks of 8)
    whole chunks of pads, which are skipped: a turn in a block of 256."""
    q, k, v, g, beta, state = _delta_inputs(37, -0.13, -0.0007, seed=1)
    got, kept = gated_delta_rule(*map(jnp.asarray, (q, k, v, g, beta)),
                                 jnp.asarray(state, jnp.float32),
                                 jnp.asarray(counts), chunk=8)
    for row, n in enumerate(counts):
        cut = [x[row:row + 1, :n] for x in (q, k, v, g, beta)]
        want, after = _recurrence(*cut, state[row:row + 1])
        assert np.max(np.abs(got[row, :n] - want[0])) < 2e-6
        assert np.max(np.abs(kept[row] - after[0])) < 2e-6
    assert np.all(np.isfinite(got))


def test_the_delta_rules_gradients_are_the_recurrences():
    """No cell trains it: the test holds it.  The chunked form's gradients
    against those of a chunk of one position (``lax.scan`` of the
    recurrence itself)."""
    inputs = [jnp.asarray(x, jnp.float32)
              for x in _delta_inputs(21, -0.13, -0.0007, seed=2)]

    def total(chunk, *args):
        out, state = gated_delta_rule(*args, chunk=chunk)
        return jnp.sum(out * out) + jnp.sum(state * state)

    chunked = jax.grad(lambda *a: total(8, *a), argnums=range(6))(*inputs)
    stepped = jax.grad(lambda *a: total(1, *a), argnums=range(6))(*inputs)
    for ours, theirs in zip(chunked, stepped):
        assert float(jnp.max(jnp.abs(theirs))) > 0
        assert np.max(np.abs(ours - theirs)) < 2e-4 * (
            1 + float(jnp.max(jnp.abs(theirs))))


# ------------------------------------------------------ latent attention
def test_absorbed_decode_is_expanded_prefill(small, tokens, monkeypatch):
    """One block of 16 tokens against a cached prefix of 40, both ways:
    the rows read as they lie with the expansion absorbed into the query
    and the output, and K and V expanded from the rows and run blockwise
    (which a long block against a long cache takes)."""
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
    assert np.max(np.abs(absorbed)) > 1.0


def test_the_decode_kernel_is_the_plain_absorbed_attention(monkeypatch):
    """ops/pallas/latent_decode.py (interpreted here) against the plain
    form: alone, over lanes that hold one position, a block exactly, and a
    block and a half; and inside a decode round of a model of 16 heads,
    where the rule takes it for a single token a lane."""
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import latent_decode

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(3, 16, 128)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(3, 2048, 128)), jnp.float32)
    lengths = jnp.asarray([1, 1024, 1500])
    assert latent_decode.fits(q.shape, rows.shape)
    assert not latent_decode.fits((3, 4, 128), rows.shape)
    assert not latent_decode.fits(q.shape, (3, 2000, 128))
    got = latent_decode.latent_decode_attention(q, rows, lengths, 0.1)
    scores = jnp.einsum("bhw,bmw->bhm", q, rows) * 0.1
    live = jnp.arange(2048)[None, None, :] < lengths[:, None, None]
    want = jnp.einsum("bhm,bmw->bhw", jax.nn.softmax(
        jnp.where(live, scores, -jnp.inf), -1), rows)
    assert np.max(np.abs(got - want)) < 2e-6

    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=16, head_dim=8, n_layers=2, d_ff=48,
        max_seq=1024, dtype=jnp.float32, kv_latent=24, qk_shared=8,
        pattern=(LayerSpec(mixer="latent"),))
    model = Transformer(config)
    params = model.init_params(2)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 20)))
    _, cache = generation.prefill(model, params, tokens[:, :19], 1024)

    def round_():
        return np.asarray(generation.decode_block(
            model, params, tokens[:, 19:], cache,
            lengths=jnp.asarray([19, 19]))[0])

    plain = round_()
    arm = functools.partial(transformer.round_arm, "latent")
    assert arm((2, 1, 16, 128), (2, 1024, 128)) == "dense"      # no TPU
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert np.max(np.abs(round_() - plain)) < 1e-5
    assert np.max(np.abs(plain)) > 0.1
    # one place says which implementation runs: a round's token on a TPU
    # takes the kernel or is refused; a block of several takes the einsums
    assert arm((2, 1, 16, 128), (2, 1024, 128)) == "kernel"
    assert arm((2, 4, 16, 128), (2, 1024, 128)) == "dense"
    for q, rows in (((2, 1, 16, 128), (2, 1000, 128)),
                    ((2, 1, 12, 128), (2, 1024, 128)),
                    ((2, 1, 16, 96), (2, 1024, 96))):
        with pytest.raises(ValueError, match="latent_decode.py"):
            arm(q, rows)
    _, short = generation.prefill(model, params, tokens[:, :19], 1000)
    with pytest.raises(ValueError, match="whole blocks of 1024"):
        generation.decode_block(model, params, tokens[:, 19:], short,
                                lengths=jnp.asarray([19, 19]))


@pytest.mark.parametrize("heads", [16, 12])
def test_a_latent_round_over_a_mesh_takes_the_einsums(monkeypatch, heads):
    """A latent layer's round against a cache spread over four devices, on
    a TPU backend: GSPMD cannot cut a kernel, so the round takes the plain
    form, whether the kernel would have taken the shapes (16 heads) or not
    (12), and refuses nothing; on ONE device the shapes the kernel does not
    take are refused, as ever.  (Before PR 59 the latent layer's chooser
    was never told the devices: it took the kernel, or refused.)"""
    from parameter_server_distributed_tpu.models import transformer

    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=heads, head_dim=8, n_layers=2,
        d_ff=48, max_seq=1024, dtype=jnp.float32, kv_latent=24, qk_shared=8,
        pattern=(LayerSpec(mixer="latent"),))
    model = Transformer(config)
    params = model.init_params(2)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 64, (2, 20)))
    _, cache = generation.prefill(model, params, tokens[:, :19], 1024)
    calls = []
    monkeypatch.setattr(
        "parameter_server_distributed_tpu.ops.pallas.latent_decode."
        "latent_decode_attention", lambda *args: calls.append(1))

    def round_(devices):
        return np.asarray(generation.decode_block(
            model, params, tokens[:, 19:],
            dataclasses.replace(cache, devices=devices),
            lengths=jnp.asarray([19, 19]))[0])

    plain = round_(1)                                           # no TPU
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert transformer.round_arm("latent", (2, 1, heads, 128),
                                 (2, 1024, 128), devices=4) == "dense"
    assert np.array_equal(round_(4), plain) and not calls
    if heads == 12:
        with pytest.raises(ValueError, match="latent_decode.py, which takes "
                           "heads in 16s, rows of whole 128-lane registers "
                           "and a cache of whole blocks of 1024 positions; "
                           "got queries"):
            round_(1)


def test_a_latent_part_is_one_row_a_position_in_whole_registers(small):
    _, model, _, _ = small
    cache = generation.init_cache(model, 3, 32)
    assert [x.shape for x in cache.latent] == [(3, 32, 128)]
    assert cache.latent_layers == (3,) and cache.k == () == cache.v
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((3, 3, 192), jnp.float32), ((3, 4, 16, 16), jnp.float32)]] * 5
    with pytest.raises(ValueError, match="native cache"):
        generation.init_cache(model, 3, 32, "int8")
    with pytest.raises(ValueError, match="cannot be rolled back"):
        generation.check_rolls_back(model)


# -------------------------------------------------------------- serving
def _served(small, prompts, new=12, max_len=128):
    _, model, params, _ = small
    srv = serving.DecodeServer(model, params, slots=4, max_len=max_len,
                               prompt_cache=8, prefix_cache_bytes=1 << 24)
    out = []
    for prompt in prompts:
        rid = srv.submit(prompt, max_new_tokens=new)
        out.append(srv.run_to_completion()[rid])
    return srv, out


def test_an_extension_against_a_restored_row_and_snapshot(small):
    """A resident context, then the context + a turn: the admission
    restores the latent layer's rows and five layers' two states at the
    node's end and forwards only the turn; every served token is the
    reference's argmax over the uncut sequence."""
    rng = np.random.default_rng(5)
    system = rng.integers(0, 512, 50)
    turn = np.concatenate([system, rng.integers(0, 512, 21)])
    srv, (_, served) = _served(small, [system, turn])
    stats = srv.stats
    assert stats["prefix_hits"] == 1 and stats["prefill_tokens"] == 50 + 21
    node, matched, _ = srv._prefix_tree.lookup(tuple(turn.tolist()))
    assert matched == 71 and node.handle.state_at == 71
    row = node.handle.row
    # no layer keeps K/V; one latent layer's rows; five layers x two states;
    # a kda model's smallest suffix bucket (256) does not fit this lane of
    # 128 beside the prefix's 64, so the turn of 21 tokens takes its own
    assert serving._suffix_floor(small[1]) == 256
    assert not srv._ahead
    assert row[0].shape == (0, 64 + 32, 1, 1) and len(row) == 2 + 1 + 10
    assert row[2].shape == (1, 64 + 32, 128)
    assert [x.shape for x in row[3:5]] == [(3, 192), (4, 16, 16)]
    logits = _reference_logits(small, np.concatenate([turn, served]))
    assert served == np.argmax(logits[70:82], -1).tolist()
    assert np.max(np.abs(np.asarray(node.last) - logits[70])) < CLOSE
    assert stats["cache_latent_bytes"] == 4 * 128 * 128 * 4
    assert stats["cache_state_bytes"] == 4 * 5 * (3 * 192 + 4 * 256) * 4


def test_every_turn_shares_the_program_built_beside_the_prefill(small):
    """In a lane that holds it, a kda model's suffix bucket is 256 whatever
    the turn: ONE extension program a prefix bucket, started on a thread of
    its own when the PREFILL puts the context into the tree (a row that an
    extension made starts nothing); the first turn waits for it; every
    served token is the reference's argmax over the uncut sequence."""
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    def programs():
        return obs_stats.REGISTRY.snapshot()["counters"].get(
            "serve.programs", 0)

    rng = np.random.default_rng(15)
    system = rng.integers(0, 512, 50)
    turns = [np.concatenate([system, rng.integers(0, 512, n)])
             for n in (5, 21, 40)]
    _, model, params, _ = small
    srv = serving.DecodeServer(model, params, slots=4, max_len=512,
                               prompt_cache=8, prefix_cache_bytes=1 << 24)
    rid = srv.submit(system, max_new_tokens=1)
    srv.run_to_completion()
    assert list(srv._ahead) == [(64, 256)]
    before = None
    for turn in turns:
        rid = srv.submit(turn, max_new_tokens=6)
        served = srv.run_to_completion()[rid]
        logits = _reference_logits(small, np.concatenate([turn, served]))
        at = len(turn) - 1
        assert served == np.argmax(logits[at:at + 6], -1).tolist()
        node, _, _ = srv._prefix_tree.lookup(tuple(turn.tolist()))
        assert node.handle.row[2].shape == (1, 64 + 256, 128)
        # the first turn built the splice of its row's width; no turn
        # after it builds anything
        assert before is None or programs() == before
        before = programs()
    assert not srv._ahead[64, 256].is_alive()
    assert list(srv._ahead) == [(64, 256)]
    assert srv.stats["prefix_hits"] == 3


def test_a_prompt_prefilled_in_chunks_carries_both_states(small,
                                                          monkeypatch):
    """Chunks of 32 positions against the row so far (the path every
    context of 4,096 tokens or more takes at the published widths: ONE
    program for every such length, a row filled a lane wide and cut to its
    bucket), then a turn against the row it left."""
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    monkeypatch.setattr(serving, "_PREFILL_CHUNK", 32)
    _, model, _, _ = small
    assert serving._builds_few(model)
    assert serving._prefills_whole(model, 16)
    assert not serving._prefills_whole(model, 32)
    assert not serving._prefills_whole(model, 64)
    rng = np.random.default_rng(6)
    long = rng.integers(0, 512, 100)
    turn = np.concatenate([long, rng.integers(0, 512, 5)])
    srv, (first, second) = _served(small, [long, turn], new=8, max_len=256)
    assert srv.stats["prefix_hits"] == 1
    node, _, _ = srv._prefix_tree.lookup(tuple(long.tolist()))
    assert node.handle.row[2].shape == (1, 128, 128)   # its bucket's worth
    # another length, the same prefill program: nothing heavy is built
    # (the cut of its row to 64 positions and a splice are)
    before = dict(generation._RUNNERS)
    other = rng.integers(0, 512, 40)
    rid = srv.submit(other, max_new_tokens=8)
    third = srv.run_to_completion()[rid]
    built = [key[1:] for key in generation._RUNNERS if key not in before]
    assert {key[0] for key in built} <= {"serve_splice"}, built
    logits = _reference_logits(small, np.concatenate([other, third]))
    assert third == np.argmax(logits[39:47], -1).tolist()
    for prompt, served in ((long, first), (turn, second)):
        logits = _reference_logits(small, np.concatenate([prompt, served]))
        at = len(prompt) - 1
        assert served == np.argmax(logits[at:at + 8], -1).tolist()


def test_the_counters_count_states_and_live_rows(small):
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    def read():
        counters = obs_stats.REGISTRY.snapshot()["counters"]
        return {name: counters.get(name, 0) for name in (
            "serve.linear.state_updates", "serve.latent.positions_read",
            "serve.latent.positions_cached")}

    before = read()
    srv, _ = _served(small, [np.arange(1, 20)], new=6)
    moved = {name: value - before[name] for name, value in read().items()}
    rounds = srv.stats["steps"]
    # five kda layers x four lanes a round; one latent layer's part whole
    assert moved["serve.linear.state_updates"] >= rounds * 5 * 4
    assert moved["serve.latent.positions_cached"] % (4 * 128) == 0
    assert 0 < moved["serve.latent.positions_read"] \
        < moved["serve.latent.positions_cached"]
    gauges = obs_stats.REGISTRY.snapshot()["gauges"]
    assert gauges["serve.cache.latent_bytes"] == 4 * 128 * 128 * 4


# ------------------------------------------- states of more than one shape
@pytest.mark.parametrize("pattern", [
    ("linear", "conv"), ("conv", "kda"), ("kda", "linear", "softmax")])
def test_state_layers_of_different_shapes_in_one_model(pattern):
    """What a row's snapshot used to refuse ("linear beside conv"): every
    state layer keeps a tuple of states of its own shapes, in the cache,
    in the row and in the tree; served through a resident prefix exactly
    as ``generate`` decodes."""
    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=len(pattern) + 1, d_ff=48,
        max_seq=128, dtype=jnp.float32, conv_kernel=3,
        pattern=tuple(LayerSpec(mixer=m, rope=m == "softmax")
                      for m in pattern))
    model = Transformer(config)
    params = model.init_params(1)
    shapes = generation.state_shape(model)
    assert len(shapes) == len(config.state_layers)
    assert len({layer for layer in shapes}) > 1
    rng = np.random.default_rng(7)
    system = rng.integers(0, 64, 30)
    turn = np.concatenate([system, rng.integers(0, 64, 9)])
    srv = serving.DecodeServer(model, params, slots=2, max_len=128,
                               prompt_cache=8, prefix_cache_bytes=1 << 22)
    for prompt in (system, turn):
        rid = srv.submit(prompt, max_new_tokens=6)
        served = srv.run_to_completion()[rid]
        want = generation.generate(model, params,
                                   jnp.asarray(prompt)[None], 6)
        assert served == np.asarray(want)[0].tolist()
    assert srv.stats["prefix_hits"] == 1


# ------------------------------------------------------------ the experts
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """PR 40's test, one more case: 256 outputs cut to 16 here, eight
    ranks of two experts, top-3, the shared expert counted once."""
    rng = np.random.default_rng(8)
    d, width, experts, ranks = 24, 12, 16, 8
    w = {"x": rng.normal(size=(40, d)),
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
        whole, _ = reference.expert_layer(w["x"], w, 3, 2.446)
        shared = reference._swiglu(w["x"], w["shared_w1"], w["shared_w3"],
                                   w["shared_w2"])
        total = 0.0
        for rank in range(ranks):
            first = rank * count
            part, loads = moe.dropless_experts(
                w["x"], w["x"] @ w["router"],
                w["w1"][first:first + count], w["w2"][first:first + count],
                w["w3"][first:first + count], top_k=3, act="swiglu",
                score="sigmoid", bias=w["bias"], scale=2.446,
                held=(first, count))
            assert loads.shape == (count + 1,)
            assert int(loads.sum()) == 40 * 3
            total = total + part
    assert np.max(np.abs(total + shared - whole)) < 1e-4
    assert float(jnp.max(jnp.abs(whole))) > 0.5


def test_the_reference_under_the_programs_selection(small, tokens, expected):
    config, model, params, weights = small
    arguments = kimi_linear._reference_arguments(config)
    chosen = jax.jit(model.expert_selections)(params, tokens)
    assert [x.shape for x in chosen] == [(2, SEQ, 3)] * 5
    seen = []
    given = jax.jit(lambda w, t, s: reference.forward(
        w, t, selection=s, report=seen.append, **arguments))(
            weights, tokens, chosen)
    own = jax.jit(lambda w, t: reference.forward(w, t, **arguments))(
        weights, tokens)
    assert np.max(np.abs(np.asarray(given) - np.asarray(own))) < CLOSE
    assert np.max(np.abs(np.asarray(given) - expected)) < CLOSE


def test_the_check_reads_the_selection_and_the_first_state(small, tokens,
                                                           expected,
                                                           monkeypatch):
    """What ``reference_forward`` judges by, at the tiny size in float32:
    the program's selection is the reference's own, every KDA layer's
    matrix state after the last token is the reference's scan's, and a
    matrix state kept at bfloat16's mantissa (in the reference: the
    comparison cannot tell whose fault it is) is NOT a number, by the
    state's limit alone; no host callback keeps the reference's program
    out of the compile cache."""
    config, _, _, weights = small
    read = jax.jit(lambda w, t: kimi_linear.reference_readings(
        config, w, t))
    logits, compared, apart = read(weights, tokens)
    assert np.max(np.abs(np.asarray(logits) - expected)) < CLOSE
    assert compared.shape == (5, 2, 2) and float(jnp.max(compared)) == 0.0
    assert apart.shape == (5,) and float(jnp.max(apart)) < 1e-5
    assert "callback" not in read.lower(weights, tokens).as_text()
    faults = {"kda": {"state_bits": 7}}
    _, compared, apart = jax.jit(
        lambda w, t: kimi_linear.reference_readings(config, w, t, faults))(
            weights, tokens)
    assert float(jnp.max(compared[..., 1])) < kimi_linear.SELECTION_MARGIN
    # (heads of 16 over 96 positions: a hundred times the sound reading;
    # the limit itself is read on the chip at the published widths)
    assert float(apart[0]) > 1e-3
    monkeypatch.setattr(kimi_linear, "STATE_TOLERANCE", 1e-3)
    refused = jax.jit(lambda w, t: kimi_linear.reference_forward(
        config, w, t, faults))(weights, tokens)
    assert bool(jnp.all(jnp.isnan(refused)))


@pytest.mark.parametrize("control", [
    "bf16_state", "no_delta_term", "latent_not_normed", "sqrt_128",
    "another_share", "no_conv"])
def test_the_controls_are_far_from_the_reference(small, tokens, expected,
                                                 control):
    """Each of the faults the chip's controls use moves the float32
    reference's logits by far more than CLOSE: the comparison sees them."""
    config, _, _, weights = small
    arguments = kimi_linear._reference_arguments(config)
    faults = {"bf16_state": {"kda": {"state_bits": 7}},
              "no_delta_term": {"kda": {"delta": False}},
              "latent_not_normed": {"mla": {"normed": False}},
              "sqrt_128": {"mla": {"scale_dim": 16}}}.get(control)
    if control == "another_share":
        arguments["held"] = (0, 4)
    if control == "no_conv":
        weights = dict(weights, layers=[
            {name: (jnp.zeros_like(value).at[-1].set(1.0)
                    if name.startswith("conv_") else value)
             for name, value in layer.items()}
            for layer in weights["layers"]])
    got = np.asarray(jax.jit(lambda w, t: reference.forward(
        w, t, faults=faults, **arguments))(weights, tokens))
    error, worst = correct.logits_errors(got, expected)
    assert error > (3e-4 if control == "bf16_state" else 0.01)
    assert worst > 100 * CLOSE


def test_the_loss_and_its_gradient_against_the_reference(small, tokens):
    config, model, params, weights = small
    tokens = tokens[:, :64]
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, _), ref_grads = correct.reference_backward(config)(
        weights, tokens)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 1e-5
    error, cosine = correct.gradient_errors(
        jax.tree.map(np.asarray, kimi_linear.reference_weights(config,
                                                               grads)),
        ref_grads)
    assert error < 2e-3 and cosine > 0.99999
    for name in ("layer0/attn/decay/a_log", "layer1/attn/decay/dt_bias",
                 "layer1/attn/conv_k", "layer2/attn/beta/w",
                 "layer2/attn/gate/wa", "layer3/attn/wkv_a",
                 "layer3/attn/wkv_b", "layer3/attn/kv_norm/scale",
                 "layer3/moe/shared/w1"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name


# ------------------------------------------------------- rules and refusals
def test_the_new_leaves_have_a_sharding_rule():
    from jax.sharding import PartitionSpec

    from parameter_server_distributed_tpu.parallel.mesh import (
        MeshConfig, build_mesh)

    mesh = build_mesh(MeshConfig(expert=2, fsdp=2, tensor=2))
    rule = transformer_rule(mesh)
    # by head along the outputs, like wq
    for name, shape in (("layer3/attn/wkv_b", (32, 128)),
                        ("layer0/attn/decay/wb", (16, 64)),
                        ("layer0/attn/gate/wb", (16, 64))):
        assert rule(name, shape) == rule("layer0/attn/wq", shape), name
    for name, shape in (("layer0/attn/conv_q", (4, 64)),
                        ("layer0/attn/decay/a_log", (4,)),
                        ("layer0/attn/decay/dt_bias", (64,)),
                        ("layer0/attn/beta/w", (64, 4)),
                        ("layer3/attn/wkv_a", (64, 40)),
                        ("layer3/attn/kv_norm/scale", (32,))):
        assert rule(name, shape) == PartitionSpec(), name


@pytest.mark.parametrize("fields,message", [
    (dict(pattern=(LayerSpec(mixer="latent"),)), "kv_latent"),
    (dict(pattern=(LayerSpec(mixer="kda"),), bias=True), "no bias"),
    (dict(pattern=(LayerSpec(mixer="kda"),), conv_kernel=1), "2 taps"),
    (dict(pattern=(LayerSpec(mixer="kda"),), scan_layers=True),
     "run unrolled"),
])
def test_configurations_the_program_refuses(fields, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(d_model=32, n_heads=4, **fields)


@pytest.mark.parametrize("mixer", ["kda", "latent"])
def test_the_new_mixers_take_no_flag_of_attentions(mixer):
    with pytest.raises(ValueError, match="belong to"):
        LayerSpec(mixer=mixer, gate=True)
    with pytest.raises(ValueError, match="window belongs"):
        LayerSpec(mixer=mixer, window=8)


def test_a_draft_is_refused_by_name(small):
    _, model, params, _ = small
    with pytest.raises(ValueError, match="cannot be rolled back"):
        serving.DecodeServer(model, params, slots=2, max_len=64,
                             draft=model, draft_params=params)
    assert dataclasses.replace(model.config, moe_held=()).held_experts == (
        0, 16)
