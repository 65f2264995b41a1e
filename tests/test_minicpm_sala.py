"""MiniCPM-SALA's two mixers through the model, the cache and the server,
against the plain reference (``perfbench/reference/minicpm_sala.py``), at a
small size in float32 on the CPU: widths cut, ``dense_len`` 64, blocks of 8,
kernels of 4 every 2, a window of 16 and a top-4, so that a 200-token
sequence crosses every rule (dense to selected at position 64, the window
leaving block 0 behind, more candidate blocks than the top-k takes).
"""

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
    generation, serving)
from parameter_server_distributed_tpu.ops import (  # noqa: E402
    linear_attention as la, sparse_attention as sa)
from perfbench.families import minicpm_sala  # noqa: E402
from perfbench.reference import minicpm_sala as reference  # noqa: E402

SPARSE = dict(kernel_size=4, kernel_stride=2, block_size=8, init_blocks=1,
              window_size=16, topk=4, dense_len=64)
SEQ = 200


@pytest.fixture(scope="module")
def small():
    """(configuration, model, weights, the reference's weights)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm-sala-12l.json")) as handle:
        config = minicpm_sala.tiny(json.load(handle))
    config["sparse_config"] = dict(SPARSE)
    config["max_position_embeddings"] = 256
    model = minicpm_sala.model(config)
    params = minicpm_sala.make_weights(model, 3)
    return config, model, params, minicpm_sala.reference_weights(config,
                                                                 params)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, SEQ)).astype(
        np.int32)


@pytest.fixture(scope="module")
def expected(small, tokens):
    """The reference's logits under its OWN selection."""
    config, _, _, weights = small
    return np.asarray(jax.jit(lambda w, t: minicpm_sala.reference_loss(
        config, w, t)[1])(weights, tokens))


def test_the_pattern_is_the_two_mixers_with_their_own_heads(small):
    config, model, params, _ = small
    kinds = [(s.mixer, s.kv_heads, s.rope, s.qk_norm, s.gate, s.out_norm)
             for s in model.config.period]
    assert kinds == [("sparse", 0, False, True, True, False),
                     ("linear", 4, True, True, True, True),
                     ("linear", 4, True, True, True, True),
                     ("sparse", 0, False, True, True, False)]
    assert params["layer0/attn/wk"].shape == (64, 2 * 16)
    assert params["layer1/attn/wk"].shape == (64, 4 * 16)
    assert "layer1/attn/o_norm/scale" in params
    assert "layer0/attn/o_norm/scale" not in params
    assert model.config.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert model.config.logit_scale == pytest.approx(16 / 64)
    assert model.num_params() == minicpm_sala.param_count(config)


def test_forward_against_the_reference(small, tokens, expected):
    _, model, params, _ = small
    got = jax.jit(model.apply)(params, tokens)
    assert np.max(np.abs(np.asarray(got) - expected)) < 2e-6
    assert np.std(expected) > 0.01


def test_the_program_selects_what_the_reference_selects(small, tokens,
                                                        capfd):
    """The comparison of the chip's ``correct``: the program's block masks
    given to the reference, which reports where they differ from its own."""
    config, model, params, weights = small
    chosen = jax.jit(model.sparse_selections)(params, tokens)
    # (keys padded to the 512 a step meets: 64 blocks of 8)
    assert [c.shape for c in chosen] == [(2, 2, SEQ, 512 // 8)] * 2
    per_query = np.asarray(chosen[0][0, 0, :, :SEQ // 8]).sum(-1)
    # dense under dense_len: every causal block; then 1 + 2 + 4 blocks
    assert per_query[62] == 8 and set(per_query[63:]) == {7}
    logits = jax.jit(lambda w, t: minicpm_sala.reference_forward(
        config, w, t))(weights, tokens)
    assert np.all(np.isfinite(np.asarray(logits)))
    line = json.loads(next(l for l in capfd.readouterr().out.splitlines()
                           if "selection_check" in l))
    assert line["queries_selecting"] == [2 * (SEQ - 63)] * 2
    assert line["queries_with_a_flip_pct"] == [0.0, 0.0]


def test_a_selection_by_the_wrong_kernels_is_not_a_number(small, tokens):
    """Comparison (a): blocks far from the reference's cut fail it."""
    config, model, params, weights = small
    chosen = jax.jit(model.sparse_selections)(params, tokens)
    wrong = [jnp.roll(c, 3, axis=-1)[..., :SEQ // 8] for c in chosen]
    seen = []
    reference.forward(weights, tokens, selection=wrong, report=seen.append,
                      **minicpm_sala._reference_arguments(config))
    assert float(jnp.max(seen[0][..., 2])) > minicpm_sala.SELECTION_MARGIN


@pytest.mark.parametrize("prompt", [40, 64, 100])
def test_prefill_then_decode_through_the_cache(small, tokens, expected,
                                               prompt):
    """Across the ``dense_len`` crossing (prompt 40), from it, and past it."""
    _, model, params, _ = small

    @jax.jit
    def run(params, tokens):
        logits, cache = generation.prefill(model, params, tokens[:, :prompt],
                                           208)

        def body(cache, token):
            step, cache = generation.decode_step(model, params, token, cache)
            return cache, step

        _, steps = jax.lax.scan(body, cache, tokens[:, prompt:].T)
        return logits, jnp.moveaxis(steps, 0, 1)

    first, rest = run(params, tokens)
    assert np.max(np.abs(first - expected[:, prompt - 1])) < 2e-6
    assert np.max(np.abs(rest - expected[:, prompt:])) < 2e-6


def test_chunked_linear_attention_is_the_recurrence():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 50, 4, 16)), jnp.float32)
               for _ in range(3))
    want = jnp.stack([reference.lightning(q[b], k[b], v[b])
                      for b in range(2)]) * 4.0
    step, state_step = la.linear_attention(q, k, v, chunk=1)
    chunked, state = la.linear_attention(q, k, v, chunk=16)
    assert np.allclose(step, want, rtol=1e-5, atol=1e-4)
    assert np.allclose(chunked, want, rtol=1e-5, atol=1e-4)
    assert np.allclose(state, state_step, rtol=1e-5, atol=1e-4)
    # a block against the state before it, and pads that stay out of it
    head, mid = la.linear_attention(q[:, :30], k[:, :30], v[:, :30], chunk=16)
    padded = [jnp.pad(x[:, 30:], ((0, 0), (0, 12), (0, 0), (0, 0)),
                      constant_values=7.0) for x in (q, k, v)]
    tail, end = la.linear_attention(*padded, state=mid,
                                    counts=jnp.asarray([20, 20]), chunk=16)
    assert np.allclose(jnp.concatenate([head, tail[:, :20]], 1), want,
                       rtol=1e-5, atol=1e-4)
    assert np.allclose(end, state, rtol=1e-5, atol=1e-4)


def test_compressed_keys_whole_and_one_at_a_time():
    spec = minicpm_sala.sparse_spec({"sparse_config": SPARSE})
    k = jnp.asarray(np.random.default_rng(2).normal(size=(3, 2, 40, 16)),
                    jnp.float32)
    whole = sa.compress_keys(k, spec)
    assert whole.shape == (3, 2, 20, 16)
    for i in (0, 7, 18):
        assert np.allclose(whole[:, :, i], k[:, :, 2 * i:2 * i + 4].mean(2),
                           atol=1e-6)
    index, key = sa.completed_key(k, jnp.asarray([3, 4, 19]), spec)
    assert index.tolist() == [21, 0, 21]      # only 4 completes a kernel
    assert np.allclose(key[1], whole[1, :, 0], atol=1e-6)


def _server(small, **kwargs):
    _, model, params, _ = small
    return serving.DecodeServer(model, params, slots=4, max_len=208,
                                **kwargs)


def _greedy(expected_row, prompt_len, n):
    return np.argmax(expected_row[prompt_len - 1:prompt_len - 1 + n], -1)


def test_a_round_with_slots_under_and_over_dense_len(small):
    """One program, one round: a slot of 20 positions attends densely
    beside one of 100 that selects; each serves the reference's tokens."""
    config, model, params, weights = small
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32) for n in (20, 100)]
    server = _server(small)
    ids = [server.submit(p, max_new_tokens=12) for p in prompts]
    assert server.active == 2
    done = server.run_to_completion()
    for rid, prompt in zip(ids, prompts):
        served = np.asarray(done[rid])
        sequence = np.concatenate([prompt, served])[None]
        logits = np.asarray(minicpm_sala.reference_loss(
            config, weights, sequence)[1])[0]
        assert served.tolist() == _greedy(logits, len(prompt), 12).tolist()
    counters = {name: counter.value for name, counter
                in server._obs_mixers.items()}
    assert counters["serve.linear.state_updates"] >= 11 * 4 * 2
    assert 0 < counters["serve.sparse.positions_selected"] \
        < counters["serve.sparse.positions_cached"]


def _rows_close(a, b, n):
    """Two rows (k, v, a state each linear layer) agree on their first n
    positions."""
    for x, y in zip(a[:2], b[:2]):
        assert np.max(np.abs(np.asarray(x[:, :n]) - np.asarray(y[:, :n]))) \
            < 1e-5
    assert len(a) == len(b) > 2
    for x, y in zip(a[2:], b[2:]):
        assert np.max(np.abs(np.asarray(x) - np.asarray(y))) < 1e-5


def test_a_prefix_hit_restores_row_and_snapshot(small):
    """A document resident in the tree, then a turn after it: the
    extension starts from the row AND the snapshot of the linear layers'
    states, and ends where a cold prefill of the whole prompt ends."""
    rng = np.random.default_rng(6)
    document = rng.integers(0, 512, 90).astype(np.int32)
    turn = rng.integers(0, 512, 9).astype(np.int32)
    prompt = np.concatenate([document, turn])
    warm = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    warm.submit(document, max_new_tokens=1)
    rid = warm.submit(prompt, max_new_tokens=10)
    assert warm.stats["prefix_hits"] == 1
    assert warm.stats["prefill_tokens"] == 90 + 9
    cold = _server(small)
    cold_rid = cold.submit(prompt, max_new_tokens=10)
    node, matched, _ = warm._prefix_tree.lookup(tuple(prompt.tolist()))
    assert matched == 99 and node.handle.state_at == 99
    _, cold_row, _ = serving._prefill_runner(small[1], 128, "native")(
        small[2], jnp.asarray(np.pad(prompt, (0, 29))[None]),
        jnp.asarray(99, jnp.int32))
    _rows_close(node.handle.row, cold_row, 99)
    served = cold.run_to_completion()[cold_rid]
    assert warm.run_to_completion()[rid] == served
    # an identical prompt again: the row, the snapshot and the logits replay
    again = warm.submit(prompt, max_new_tokens=10)
    assert warm.stats["prompt_cache_hits"] == 1
    assert warm.run_to_completion()[again] == served


def test_a_match_into_an_edge_falls_back_to_the_snapshot(small):
    """Two turns that share their first tokens split the first turn's
    edge: the split node has K/V and no snapshot, so the second turn
    extends from the document's end, and is right."""
    rng = np.random.default_rng(7)
    document = rng.integers(0, 512, 80).astype(np.int32)
    shared = rng.integers(0, 512, 6).astype(np.int32)
    first = np.concatenate([document, shared, rng.integers(0, 512, 5)]
                           ).astype(np.int32)
    second = np.concatenate([document, shared, rng.integers(0, 512, 7)]
                            ).astype(np.int32)
    warm = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    warm.submit(document, max_new_tokens=1)
    warm.submit(first, max_new_tokens=1)
    before = warm.stats["prefill_tokens"]
    rid = warm.submit(second, max_new_tokens=8)
    # 13 tokens forwarded (from the document's end), not 7 (from the split)
    assert warm.stats["prefill_tokens"] - before == len(second) - 80
    assert warm._prefix_tree.splits == 1
    cold = _server(small)
    cold_rid = cold.submit(second, max_new_tokens=8)
    assert warm.run_to_completion()[rid] == cold.run_to_completion()[cold_rid]


def test_a_long_prompt_prefilled_in_chunks(small, monkeypatch):
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
    assert np.max(np.abs(np.asarray(last) - np.asarray(want_last))) < 2e-6
    rid = chunked.submit(prompt, max_new_tokens=6)
    monkeypatch.setattr(serving, "_PREFILL_WHOLE", 1 << 27)
    assert serving._prefills_whole(small[1], 128)
    whole = _server(small)
    whole_rid = whole.submit(prompt, max_new_tokens=6)
    assert chunked.run_to_completion()[rid] == \
        whole.run_to_completion()[whole_rid]


def test_the_cache_by_kind_and_eviction_count_the_snapshot(small):
    server = _server(small, prompt_cache=8, prefix_cache_bytes=1 << 24)
    kinds = server._cache.nbytes_by_kind()
    # 4 slots: 2 sparse layers x (K, V of 208 + 104 compressed keys) x 2
    # K/V heads x 16 x 4 B, each part by head [4, 2, 208, 16]; 2 linear
    # layers x 4 heads x 16 x 16 x 4 B
    assert [x.shape for x in server._cache.k] == [(4, 2, 208, 16)] * 2
    assert kinds == {"full": 4 * 2 * (2 * 208 + 104) * 2 * 16 * 4,
                     "window": 0, "state": 4 * 2 * 4 * 16 * 16 * 4,
                     "latent": 0}
    assert server.stats["cache_state_bytes"] == kinds["state"]
    server.submit(np.arange(1, 41, dtype=np.int32), max_new_tokens=1)
    row_bytes = 2 * 2 * 64 * 2 * 16 * 4 + 2 * 4 * 16 * 16 * 4
    assert server._prefix_tree.bytes == row_bytes


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
