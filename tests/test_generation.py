"""KV-cached generation tests: the cached decode path must reproduce the
full-sequence forward exactly (the strongest possible cache-correctness
check), plus sampling behavior."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models.generation import (
    generate, init_cache, prefill, sample_token, sample_token_rowwise)
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)


def tiny_model():
    return Transformer(TransformerConfig(
        vocab=96, d_model=48, n_heads=4, n_layers=2, d_ff=96,
        max_seq=64, dtype=jnp.float32))


def greedy_by_full_forward(model, params, prompt, n):
    """Reference: re-run the whole sequence through apply() per token."""
    toks = prompt
    out = []
    apply = jax.jit(model.apply)  # one program a length, not one a primitive
    for _ in range(n):
        logits = apply(params, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(nxt)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_cached_greedy_matches_full_forward(rng):
    model = tiny_model()
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    expected = greedy_by_full_forward(model, params, prompt, 6)
    got = generate(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def test_prefill_logits_match_apply(rng):
    model = tiny_model()
    params = model.init_params(1)
    prompt = jnp.asarray(rng.integers(0, 96, (3, 10)), jnp.int32)
    full = model.apply(params, prompt)[:, -1]
    last, cache = prefill(model, params, prompt, max_len=16)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full),
                               rtol=1e-5, atol=1e-6)
    assert int(cache.length) == 10 and cache.max_len == 16


def test_sampling_is_seeded_and_in_vocab(rng):
    model = tiny_model()
    params = model.init_params(2)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 4)), jnp.int32)
    a = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=7)
    b = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=7)
    c = generate(model, params, prompt, 5, temperature=0.8, top_k=10, rng=8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (2, 5)
    assert np.asarray(a).min() >= 0 and np.asarray(a).max() < 96
    assert not np.array_equal(np.asarray(a), np.asarray(c))  # seed matters


def test_top_k_restricts_support():
    logits = jnp.asarray([[5.0, 4.0, -1.0, -2.0, -3.0]])
    picks = {int(sample_token(logits, jax.random.key(i), temperature=1.0,
                              top_k=2)[0]) for i in range(50)}
    assert picks <= {0, 1}
    assert int(sample_token(logits, jax.random.key(0))[0]) == 0  # greedy


def test_prompt_longer_than_cache_rejected(rng):
    model = tiny_model()
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (1, 12)), jnp.int32)
    with pytest.raises(ValueError, match="exceeds cache"):
        prefill(model, params, prompt, max_len=8)


def test_init_cache_shapes():
    """A part per layer, positions on axis 1, and in a row as many whole
    heads as fit the 128 lanes: all 4 heads of 12."""
    model = tiny_model()
    cache = init_cache(model, batch=3, max_len=32)
    assert [part.shape for part in cache.k] == [(3, 32, 1, 48)] * 2
    assert [part.shape for part in cache.v] == [(3, 32, 1, 48)] * 2
    assert int(cache.length) == 0 and cache.max_len == 32


@pytest.mark.parametrize("kv_heads, head_dim, pack", [
    (16, 64, 2),     # GPT-2 medium: two heads of 64 fill the 128 lanes
    (4, 128, 1),     # SmallThinker: a head is a row
    (20, 64, 2), (4, 12, 4), (3, 64, 1), (2, 32, 2), (8, 32, 4),
    (1, 64, 1), (6, 16, 6), (4, 256, 1)])
def test_the_layout_rule_reads_only_shapes(kv_heads, head_dim, pack):
    from parameter_server_distributed_tpu.models.generation import (
        heads_per_row, pack_heads)

    assert heads_per_row(kv_heads, head_dim) == pack
    x = jnp.arange(2 * 3 * kv_heads * head_dim).reshape(
        2, 3, kv_heads, head_dim)
    rows = pack_heads(x, pack)
    assert rows.shape == (2, 3, kv_heads // pack, pack * head_dim)
    # head h is lanes (h % pack) * D .. of row h // pack
    for h in (0, kv_heads - 1):
        np.testing.assert_array_equal(
            np.asarray(rows[:, :, h // pack,
                            (h % pack) * head_dim:(h % pack + 1) * head_dim]),
            np.asarray(x[:, :, h]))


def test_repeated_generate_does_not_retrace(rng):
    from parameter_server_distributed_tpu.models import generation

    model = tiny_model()
    params = model.init_params(3)
    prompt = jnp.asarray(rng.integers(0, 96, (1, 4)), jnp.int32)
    generate(model, params, prompt, 3)
    run = generation._RUNNERS[
        (generation._model_key(model), 3, 0.0, 0, 0.0, "native")]
    traces_before = run._cache_size()
    out1 = generate(model, params, prompt, 3)
    out2 = generate(model, params, prompt, 3)
    assert run._cache_size() == traces_before  # same wrapper, no retrace
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_top_k_larger_than_vocab_is_no_truncation():
    logits = jnp.asarray([[1.0, 2.0, 3.0]])
    tok = sample_token(logits, jax.random.key(0), temperature=1.0, top_k=99)
    assert 0 <= int(tok[0]) < 3


def gqa_model(n_kv_heads):
    return Transformer(TransformerConfig(
        vocab=96, d_model=48, n_heads=4, n_kv_heads=n_kv_heads, n_layers=2,
        d_ff=96, max_seq=64, dtype=jnp.float32))


@pytest.mark.parametrize("n_kv", [1, 2])
def test_gqa_cached_greedy_matches_full_forward(rng, n_kv):
    """GQA decode (kv_heads-shaped cache, heads expanded at use) must
    reproduce the full-sequence forward token for token."""
    model = gqa_model(n_kv)
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    expected = greedy_by_full_forward(model, params, prompt, 6)
    got = generate(model, params, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def test_gqa_cache_is_smaller(rng):
    mha = init_cache(tiny_model(), batch=2, max_len=16)
    gqa = init_cache(gqa_model(1), batch=2, max_len=16)
    # one K/V head of 12 to a row against four side by side
    assert gqa.k[0].shape[2:] == (1, 12) and mha.k[0].shape[2:] == (1, 48)
    assert len(gqa.k) == len(mha.k) == 2
    assert gqa.k[0].size == mha.k[0].size // 4


def test_top_p_restricts_support():
    """probs ~ [.5, .3, .15, .05]: top_p=0.6 keeps exactly {0, 1} (tokens
    whose preceding cumulative mass < p); top_p>=1 truncates nothing."""
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    picks = {int(sample_token(logits, jax.random.key(i), temperature=1.0,
                              top_p=0.6)[0]) for i in range(60)}
    assert picks == {0, 1}
    picks_all = {int(sample_token(logits, jax.random.key(i),
                                  temperature=1.0, top_p=0.0)[0])
                 for i in range(120)}
    assert picks_all == {0, 1, 2, 3}
    # argmax token always survives even a tiny p
    assert int(sample_token(logits, jax.random.key(0), temperature=1.0,
                            top_p=1e-6)[0]) == 0


def test_top_p_generation_seeded(rng):
    model = tiny_model()
    params = model.init_params(4)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 4)), jnp.int32)
    a = generate(model, params, prompt, 5, temperature=0.9, top_p=0.8, rng=3)
    b = generate(model, params, prompt, 5, temperature=0.9, top_p=0.8, rng=3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).min() >= 0 and np.asarray(a).max() < 96


def test_generate_cli_end_to_end(tmp_path, rng, capsys):
    """pst-generate: train -> host checkpoint -> decode text, all through
    the CLI entry point."""
    from parameter_server_distributed_tpu.checkpoint import codec
    from parameter_server_distributed_tpu.cli.generate_main import main
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)

    model, _ = get_model_and_batches("small_lm", 1)
    params = {k: np.asarray(v) for k, v in model.init_params(0).items()}
    ckpt = tmp_path / "m.ckpt"
    codec.save(str(ckpt), 1, 10, params)

    rc = main(["--model=small_lm", f"--ckpt={ckpt}", "--prompt=ab",
               "--max-new=4"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.endswith("\n") and len(out) >= 1  # decoded text printed

    # raw token-id mode
    rc = main(["--model=small_lm", f"--ckpt={ckpt}", "--tokens=1,2,3",
               "--max-new=3", "--temperature=0.5", "--top-p=0.9"])
    assert rc == 0
    ids = [int(t) for t in capsys.readouterr().out.strip().split(",")]
    assert len(ids) == 3 and all(0 <= i < 1024 for i in ids)

    with pytest.raises(ValueError, match="out of range"):
        main(["--model=small_lm", f"--ckpt={ckpt}", "--tokens=99999"])


def test_generate_cli_from_sharded_checkpoint(tmp_path, capsys):
    """pst-train orbax checkpoint -> pst-generate --ckpt-dir round-trip."""
    from parameter_server_distributed_tpu.cli.generate_main import main
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    run_training(TrainLoopConfig(
        model="small_lm", batch_size=8, steps=2, optimizer="sgd",
        learning_rate=0.1, mesh=MeshConfig(data=8),
        checkpoint_dir=str(tmp_path), checkpoint_every=2, log_every=1))
    rc = main(["--model=small_lm", f"--ckpt-dir={tmp_path}",
               "--prompt=hello", "--max-new=4"])
    assert rc == 0
    assert "sharded checkpoint step 2" in capsys.readouterr().err


def test_generate_cli_cross_layout(tmp_path, capsys):
    """A store trained with --scan-layers (stacked blocks/*) decodes on an
    unrolled model and vice versa — generate_main converts layouts, and
    greedy output is identical either way."""
    from parameter_server_distributed_tpu.checkpoint import codec
    from parameter_server_distributed_tpu.cli.generate_main import main
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)
    from parameter_server_distributed_tpu.models.transformer import (
        stack_layers)

    model, _ = get_model_and_batches("small_lm", 1)
    params = {k: np.asarray(v) for k, v in model.init_params(0).items()}
    stacked = stack_layers(params, model.config.n_layers)

    flat_ckpt = tmp_path / "flat.ckpt"
    codec.save(str(flat_ckpt), 1, 10, params)
    stacked_ckpt = tmp_path / "stacked.ckpt"
    codec.save(str(stacked_ckpt), 1, 10,
               {k: np.asarray(v) for k, v in stacked.items()})

    outs = []
    for ckpt, flag in [(flat_ckpt, "--scan-layers"),
                       (stacked_ckpt, ""),          # unrolled model default
                       (stacked_ckpt, "--scan-layers"),
                       (flat_ckpt, "")]:
        argv = ["--model=small_lm", f"--ckpt={ckpt}", "--tokens=1,2,3",
                "--max-new=4"]
        if flag:
            argv.append(flag)
        assert main(argv) == 0
        outs.append(capsys.readouterr().out.strip())
    assert len(set(outs)) == 1, outs


def test_beam_width_one_is_greedy(rng):
    from parameter_server_distributed_tpu.models.generation import (
        beam_search, generate)
    from parameter_server_distributed_tpu.models.transformer import small_lm

    model = small_lm(vocab=64, seq=32)
    params = model.init_params(0)
    prompt = rng.integers(0, 64, (2, 5)).astype(np.int32)
    greedy = np.asarray(generate(model, params, prompt, max_new_tokens=6))
    beam, scores = beam_search(model, params, prompt, max_new_tokens=6,
                               beam_width=1)
    np.testing.assert_array_equal(np.asarray(beam), greedy)
    assert np.all(np.isfinite(np.asarray(scores)))


def test_beam_search_full_width_finds_joint_argmax(rng):
    """With beam_width = vocab, a 2-step beam search is exhaustive: its
    result must be the argmax of the joint log-prob over ALL two-token
    continuations, computed by brute force through the full forward."""
    from parameter_server_distributed_tpu.models.generation import beam_search
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    vocab = 16
    model = Transformer(TransformerConfig(
        vocab=vocab, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=16, dtype=jnp.float32))
    params = model.init_params(0)
    prompt = rng.integers(0, vocab, (1, 3)).astype(np.int32)

    out, score = beam_search(model, params, prompt, max_new_tokens=2,
                             beam_width=vocab)
    out = np.asarray(out)[0]

    # brute force: joint logprob of every (t1, t2)
    best = (None, -np.inf)
    logits = np.asarray(model.apply(params, prompt))  # [1, 3, V]
    lp1 = jax.nn.log_softmax(logits[0, -1])
    for t1 in range(vocab):
        seq = np.concatenate([prompt[0], [t1]])[None].astype(np.int32)
        lp2 = jax.nn.log_softmax(np.asarray(model.apply(params, seq))[0, -1])
        for t2 in range(vocab):
            joint = float(lp1[t1]) + float(lp2[t2])
            if joint > best[1]:
                best = ((t1, t2), joint)
    assert tuple(out) == best[0]
    assert float(np.asarray(score)[0]) == pytest.approx(best[1], rel=1e-4)


def test_beam_width_validation(rng):
    from parameter_server_distributed_tpu.models.generation import beam_search
    from parameter_server_distributed_tpu.models.transformer import small_lm

    model = small_lm(vocab=64, seq=32)
    params = model.init_params(0)
    prompt = rng.integers(0, 64, (1, 4)).astype(np.int32)
    for bad in (0, 65):
        with pytest.raises(ValueError, match="beam_width"):
            beam_search(model, params, prompt, 4, beam_width=bad)


def test_beam_search_eos_freezes_score(rng):
    """A beam that emits eos_id finishes: score frozen, EOS-padded, and it
    stays comparable against live beams.  Rigged so EOS is the argmax
    from the first step: the best beam must be all-EOS with joint score
    exactly logp(EOS at step 1)."""
    from parameter_server_distributed_tpu.models.generation import beam_search
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    vocab = 16
    model = Transformer(TransformerConfig(
        vocab=vocab, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=16, dtype=jnp.float32))
    params = model.init_params(0)
    prompt = rng.integers(0, vocab, (1, 3)).astype(np.int32)
    # the model's own first greedy token as EOS: the top beam finishes at
    # step 1 with score logp(eos), and no live beam can ever overtake it
    # (a live beam's joint is logp(weaker first token) + non-positive
    # continuations < logp(eos)), so the frozen beam must win
    logits = np.asarray(model.apply(params, prompt))[0, -1]
    eos = int(logits.argmax())

    out, score = beam_search(model, params, prompt, max_new_tokens=5,
                             beam_width=3, eos_id=eos)
    out = np.asarray(out)[0]
    assert np.all(out == eos)  # finished at step 1, EOS-padded after
    expect = float(jax.nn.log_softmax(logits)[eos])
    assert float(np.asarray(score)[0]) == pytest.approx(expect, rel=1e-5)


def test_beam_length_penalty_prefers_longer(rng):
    """alpha=0 picks the short frozen beam (highest raw joint log-prob);
    a large alpha divides long beams' negative scores by a big factor,
    flipping the selection to a full-length live beam."""
    from parameter_server_distributed_tpu.models.generation import beam_search
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    vocab = 16
    model = Transformer(TransformerConfig(
        vocab=vocab, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=16, dtype=jnp.float32))
    params = model.init_params(0)
    prompt = rng.integers(0, vocab, (1, 3)).astype(np.int32)
    logits = np.asarray(model.apply(params, prompt))[0, -1]
    eos = int(logits.argmax())

    raw, _ = beam_search(model, params, prompt, max_new_tokens=5,
                         beam_width=3, eos_id=eos)
    assert np.all(np.asarray(raw)[0] == eos)  # short frozen beam wins

    # alpha=50: a full-length beam's negative score is divided by
    # (10/6)^50 ~ 1e11, so any live beam beats the frozen one unless
    # p(EOS) > 1 - 1e-10 — impossible for an untrained model
    norm, _ = beam_search(model, params, prompt, max_new_tokens=5,
                          beam_width=3, eos_id=eos, length_penalty=50.0)
    assert np.asarray(norm)[0][0] != eos


def test_speculative_matches_target_greedy(rng):
    """Speculative decoding is an exactness-preserving accelerator: for
    any draft (here a 1-layer LM with the target's vocab) the output must
    be token-identical to target-alone greedy decoding, while committing
    multiple tokens per target forward."""
    from parameter_server_distributed_tpu.models.generation import (
        generate, speculative_generate)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig, small_lm)

    target = small_lm(vocab=256, seq=64)
    draft = Transformer(TransformerConfig(
        vocab=256, d_model=64, n_heads=4, n_layers=1, d_ff=128,
        max_seq=64, dtype=jnp.float32))
    tparams = target.init_params(0)
    dparams = draft.init_params(1)
    prompt = rng.integers(0, 256, (1, 7)).astype(np.int32)

    reference = np.asarray(generate(target, tparams, prompt,
                                    max_new_tokens=16))
    out, stats = speculative_generate(target, tparams, draft, dparams,
                                      prompt, 16, draft_len=3)
    np.testing.assert_array_equal(out, reference)
    assert stats["verify_calls"] >= 1
    assert stats["tokens_per_target_forward"] >= 1.0

    # a PERFECT draft (the target itself) must accept everything:
    # draft_len+1 tokens per verify call
    out2, stats2 = speculative_generate(target, tparams, target, tparams,
                                        prompt, 16, draft_len=3)
    np.testing.assert_array_equal(out2, reference)
    assert stats2["draft_accept_rate"] == pytest.approx(1.0)
    # 16 tokens from prefill + 4 fully-accepted verify calls = 5 forwards
    assert stats2["tokens_per_target_forward"] == pytest.approx(16 / 5)

    with pytest.raises(ValueError, match="vocab"):
        speculative_generate(target, tparams, small_lm(vocab=64, seq=32),
                             small_lm(vocab=64, seq=32).init_params(0),
                             prompt, 4)
    with pytest.raises(ValueError, match="batch-1"):
        speculative_generate(target, tparams, draft, dparams,
                             np.zeros((2, 4), np.int32), 4)


def test_accept_or_resample_preserves_target_distribution():
    """The rejection rule's defining property: over x ~ q followed by
    accept/resample, the output token is distributed exactly as p —
    checked empirically on a skewed (p, q) pair."""
    from parameter_server_distributed_tpu.models.generation import (
        accept_or_resample)

    rng = np.random.default_rng(0)
    p = np.asarray([0.5, 0.3, 0.15, 0.05])
    q = np.asarray([0.05, 0.15, 0.3, 0.5])  # draft skewed the wrong way
    n = 20000
    counts = np.zeros(4)
    for _ in range(n):
        x = int(rng.choice(4, p=q))
        token, _ = accept_or_resample(p, q, x, rng)
        counts[token] += 1
    freq = counts / n
    # 3-sigma bound per bin: sigma = sqrt(p(1-p)/n) < 0.0036
    np.testing.assert_allclose(freq, p, atol=0.012)


def test_speculative_sampling_perfect_draft_accepts_all(rng):
    """temperature > 0 with draft == target: p == q so acceptance is
    certain; output length and stats must reflect full acceptance."""
    from parameter_server_distributed_tpu.models.generation import (
        speculative_generate)
    from parameter_server_distributed_tpu.models.transformer import small_lm

    model = small_lm(vocab=128, seq=64)
    params = model.init_params(0)
    prompt = rng.integers(0, 128, (1, 5)).astype(np.int32)
    out, stats = speculative_generate(model, params, model, params,
                                      prompt, 12, draft_len=3,
                                      temperature=1.0, seed=7)
    assert out.shape == (1, 12)
    assert stats["draft_accept_rate"] == pytest.approx(1.0)
    # deterministic given the seed
    out2, _ = speculative_generate(model, params, model, params,
                                   prompt, 12, draft_len=3,
                                   temperature=1.0, seed=7)
    np.testing.assert_array_equal(out, out2)


def test_decode_block_matches_sequential_steps(rng):
    """A T-token decode_block equals T sequential decode_steps: same
    final logits and same cache contents (the verify-step contract)."""
    import dataclasses

    from parameter_server_distributed_tpu.models.generation import (
        decode_block, decode_step, init_cache, prefill)
    from parameter_server_distributed_tpu.models.transformer import small_lm

    model = small_lm(vocab=128, seq=64)
    params = model.init_params(0)
    prompt = rng.integers(0, 128, (2, 6)).astype(np.int32)
    toks = rng.integers(0, 128, (2, 4)).astype(np.int32)

    _, cache_a = prefill(model, params, prompt, 32)
    block_logits, cache_a = decode_block(model, params, toks, cache_a)

    _, cache_b = prefill(model, params, prompt, 32)
    step_logits = []
    for j in range(4):
        lg, cache_b = decode_step(model, params, toks[:, j], cache_b)
        step_logits.append(lg)

    np.testing.assert_allclose(np.asarray(block_logits[:, -1]),
                               np.asarray(step_logits[-1]),
                               rtol=2e-5, atol=2e-5)
    for j in range(4):
        np.testing.assert_allclose(np.asarray(block_logits[:, j]),
                                   np.asarray(step_logits[j]),
                                   rtol=2e-5, atol=2e-5)
    assert int(np.asarray(cache_a.length)) == int(np.asarray(cache_b.length))
    np.testing.assert_allclose(np.asarray(cache_a.k), np.asarray(cache_b.k),
                               rtol=2e-5, atol=2e-5)


def test_generate_cli_speculative_matches_greedy(tmp_path, capsys):
    """pst-generate --draft-model: greedy speculative output through the
    CLI is byte-identical to plain greedy decoding of the same model."""
    from parameter_server_distributed_tpu.checkpoint import codec
    from parameter_server_distributed_tpu.cli.generate_main import main
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)

    model, _ = get_model_and_batches("small_lm", 1)
    params = {k: np.asarray(v) for k, v in model.init_params(0).items()}
    ckpt = tmp_path / "m.ckpt"
    codec.save(str(ckpt), 1, 10, params)

    base = ["--model=small_lm", f"--ckpt={ckpt}", "--tokens=5,6,7",
            "--max-new=8"]
    assert main(base) == 0
    greedy = capsys.readouterr().out.strip()
    assert main(base + ["--draft-model=moe_lm", "--draft-len=2"]) == 0
    spec = capsys.readouterr().out.strip()
    assert spec == greedy


# ---------------------------------------------------------------------------
# Batched on-device speculative decoding (whole loop under one jit)
# ---------------------------------------------------------------------------

def _spec_pair():
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig, small_lm)

    target = small_lm(vocab=256, seq=64)
    draft = Transformer(TransformerConfig(
        vocab=256, d_model=64, n_heads=4, n_layers=1, d_ff=128,
        max_seq=64, dtype=jnp.float32))
    return target, target.init_params(0), draft, draft.init_params(1)


def test_speculative_batched_greedy_matches_target(rng):
    """Every ROW of a batched device-speculative greedy run must equal
    target-alone greedy decoding — per-row acceptance lengths diverge, so
    this exercises the ragged caches end to end."""
    from parameter_server_distributed_tpu.models.generation import (
        generate, speculative_generate_batched)

    target, tparams, draft, dparams = _spec_pair()
    prompt = rng.integers(0, 256, (4, 7)).astype(np.int32)
    reference = np.asarray(generate(target, tparams, prompt,
                                    max_new_tokens=16))
    out, stats = speculative_generate_batched(target, tparams, draft,
                                              dparams, prompt, 16,
                                              draft_len=3)
    np.testing.assert_array_equal(out, reference)
    assert stats["verify_calls"] >= 1

    # perfect draft: every proposal accepted for every row
    out2, stats2 = speculative_generate_batched(target, tparams, target,
                                                tparams, prompt, 16,
                                                draft_len=3)
    np.testing.assert_array_equal(out2, reference)
    assert stats2["draft_accept_rate"] == pytest.approx(1.0)
    assert stats2["tokens_per_target_forward"] == pytest.approx(16 / 5)


def test_speculative_batched_agrees_with_host_reference(rng):
    """Batch-1 device greedy run == the host-loop reference
    implementation, token for token and stat for stat."""
    from parameter_server_distributed_tpu.models.generation import (
        speculative_generate, speculative_generate_batched)

    target, tparams, draft, dparams = _spec_pair()
    prompt = rng.integers(0, 256, (1, 7)).astype(np.int32)
    got, s_dev = speculative_generate_batched(target, tparams, draft,
                                              dparams, prompt, 16,
                                              draft_len=3)
    want, s_host = speculative_generate(target, tparams, draft, dparams,
                                        prompt, 16, draft_len=3)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert s_dev["verify_calls"] == s_host["verify_calls"]


def test_speculative_batched_sampling_preserves_distribution():
    """The vectorized on-device rejection rule preserves the target
    distribution: empirical first-token frequencies of many seeded
    batched runs match direct target sampling (tiny vocab, 3-sigma)."""
    from parameter_server_distributed_tpu.models.generation import (
        speculative_generate_batched)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    vocab = 8
    target = Transformer(TransformerConfig(
        vocab=vocab, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq=32, dtype=jnp.float32))
    draft = Transformer(TransformerConfig(
        vocab=vocab, d_model=8, n_heads=1, n_layers=1, d_ff=16,
        max_seq=32, dtype=jnp.float32))
    tparams, dparams = target.init_params(0), draft.init_params(3)
    prompt = np.full((64, 4), 2, np.int32)  # identical rows
    temp = 1.0

    counts = np.zeros(vocab)
    reps = 8
    for seed in range(reps):
        out, _ = speculative_generate_batched(
            target, tparams, draft, dparams, prompt, 2, draft_len=2,
            temperature=temp, seed=seed)
        for tok in out[:, 0]:
            counts[int(tok)] += 1
    freq = counts / (64 * reps)

    # ground truth: the target's own first-token distribution
    from parameter_server_distributed_tpu.models.generation import prefill
    logits, _ = prefill(target, tparams, jnp.asarray(prompt[:1]), 8)
    p = np.asarray(jax.nn.softmax(logits[0] / temp))
    sigma = np.sqrt(p * (1 - p) / (64 * reps))
    np.testing.assert_array_less(np.abs(freq - p), 4 * sigma + 0.01)


def test_speculative_batched_rejects_vocab_mismatch(rng):
    from parameter_server_distributed_tpu.models.generation import (
        speculative_generate_batched)
    from parameter_server_distributed_tpu.models.transformer import small_lm

    target, tparams, _, _ = _spec_pair()
    other = small_lm(vocab=64, seq=32)
    with pytest.raises(ValueError, match="vocab"):
        speculative_generate_batched(target, tparams, other,
                                     other.init_params(0),
                                     np.zeros((2, 4), np.int32), 4)


def test_speculative_batched_gqa_target_matches_greedy(rng):
    """Batched device speculative decoding with a GQA target (unexpanded
    K/V caches through the ragged decode path) stays token-exact vs
    target-alone greedy decoding."""
    from parameter_server_distributed_tpu.models.generation import (
        generate, speculative_generate_batched)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    target = Transformer(TransformerConfig(
        vocab=256, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq=64, dtype=jnp.float32))
    tparams = target.init_params(0)
    draft = Transformer(TransformerConfig(
        vocab=256, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq=64, dtype=jnp.float32))
    dparams = draft.init_params(1)
    prompt = rng.integers(0, 256, (3, 6)).astype(np.int32)
    reference = np.asarray(generate(target, tparams, prompt,
                                    max_new_tokens=12))
    out, _ = speculative_generate_batched(target, tparams, draft, dparams,
                                          prompt, 12, draft_len=3)
    np.testing.assert_array_equal(out, reference)


def test_generation_with_a_callers_attention_prefill_matches_dense(rng):
    """A model built with a caller's attention_fn (the plain-XLA blocks)
    serves the same prefill as the default model (decode then uses the
    cache einsums either way).  Logits compared with a tolerance, not
    token equality — the two reorder float accumulation, and a near-tie
    argmax flip would make discrete comparison flaky across backends."""
    from parameter_server_distributed_tpu.models.generation import prefill
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.ops.blockwise_attention import (
        blockwise_attention)

    config = TransformerConfig(vocab=256, d_model=32, n_heads=4,
                               n_layers=2, d_ff=64, max_seq=64,
                               dtype=jnp.float32)
    dense = Transformer(config)
    flash = Transformer(
        config, attention_fn=lambda q, k, v: blockwise_attention(
            q, k, v, jnp.zeros((q.shape[0],), jnp.int32), block_q=4,
            block_k=4))
    params = dense.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 256, (2, 8)), jnp.int32)
    logits_d, cache_d = prefill(dense, params, prompt, 32)
    logits_f, cache_f = prefill(flash, params, prompt, 32)
    np.testing.assert_allclose(np.asarray(logits_f), np.asarray(logits_d),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache_f.k), np.asarray(cache_d.k),
                               rtol=2e-4, atol=2e-4)


def test_sample_token_rowwise_exactness(rng):
    """The per-row sampler's contract against the scalar one: with a
    uniform temperature vector it draws EXACTLY sample_token's tokens
    (same rng, same truncation math), zero-temperature rows are exact
    argmax regardless of the other rows, and static top_k truncation
    applies to sampled rows."""
    logits = jnp.asarray(rng.standard_normal((6, 32)) * 3.0, jnp.float32)
    key = jax.random.key(7)

    # uniform hot vector == scalar sampler, token for token
    uniform = jnp.full((6,), 0.8, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sample_token_rowwise(logits, key, uniform)),
        np.asarray(sample_token(logits, key, 0.8)))
    # ... including under top_k/top_p truncation
    np.testing.assert_array_equal(
        np.asarray(sample_token_rowwise(logits, key, uniform,
                                        top_k=5, top_p=0.9)),
        np.asarray(sample_token(logits, key, 0.8, top_k=5, top_p=0.9)))

    # mixed batch: zero rows are exact argmax, whatever the others do
    mixed = jnp.asarray([0.0, 9.0, 0.0, 0.5, 0.0, 2.0], jnp.float32)
    out = np.asarray(sample_token_rowwise(logits, key, mixed))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    for i in (0, 2, 4):
        assert out[i] == greedy[i]

    # top_k=1 forces argmax even at high temperature (truncation is
    # shared/static across rows)
    hot = jnp.full((6,), 9.0, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sample_token_rowwise(logits, key, hot, top_k=1)),
        greedy)


def test_optimal_draft_depth_controller():
    """The expected-throughput controller: depth follows per-proposal
    agreement p and the draft/target cost ratio.  Anchors: the round-4
    measurements (accept 0.57 at k=2 -> 1.20x, accept 0.36 at k=4 ->
    0.76x over-speculation) must map to k* <= 2 at rho~1/3, and a
    perfect draft must max out the cap."""
    from parameter_server_distributed_tpu.models.generation import (
        _invert_accept_fraction, optimal_draft_depth)

    # inversion: fraction at depth k back to per-proposal p
    for p in (0.1, 0.5, 0.9):
        for k in (1, 2, 4):
            frac = sum(p ** i for i in range(1, k + 1)) / k
            assert _invert_accept_fraction(frac, k) == pytest.approx(
                p, abs=1e-6)
    assert _invert_accept_fraction(0.0, 4) == 0.0
    assert _invert_accept_fraction(1.0, 4) == 1.0

    # perfect draft -> cap; hopeless draft -> minimum depth
    assert optimal_draft_depth(1.0, 2, 8, cost_ratio=0.1) == 8
    assert optimal_draft_depth(0.0, 4, 8, cost_ratio=0.3) == 1
    # the round-4 regression shape: mid accept, moderate cost ratio
    assert optimal_draft_depth(0.36, 4, 4, cost_ratio=1 / 3) <= 2
    assert optimal_draft_depth(0.57, 2, 4, cost_ratio=1 / 3) <= 2
    # near-free draft deepens even at mid accept
    assert optimal_draft_depth(0.6, 2, 8, cost_ratio=0.02) >= 4


def test_speculative_batched_adaptive_token_exact_and_settles(rng):
    """adaptive=True: token-exact vs target-alone greedy for any depth
    trajectory, and the controller settles where acceptance points —
    depth 0 (speculation disabled, plain greedy segments) for a
    random-init draft whose economics can never pay, the cap for a
    perfect self-draft (accept 1.0)."""
    from parameter_server_distributed_tpu.models.generation import (
        generate, speculative_generate_batched)

    target, tparams, draft, dparams = _spec_pair()
    prompt = rng.integers(0, 256, (4, 7)).astype(np.int32)
    reference = np.asarray(generate(target, tparams, prompt,
                                    max_new_tokens=32))
    out, stats = speculative_generate_batched(
        target, tparams, draft, dparams, prompt, 32, draft_len=4,
        adaptive=True, draft_cost_ratio=0.3, calibration="model")
    np.testing.assert_array_equal(out, reference)
    assert stats["draft_depths"][0] == 2          # starts at min(2, cap)
    assert stats["draft_depth"] == 0              # junk draft -> disabled
    assert 0 in stats["draft_depths"]             # greedy segments ran

    out2, stats2 = speculative_generate_batched(
        target, tparams, target, tparams, prompt, 32, draft_len=4,
        adaptive=True, draft_cost_ratio=0.3, calibration="model")
    np.testing.assert_array_equal(out2, reference)
    assert stats2["draft_depth"] == 4             # perfect draft -> cap
    assert stats2["draft_accept_rate"] == pytest.approx(1.0)

    # measured mode: depth choices are host-timing-dependent, but the
    # outputs must stay token-exact whatever the probes decide
    out3, stats3 = speculative_generate_batched(
        target, tparams, draft, dparams, prompt, 32, draft_len=4,
        adaptive=True, draft_cost_ratio=0.3)
    np.testing.assert_array_equal(out3, reference)
    assert stats3["draft_depth"] in (0, 1, 2, 3, 4)


def test_adaptive_memoizes_steady_state_depth(rng):
    """The first adaptive call calibrates (segmented run); subsequent
    calls for the same (target, draft, sampling) jump straight to the
    winning FUSED program — depths report "memo" and outputs stay
    token-exact.  A junk draft memoizes k=0 (plain generate); a perfect
    draft memoizes the cap (whole-loop spec)."""
    from parameter_server_distributed_tpu.models.generation import (
        generate, speculative_generate_batched)

    target, tparams, draft, dparams = _spec_pair()
    prompt = rng.integers(0, 256, (4, 7)).astype(np.int32)
    reference = np.asarray(generate(target, tparams, prompt,
                                    max_new_tokens=32))
    kw = dict(draft_len=4, adaptive=True, draft_cost_ratio=0.3,
              calibration="model")
    _, first = speculative_generate_batched(
        target, tparams, draft, dparams, prompt, 32, **kw)
    assert first["draft_depth"] == 0
    out, steady = speculative_generate_batched(
        target, tparams, draft, dparams, prompt, 32, **kw)
    np.testing.assert_array_equal(out, reference)
    assert steady["draft_depths"] == ["memo"]
    assert steady["draft_depth"] == 0
    assert steady["verify_calls"] == 32       # one target fwd per token

    _, first2 = speculative_generate_batched(
        target, tparams, target, tparams, prompt, 32, **kw)
    assert first2["draft_depth"] == 4
    out2, steady2 = speculative_generate_batched(
        target, tparams, target, tparams, prompt, 32, **kw)
    np.testing.assert_array_equal(out2, reference)
    assert steady2["draft_depths"] == ["memo"]
    assert steady2["draft_accept_rate"] == pytest.approx(1.0)
