"""HF GPT-2 interop (models/hf.py) + the compatibility knobs it exercises
(learned positions, LayerNorm, projection biases).

Ground truth is the torch forward of a random-init GPT2LMHeadModel —
no network or checkpoint files involved; the conversion must be a pure
re-layout, so logits match to float32 tolerance and every downstream
capability (KV-cached decode, continuous batching, quantization) works
on the converted store unchanged.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax  # noqa: E402

from parameter_server_distributed_tpu.models.generation import (  # noqa: E402
    generate)
from parameter_server_distributed_tpu.models.hf import (  # noqa: E402
    from_hf_gpt2)
from parameter_server_distributed_tpu.models.serving import (  # noqa: E402
    DecodeServer)


@pytest.fixture(scope="module")
def hf_pair():
    torch.manual_seed(0)
    cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=2)
    hf_model = transformers.GPT2LMHeadModel(cfg).eval()
    model, params = from_hf_gpt2(hf_model)
    return hf_model, model, params


def _torch_logits(hf_model, x):
    with torch.no_grad():
        return hf_model(torch.from_numpy(
            np.asarray(x, np.int64))).logits.numpy()


def test_logits_parity(hf_pair, rng):
    hf_model, model, params = hf_pair
    x = rng.integers(0, 128, (2, 12)).astype(np.int32)
    want = _torch_logits(hf_model, x)
    got = np.asarray(model.apply(params, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_scan_layers_conversion_parity(hf_pair, rng):
    hf_model, _, _ = hf_pair
    model, params = from_hf_gpt2(hf_model, scan_layers=True)
    x = rng.integers(0, 128, (1, 9)).astype(np.int32)
    want = _torch_logits(hf_model, x)
    got = np.asarray(model.apply(params, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_greedy_generation_matches_hf(hf_pair, rng):
    """End-to-end: our KV-cached greedy decode reproduces HF's greedy
    continuation token for token."""
    hf_model, model, params = hf_pair
    prompt = rng.integers(0, 128, (1, 6)).astype(np.int32)
    n = 8
    with torch.no_grad():
        hf_out = hf_model.generate(
            torch.from_numpy(prompt.astype(np.int64)),
            max_new_tokens=n, do_sample=False,
            pad_token_id=0)[0, prompt.shape[1]:].numpy()
    ours = np.asarray(generate(model, params, jnp.asarray(prompt), n))[0]
    np.testing.assert_array_equal(ours, hf_out.astype(ours.dtype))


def test_cached_decode_matches_full_forward_learned_pos(hf_pair, rng):
    """The cache-correctness invariant under learned positions: cached
    decode must equal re-running the whole sequence (position info enters
    via embed, not rope — a decode path that dropped the positional add
    would diverge here)."""
    hf_model, model, params = hf_pair
    prompt = jnp.asarray(rng.integers(0, 128, (2, 5)), jnp.int32)
    toks = prompt
    expected = []
    apply = jax.jit(model.apply)  # one program a length, not one a primitive
    for _ in range(5):
        nxt = jnp.argmax(apply(params, toks)[:, -1], -1)
        expected.append(nxt.astype(jnp.int32))
        toks = jnp.concatenate([toks, nxt[:, None].astype(jnp.int32)], 1)
    got = generate(model, params, prompt, 5)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack(expected, 1)))


def test_converted_model_serves_and_quantizes(hf_pair, rng):
    """The whole serving stack composes on a converted checkpoint:
    continuous batching + int8 weights + int8 KV cache."""
    from parameter_server_distributed_tpu.models.quant import (
        quantize_params)
    hf_model, model, params = hf_pair
    prompt = list(rng.integers(0, 128, 6))
    ref = list(np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32), 5))[0])
    srv = DecodeServer(model, quantize_params(params), slots=2, max_len=64,
                       cache_dtype="int8")
    rid = srv.submit(prompt, max_new_tokens=5)
    results = srv.run_to_completion()
    assert len(results[rid]) == 5
    # int8 noise may flip late tokens on a random-init model; the first
    # token comes from prefill logits and must agree
    assert results[rid][0] == ref[0]


def test_conversion_shape_contract(hf_pair):
    hf_model, model, params = hf_pair
    assert {k: tuple(v.shape) for k, v in params.items()} \
        == model.param_shapes()


def test_position_budget_guard(hf_pair, rng):
    """Learned-position models reject decoding past max_seq (n_positions)
    instead of silently reusing the last position embedding."""
    hf_model, model, params = hf_pair
    max_seq = model.config.max_seq
    prompt = jnp.asarray(rng.integers(0, 128, (1, max_seq - 2)), jnp.int32)
    with pytest.raises(ValueError, match="learned-position"):
        generate(model, params, prompt, 5)
    srv = DecodeServer(model, params, slots=1, max_len=2 * max_seq)
    with pytest.raises(ValueError, match="learned-position"):
        srv.submit(list(np.asarray(prompt)[0]), max_new_tokens=5)


def test_unsupported_activation_rejected():
    cfg = transformers.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=16, n_layer=1, n_head=2,
        activation_function="gelu")  # exact erf GELU — not our math
    hf_model = transformers.GPT2LMHeadModel(cfg)
    with pytest.raises(ValueError, match="activation_function"):
        from_hf_gpt2(hf_model)


def test_n_inner_honored():
    cfg = transformers.GPT2Config(
        vocab_size=64, n_positions=32, n_embd=16, n_layer=1, n_head=2,
        n_inner=40)
    model, params = from_hf_gpt2(transformers.GPT2LMHeadModel(cfg))
    assert model.config.d_ff == 40
    assert params["layer0/mlp/w1"].shape == (16, 40)


def test_config_knob_validation():
    from parameter_server_distributed_tpu.models.transformer import (
        TransformerConfig)
    with pytest.raises(ValueError, match="pos_emb"):
        TransformerConfig(pos_emb="learnt")
    with pytest.raises(ValueError, match="norm"):
        TransformerConfig(norm="layer_norm")


def test_position_budget_guard_beam_and_host_spec(hf_pair, rng):
    """Every decode entry point rejects past-max_seq generation on
    learned-position models — beam search and the host-loop speculative
    decoder included."""
    from parameter_server_distributed_tpu.models.generation import (
        beam_search, speculative_generate)
    hf_model, model, params = hf_pair
    max_seq = model.config.max_seq
    prompt = jnp.asarray(rng.integers(0, 128, (1, max_seq - 2)), jnp.int32)
    with pytest.raises(ValueError, match="learned-position"):
        beam_search(model, params, prompt, 5, beam_width=2)
    with pytest.raises(ValueError, match="learned-position"):
        speculative_generate(model, params, model, params, prompt, 5)


def test_attention_variant_configs_rejected():
    for field in ("scale_attn_by_inverse_layer_idx",
                  "reorder_and_upcast_attn"):
        cfg = transformers.GPT2Config(
            vocab_size=64, n_positions=32, n_embd=16, n_layer=1, n_head=2,
            **{field: True})
        with pytest.raises(ValueError, match=field):
            from_hf_gpt2(transformers.GPT2LMHeadModel(cfg))


@pytest.fixture(scope="module")
def llama_pair():
    torch.manual_seed(0)
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=56,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64)
    hf_model = transformers.LlamaForCausalLM(cfg).eval()
    from parameter_server_distributed_tpu.models.hf import from_hf_llama
    model, params = from_hf_llama(hf_model, dtype=jnp.float32)
    return hf_model, model, params


def test_llama_logits_parity(llama_pair, rng):
    """GQA + SwiGLU + RoPE (rotate-half) all line up with the torch
    forward — the LLaMA family is the native architecture."""
    hf_model, model, params = llama_pair
    assert model.config.mlp_act == "swiglu"
    assert model.config.kv_heads == 2
    x = rng.integers(0, 128, (2, 12)).astype(np.int32)
    want = _torch_logits(hf_model, x)
    got = np.asarray(model.apply(params, jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_llama_greedy_generation_matches_hf(llama_pair, rng):
    hf_model, model, params = llama_pair
    prompt = rng.integers(0, 128, (1, 6)).astype(np.int32)
    n = 8
    with torch.no_grad():
        hf_out = hf_model.generate(
            torch.from_numpy(prompt.astype(np.int64)),
            max_new_tokens=n, do_sample=False,
            pad_token_id=0)[0, prompt.shape[1]:].numpy()
    ours = np.asarray(generate(model, params, jnp.asarray(prompt), n))[0]
    np.testing.assert_array_equal(ours, hf_out.astype(ours.dtype))


def test_llama_scan_layers_and_quant_compose(llama_pair, rng):
    from parameter_server_distributed_tpu.models.hf import from_hf_llama
    from parameter_server_distributed_tpu.models.quant import (
        QTensor, quantize_params)
    hf_model, _, _ = llama_pair
    model, params = from_hf_llama(hf_model, dtype=jnp.float32,
                                  scan_layers=True)
    qparams = quantize_params(params)
    assert isinstance(qparams["blocks/mlp/w3"], QTensor)
    prompt = jnp.asarray(rng.integers(0, 128, (1, 6)), jnp.int32)
    out = generate(model, qparams, prompt, 4, cache_dtype="int8")
    assert out.shape == (1, 4)


def test_llama_unsupported_variants_rejected():
    from parameter_server_distributed_tpu.models.hf import (
        config_from_hf_llama)
    base = dict(vocab_size=64, hidden_size=16, intermediate_size=32,
                num_hidden_layers=1, num_attention_heads=2,
                num_key_value_heads=2, max_position_embeddings=32)
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf_llama(transformers.LlamaConfig(
            **base, rope_scaling={"rope_type": "linear", "factor": 2.0}))
    with pytest.raises(ValueError, match="attention_bias"):
        config_from_hf_llama(transformers.LlamaConfig(
            **base, attention_bias=True))
    with pytest.raises(ValueError, match="hidden_act"):
        config_from_hf_llama(transformers.LlamaConfig(
            **base, hidden_act="gelu"))


def test_bf16_torch_checkpoint_converts():
    """Real checkpoints ship bf16 and torch bf16 tensors lack .numpy();
    the converter must upcast through float32."""
    from parameter_server_distributed_tpu.models.hf import from_hf_llama
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=16, intermediate_size=32,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=32)
    hf_model = transformers.LlamaForCausalLM(cfg).to(torch.bfloat16)
    model, params = from_hf_llama(hf_model)
    assert params["embed/tok"].dtype == jnp.bfloat16


def test_training_forward_rejects_past_position_table(hf_pair, rng):
    """apply()/loss() on a learned-position model must reject sequences
    longer than the table instead of silently clipping (wrong gradients)."""
    hf_model, model, params = hf_pair
    seq = model.config.max_seq + 8
    toks = jnp.asarray(rng.integers(0, 128, (1, seq)), jnp.int32)
    with pytest.raises(ValueError, match="learned-position"):
        model.apply(params, toks)


def test_swiglu_knob_validation():
    from parameter_server_distributed_tpu.models.transformer import (
        TransformerConfig)
    with pytest.raises(ValueError, match="mlp_act"):
        TransformerConfig(mlp_act="geglu")


@pytest.mark.parametrize("pair", ["gpt2", "llama"])
def test_converted_checkpoints_finetune(pair, hf_pair, llama_pair, rng):
    """The fine-tuning loop closes on converted checkpoints: gradients
    flow through every compatibility knob (learned pos + LayerNorm +
    biases for GPT-2; SwiGLU + GQA for LLaMA) and a few SGD steps reduce
    the loss on a fixed batch."""
    import jax

    _, model, params = hf_pair if pair == "gpt2" else llama_pair
    toks = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
    loss_fn = jax.jit(jax.value_and_grad(model.loss))
    l0, grads = loss_fn(params, toks)
    # every parameter receives real gradient signal (biases/pos table
    # included — an accidentally-detached leaf would be all-zero)
    zero_grads = [k for k, g in grads.items()
                  if float(jnp.abs(g).max()) == 0.0]
    assert not zero_grads, zero_grads
    for _ in range(5):
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        _, grads = loss_fn(params, toks)
    final, _ = loss_fn(params, toks)
    assert float(final) < float(l0), (float(final), float(l0))


def test_llama_350m_registry_entry():
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)
    model, _ = get_model_and_batches("llama_350m", 2)
    assert model.config.mlp_act == "swiglu"
    assert model.config.kv_heads == 4
    assert 300e6 < model.num_params() < 420e6


def test_export_round_trip_llama_finetuned(llama_pair, rng):
    """to_hf_llama: a store fine-tuned HERE loads back into the torch
    model with exact logits parity — the interop round-trips both ways
    (LLaMA's head is untied, so tuned weights export faithfully)."""
    import copy

    import jax

    from parameter_server_distributed_tpu.models.hf import to_hf_llama
    hf_model, model, params = llama_pair
    # the fixture is module-scoped: load tuned weights into a COPY so
    # the other parity tests keep their pristine torch model
    hf_model = copy.deepcopy(hf_model)
    toks = jnp.asarray(rng.integers(0, 128, (2, 12)), jnp.int32)
    _, grads = jax.value_and_grad(model.loss)(params, toks)
    tuned = jax.tree.map(lambda p, g: p - 0.01 * g, params, grads)
    sd = to_hf_llama(model, tuned)
    hf_model.load_state_dict(sd)
    x = rng.integers(0, 128, (2, 9)).astype(np.int32)
    want = np.asarray(model.apply(tuned, jnp.asarray(x)))
    got = _torch_logits(hf_model, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_export_round_trip_gpt2(hf_pair, rng):
    """to_hf_gpt2 round-trips an (untuned or head-retied) store exactly;
    a fine-tuned store whose head diverged from wte.T is rejected loudly
    — HF GPT-2's tying cannot represent it."""
    import jax

    from parameter_server_distributed_tpu.models.hf import to_hf_gpt2
    hf_model, model, params = hf_pair
    import copy
    hf_model = copy.deepcopy(hf_model)   # module-scoped fixture
    x = rng.integers(0, 128, (2, 9)).astype(np.int32)
    want = np.asarray(model.apply(params, jnp.asarray(x)))
    hf_model.load_state_dict(to_hf_gpt2(model, params))
    got = _torch_logits(hf_model, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # fine-tune -> head unties -> export must refuse
    toks = jnp.asarray(rng.integers(0, 128, (2, 12)), jnp.int32)
    _, grads = jax.value_and_grad(model.loss)(params, toks)
    tuned = jax.tree.map(lambda p, g: p - 0.01 * g, params, grads)
    with pytest.raises(ValueError, match="ties lm_head"):
        to_hf_gpt2(model, tuned)
    # re-tying restores exportability
    tuned = dict(tuned)
    tuned["lm_head/w"] = tuned["embed/tok"].T
    hf_model.load_state_dict(to_hf_gpt2(model, tuned))


def test_export_scan_layout_and_quant_guard(llama_pair):
    import copy

    from parameter_server_distributed_tpu.models.hf import (from_hf_llama,
                                                            to_hf_llama)
    from parameter_server_distributed_tpu.models.quant import quantize_params
    hf_model, _, _ = llama_pair
    hf_model = copy.deepcopy(hf_model)       # module-scoped fixture
    model, params = from_hf_llama(hf_model, dtype=jnp.float32,
                                  scan_layers=True)
    sd = to_hf_llama(model, params)           # stacked layout exports too
    hf_model.load_state_dict(sd)
    with pytest.raises(ValueError, match="int8-quantized"):
        to_hf_llama(model, quantize_params(params))


def test_export_tied_destination_guard(llama_pair):
    """tie_word_embeddings=True destinations: the export omits lm_head
    (emitting it would stomp the shared embedding) and refuses a store
    whose head diverged from the tie."""
    from parameter_server_distributed_tpu.models.hf import to_hf_llama
    _, model, params = llama_pair
    tied = dict(params)
    tied["lm_head/w"] = tied["embed/tok"].T
    sd = to_hf_llama(model, tied, tie_word_embeddings=True)
    assert "lm_head.weight" not in sd
    with pytest.raises(ValueError, match="diverged"):
        to_hf_llama(model, params, tie_word_embeddings=True)


def test_pipeline_composes_with_converted_gpt2(hf_pair, rng):
    """A CONVERTED GPT-2 checkpoint trains under pipeline parallelism
    (GPipe) since round 5: the pipelined loss equals the plain converted
    model's (positional table and biases included).  The hand-written
    1F1B schedule keeps its native-arch guard and points at gpipe."""
    import jax

    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)
    from parameter_server_distributed_tpu.config import MeshConfig
    _, model, params = hf_pair
    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    piped = PipelinedTransformerLM(model, mesh, num_microbatches=2)
    tokens = rng.integers(0, 128, (8, 16)).astype(np.int32)
    # the converted store is the unrolled layer<i>/* layout; restack it
    # into the pipeline's blocks/* layout so both run IDENTICAL weights
    loss_plain = float(jax.jit(model.loss)(params, jnp.asarray(tokens)))
    stacked = piped.restack_params(
        {k: jnp.asarray(v) for k, v in params.items()})
    loss_piped = float(jax.jit(piped.loss)(stacked, jnp.asarray(tokens)))
    np.testing.assert_allclose(loss_piped, loss_plain, rtol=1e-5)
    # the hand-written 1F1B schedule handles the converted arch too
    fb = PipelinedTransformerLM(model, mesh, num_microbatches=2,
                                schedule="1f1b")
    loss_fb, grads_fb = jax.jit(fb.value_and_grad)(
        stacked, jnp.asarray(tokens))
    np.testing.assert_allclose(float(loss_fb), loss_plain, rtol=1e-5)
    assert float(np.abs(np.asarray(grads_fb["embed/pos"])).max()) > 0


def test_run_training_finetunes_hf_checkpoint(tmp_path, hf_pair, rng):
    """pst-train --hf-gpt2=<checkout>: the FULL converted-checkpoint
    fine-tune flow through the training loop — plain, then --lora on a
    pipe mesh under 1F1B (the round-5 composition for converted
    models)."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    hf_model, _, _ = hf_pair
    checkout = tmp_path / "hf_ckpt"
    hf_model.save_pretrained(checkout)

    summary = run_training(TrainLoopConfig(
        hf_gpt2=str(checkout), batch_size=8, steps=3, optimizer="adam",
        learning_rate=1e-3, log_every=1))
    assert summary["steps"] == 3
    assert np.isfinite(summary["final_loss"])

    summary2 = run_training(TrainLoopConfig(
        hf_gpt2=str(checkout), batch_size=8, steps=2, lora="2:4",
        pipeline_schedule="1f1b", log_every=1,
        mesh=MeshConfig(pipeline=2, data=4)))
    assert summary2["steps"] == 2
    assert np.isfinite(summary2["final_loss"])

    # initializer exclusivity is rejected loudly
    with pytest.raises(ValueError, match="initializers"):
        run_training(TrainLoopConfig(
            hf_gpt2=str(checkout), init_ckpt_dir=str(tmp_path), steps=1))


def test_run_training_finetunes_hf_llama(tmp_path, rng):
    """--hf-llama: the converted LlamaForCausalLM trains through
    run_training — native arch, so the 1F1B pipe mesh applies directly;
    --hf-gpt2 x --hf-llama conflict rejected."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    torch.manual_seed(0)
    cfg = transformers.LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=32)
    checkout = tmp_path / "llama"
    transformers.LlamaForCausalLM(cfg).save_pretrained(checkout)

    summary = run_training(TrainLoopConfig(
        hf_llama=str(checkout), batch_size=8, steps=2, lora="2:4",
        pipeline_schedule="1f1b", log_every=1, model_dtype="f32",
        mesh=MeshConfig(pipeline=2, data=4)))
    assert summary["steps"] == 2
    assert np.isfinite(summary["final_loss"])

    with pytest.raises(ValueError, match="both pick"):
        run_training(TrainLoopConfig(
            hf_gpt2=str(checkout), hf_llama=str(checkout), steps=1))
