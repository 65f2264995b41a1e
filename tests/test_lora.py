"""LoRA fine-tuning (models/lora.py): adapters train, base stays frozen,
merge collapses exactly, and the CLI/train-loop integration works on a
sharded mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models.lora import (
    DEFAULT_TARGETS, freeze_base, init_lora, lora_loss, lora_names,
    merge_lora, split_rank_alpha, trainable_mask)
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)


def tiny(scan=False):
    return Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=16,
        dtype=jnp.float32, scan_layers=scan))


def test_init_starts_at_base_model(rng):
    """B = 0 at init, so the adapted forward equals the base forward
    exactly; A/B appear for every q/v projection in both layouts."""
    tokens = rng.integers(0, 64, (2, 16)).astype(np.int32)
    for scan in (False, True):
        model = tiny(scan)
        params = model.init_params(0)
        adapted = init_lora(params, rank=4, rng=1)
        n_targets = 2 if scan else 2 * model.config.n_layers
        assert len(lora_names(adapted)) == 2 * n_targets
        base_loss = float(model.loss(params, tokens))
        wrapped = lora_loss(model.loss)
        assert float(wrapped(adapted, tokens)) == pytest.approx(base_loss)


def test_training_updates_only_adapters(rng):
    """Gradient steps through the masked optimizer move ONLY /lora_
    entries; the base store is bit-identical after training, and the
    loss decreases."""
    import optax

    model = tiny()
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    params = init_lora(model.init_params(0), rank=4, rng=1)
    loss_fn = lora_loss(model.loss)
    opt = freeze_base(optax.adam(1e-2))
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    base_before = {n: np.asarray(v) for n, v in params.items()
                   if not n.endswith(("/lora_a", "/lora_b"))}
    losses = []
    for _ in range(12):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    for name, before in base_before.items():
        np.testing.assert_array_equal(np.asarray(params[name]), before,
                                      err_msg=f"{name} moved but is frozen")
    moved = [n for n in lora_names(params)
             if np.abs(np.asarray(params[n])).sum() > 0]
    assert any(n.endswith("/lora_b") for n in moved)  # B left zero-init


def test_merge_equals_adapted_forward(rng):
    """merge_lora folds adapters into plain dense weights whose forward
    matches the adapted model's exactly — the serving/export path."""
    model = tiny()
    tokens = rng.integers(0, 64, (2, 16)).astype(np.int32)
    params = init_lora(model.init_params(0), rank=4, rng=1)
    # give B real values so the adapters actually contribute
    for name in lora_names(params):
        if name.endswith("/lora_b"):
            key = jax.random.key(hash(name) % (2**31))
            params[name] = 0.1 * jax.random.normal(
                key, params[name].shape, params[name].dtype)
    adapted = float(lora_loss(model.loss, alpha=8.0)(params, tokens))
    merged = merge_lora(params, alpha=8.0)
    assert not lora_names(merged)
    assert float(model.loss(merged, tokens)) == pytest.approx(adapted,
                                                              rel=1e-6)
    # merged store has exactly the base names (serves/saves like dense)
    assert set(merged) == set(model.init_params(0))
    # rank is read from the factors — a different rank cannot mis-scale
    r2 = init_lora(model.init_params(0), rank=2, rng=3)
    assert merge_lora(r2)["layer0/attn/wq"].shape == (32, 32)


def test_hf_converted_checkpoint_lora_finetunes(rng):
    """The intended workflow: convert a transformers GPT-2 checkpoint,
    attach adapters, fine-tune — base (converted) weights frozen."""
    transformers = pytest.importorskip("transformers")
    import optax

    from parameter_server_distributed_tpu.models.hf import from_hf_gpt2

    cfg = transformers.GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                                  n_layer=2, n_head=2)
    hf = transformers.GPT2LMHeadModel(cfg)
    model, params = from_hf_gpt2(hf)
    params = init_lora(params, rank=2, rng=0)
    loss_fn = lora_loss(model.loss)
    opt = freeze_base(optax.adam(5e-2))
    opt_state = opt.init(params)
    tokens = rng.integers(0, 96, (2, 16)).astype(np.int32)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    wte_before = np.asarray(params["embed/tok"])
    losses = [float(step(params, opt_state)[2])]
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(np.asarray(params["embed/tok"]),
                                  wte_before)


def test_train_loop_lora_on_mesh(tmp_path):
    """pst-train's code path: a dense run checkpoints, then --lora with
    --init-ckpt-dir fine-tunes FROM that pretrained base on an 8-device
    mesh (the dense-checkpoint -> LoRA flow the CLI documents)."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    base_dir = str(tmp_path / "base")
    pre = run_training(TrainLoopConfig(
        model="small_lm", batch_size=8, steps=4, optimizer="adam",
        learning_rate=1e-2, log_every=2, checkpoint_dir=base_dir,
        checkpoint_every=4))
    summary = run_training(TrainLoopConfig(
        model="small_lm", batch_size=8, steps=6, optimizer="adam",
        learning_rate=1e-2, lora="4:8", log_every=3,
        init_ckpt_dir=base_dir,
        mesh=MeshConfig(data=2, fsdp=2, tensor=2)))
    assert pre["steps"] == 4
    assert summary["steps"] == 6
    assert np.isfinite(summary["final_loss"])


def test_generate_cli_merges_lora_checkpoint(tmp_path, rng):
    """pst-generate on a --lora checkpoint: refuses without --lora-alpha
    (the scale must match training), merges and decodes with it."""
    import os
    import subprocess
    import sys

    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    ckpt = str(tmp_path / "ft")
    run_training(TrainLoopConfig(
        model="tiny_lm", batch_size=4, steps=2, optimizer="adam",
        learning_rate=1e-2, lora="2:4", checkpoint_dir=ckpt,
        checkpoint_every=2, log_every=2))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    base = [sys.executable, "-m",
            "parameter_server_distributed_tpu.cli.generate_main",
            "--model=tiny_lm", f"--ckpt-dir={ckpt}", "--tokens=1,2,3",
            "--max-new=3"]
    refused = subprocess.run(base, capture_output=True, text=True, env=env,
                             timeout=300)
    assert refused.returncode != 0
    assert "lora-alpha" in refused.stderr + refused.stdout
    merged = subprocess.run(base + ["--lora-alpha=4"], capture_output=True,
                            text=True, env=env, timeout=300)
    assert merged.returncode == 0, merged.stderr[-1500:]
    assert "LoRA merged" in merged.stderr
    # bare --lora-alpha would silently mean alpha=1 — rejected
    bare = subprocess.run(base + ["--lora-alpha"], capture_output=True,
                          text=True, env=env, timeout=300)
    assert bare.returncode != 0
    assert "explicit value" in bare.stderr + bare.stdout
    # --avg-last over LoRA checkpoints is nonlinear in the factors
    avg = subprocess.run(base + ["--lora-alpha=4", "--avg-last=2"],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert avg.returncode != 0
    assert "nonlinear" in avg.stderr + avg.stdout


def test_init_ckpt_dir_rejects_adapter_store(tmp_path):
    """--init-ckpt-dir pointing at a LoRA run errors explicitly: with
    --lora it would overwrite trained factors, without it the adapters
    would ride along inert."""
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    ckpt = str(tmp_path / "ft")
    run_training(TrainLoopConfig(
        model="tiny_lm", batch_size=4, steps=2, optimizer="adam",
        learning_rate=1e-2, lora="2:4", checkpoint_dir=ckpt,
        checkpoint_every=2, log_every=2))
    for lora in ("2:4", ""):
        with pytest.raises(ValueError, match="already contains LoRA"):
            run_training(TrainLoopConfig(
                model="tiny_lm", batch_size=4, steps=2, lora=lora,
                init_ckpt_dir=ckpt, log_every=2))


def test_spec_parsing_and_errors():
    assert split_rank_alpha("8") == (8, 16.0)
    assert split_rank_alpha("4:32") == (4, 32.0)
    with pytest.raises(ValueError, match="--lora"):
        split_rank_alpha("abc")
    with pytest.raises(ValueError, match="rank"):
        split_rank_alpha("0")
    with pytest.raises(ValueError, match="no parameters match"):
        init_lora({"w": jnp.zeros((4, 4))}, targets=DEFAULT_TARGETS)
    # mask shape matches the store
    p = init_lora({"x/attn/wq": jnp.zeros((4, 4))}, rank=2)
    mask = trainable_mask(p)
    assert mask["x/attn/wq/lora_a"] and not mask["x/attn/wq"]


def test_lora_composes_with_pipeline(rng):
    """LoRA x pipeline: adapters follow the blocks/* restack ([P, Lc, d, r]
    factors), and lora_value_and_grad differentiates through the adapter
    collapse around the 1F1B schedule.  At init (B = 0) the loss equals
    the base pipelined model's; dL/dA = dW @ B^T = 0 while dL/dB != 0 —
    exactly the vjp chain through W_eff = W + scale * A @ B."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models.lora import (
        init_lora, lora_value_and_grad)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    piped = PipelinedTransformerLM(Transformer(config), mesh,
                                   num_microbatches=2, schedule="1f1b")
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    base_params = piped.init_params(0)
    params = init_lora(base_params, rank=2, rng=1)
    assert params["blocks/attn/wq/lora_a"].shape == (2, 2, 32, 2)
    assert params["blocks/attn/wq/lora_b"].shape == (2, 2, 2, 32)

    vg = jax.jit(lora_value_and_grad(piped.value_and_grad, alpha=4.0))
    loss0, grads = vg(params, tokens)
    loss_base, _ = jax.jit(piped.value_and_grad)(base_params, tokens)
    np.testing.assert_allclose(float(loss0), float(loss_base), rtol=1e-5)
    assert float(np.abs(np.asarray(
        grads["blocks/attn/wq/lora_b"])).max()) > 0
    np.testing.assert_allclose(
        np.asarray(grads["blocks/attn/wq/lora_a"]), 0.0, atol=1e-7)
    # base cotangents pass through the collapse unchanged
    assert float(np.abs(np.asarray(grads["blocks/attn/wq"])).max()) > 0


def test_train_loop_lora_pipeline_and_ema(tmp_path):
    """The full round-5 composition: --lora x pipeline (1F1B) x --ema in
    one run_training — adapters train under the pipe schedule, the EMA
    shadow tracks only the adapters (freeze_base masks params_ema), and
    the end-of-run eval grafts the shadowed adapters onto the frozen base
    to report ema_eval_loss."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    summary = run_training(TrainLoopConfig(
        model="small_lm4", batch_size=8, steps=4, optimizer="adam",
        learning_rate=1e-2, lora="2:4", ema=0.5, eval_every=2,
        log_every=2, pipeline_schedule="1f1b",
        mesh=MeshConfig(pipeline=2, data=4)))
    assert summary["steps"] == 4
    assert np.isfinite(summary["final_loss"])
    assert np.isfinite(summary["eval_loss"])
    assert summary["ema_eval_loss"] is not None
    assert np.isfinite(summary["ema_eval_loss"])


def test_lora_ema_shadow_tracks_adapters_only(rng):
    """--ema x --lora at the optimizer level: freeze_base(make_optimizer
    (ema_decay>0)) masks params_ema to the adapters, extract_ema returns
    MaskedNode for frozen entries, and the grafted store (shadowed
    adapters on the frozen base) is the EMA of the full store."""
    import optax

    from parameter_server_distributed_tpu.models.lora import (
        freeze_base, init_lora, lora_loss, trainable_mask)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.train_step import (
        extract_ema, make_optimizer)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    model = Transformer(config)
    params = init_lora(model.init_params(0), rank=2, rng=1)
    loss_fn = lora_loss(model.loss, alpha=4.0)
    opt = freeze_base(make_optimizer("adam", 1e-2, ema_decay=0.5))
    state = opt.init(params)
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)

    shadows = []
    for _ in range(3):
        grads = jax.grad(loss_fn)(params, tokens)
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        ema = extract_ema(state)
        assert ema is not None
        shadows.append(ema)
    mask = trainable_mask(params)
    for name, trains in mask.items():
        if trains:
            assert isinstance(ema[name], jax.Array), name
        else:
            assert isinstance(ema[name], optax.MaskedNode), name
    # decay 0.5: shadow lags the live adapter, converging toward it
    live = np.asarray(params["layer0/attn/wq/lora_b"])
    shadow = np.asarray(shadows[-1]["layer0/attn/wq/lora_b"])
    assert np.abs(shadow).max() > 0
    assert not np.allclose(shadow, live)


def test_lora_ema_survives_resume(tmp_path):
    """--lora x --ema x --resume: the masked EmaState (MaskedNode
    placeholders for frozen base entries) must round-trip the sharded
    checkpoint template restore, and the resumed run still reports
    ema_eval_loss (the advisor flagged template-free restores degrading
    NamedTuples — the template path must not)."""
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    config = dict(
        model="tiny_lm", batch_size=4, steps=4, optimizer="adam",
        learning_rate=1e-2, lora="2:4", ema=0.7, eval_every=4,
        eval_steps=1, checkpoint_dir=str(tmp_path / "ft"),
        checkpoint_every=4, log_every=2)
    first = run_training(TrainLoopConfig(**config))
    assert np.isfinite(first["ema_eval_loss"])
    resumed = run_training(TrainLoopConfig(**config, resume=True))
    assert resumed["steps"] == 4            # nothing further to train
    assert np.isfinite(resumed["ema_eval_loss"])


def test_lora_composes_with_moe_and_converted_arch_1f1b(rng):
    """Two more cells of the composition matrix: (a) LoRA on an all-MoE
    LM — adapters target the attention projections, router/experts stay
    frozen base weights; (b) LoRA through the 1F1B schedule on a
    GPT-2-ARCH config (learned positions + layernorm + biases), the
    round-5 converted-checkpoint path."""
    import optax

    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models.lora import (
        freeze_base, init_lora, lora_loss, lora_value_and_grad)
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig, switch_lm)
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    # (a) MoE: one masked adam step moves adapters only
    moe = switch_lm(vocab=128, seq=16)
    params = init_lora(moe.init_params(0), rank=2, rng=1)
    opt = freeze_base(optax.adam(1e-2))
    state = opt.init(params)
    tokens = rng.integers(0, 128, (4, 16)).astype(np.int32)
    loss_fn = lora_loss(moe.loss, alpha=4.0)
    grads = jax.jit(jax.grad(loss_fn))(params, tokens)
    updates, state = opt.update(grads, state, params)
    new = optax.apply_updates(params, updates)
    assert float(np.abs(np.asarray(
        new["layer0/attn/wq/lora_b"]
        - params["layer0/attn/wq/lora_b"])).max()) > 0
    np.testing.assert_array_equal(np.asarray(new["layer0/moe/w1"]),
                                  np.asarray(params["layer0/moe/w1"]))
    np.testing.assert_array_equal(np.asarray(new["layer0/moe/router/w"]),
                                  np.asarray(params["layer0/moe/router/w"]))

    # (b) GPT-2 arch x LoRA x 1F1B: collapse-wrapped schedule grads —
    # at init (B=0) loss equals base, dL/dB flows, base cotangents exist
    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               pos_emb="learned", norm="layernorm",
                               bias=True, mlp_act="gelu")
    piped = PipelinedTransformerLM(Transformer(config), mesh,
                                   num_microbatches=2, schedule="1f1b")
    base_params = piped.init_params(0)
    lparams = init_lora(base_params, rank=2, rng=1)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    vg = jax.jit(lora_value_and_grad(piped.value_and_grad, alpha=4.0))
    loss0, grads = vg(lparams, tokens)
    base_loss, _ = jax.jit(piped.value_and_grad)(base_params, tokens)
    # B=0 at init: the adapted model IS the base model
    np.testing.assert_allclose(float(loss0), float(base_loss), rtol=1e-5)
    assert float(np.abs(np.asarray(
        grads["blocks/attn/wq/lora_b"])).max()) > 0
    assert float(np.abs(np.asarray(grads["embed/pos"])).max()) > 0
