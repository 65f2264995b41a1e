"""Pipeline parallelism: pipelined result == sequential stage application."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.parallel.mesh import build_mesh
from parameter_server_distributed_tpu.parallel.pipeline import (
    pipeline_apply, stack_stage_params)


def stage_fn(params, h):
    return jax.nn.tanh(h @ params["w"] + params["b"])


def make_stages(rng, n_stages, d):
    return [{"w": rng.standard_normal((d, d)).astype(np.float32) * 0.5,
             "b": rng.standard_normal(d).astype(np.float32) * 0.1}
            for _ in range(n_stages)]


def sequential(stages, x):
    h = x
    for p in stages:
        h = stage_fn(p, h)
    return h


@pytest.mark.parametrize("n_pipe,microbatches", [(2, 4), (4, 4), (4, 8)])
def test_pipeline_matches_sequential(rng, n_pipe, microbatches):
    mesh = build_mesh(MeshConfig(pipeline=n_pipe, data=8 // n_pipe))
    d = 16
    stages = make_stages(rng, n_pipe, d)
    x = rng.standard_normal((32, d)).astype(np.float32)
    expect = np.asarray(sequential(stages, jnp.asarray(x)))
    stacked = stack_stage_params([{k: jnp.asarray(v) for k, v in s.items()}
                                  for s in stages], mesh)
    got = np.asarray(jax.jit(lambda p: pipeline_apply(
        stage_fn, p, x, mesh, microbatches))(stacked))
    np.testing.assert_allclose(got, expect, rtol=2e-5, atol=2e-5)


def test_pipeline_gradients_match_sequential(rng):
    mesh = build_mesh(MeshConfig(pipeline=4, data=2))
    d = 8
    stages = make_stages(rng, 4, d)
    x = rng.standard_normal((16, d)).astype(np.float32)
    stacked = stack_stage_params([{k: jnp.asarray(v) for k, v in s.items()}
                                  for s in stages], mesh)

    def loss_pipe(params):
        return jnp.sum(pipeline_apply(stage_fn, params, x, mesh, 4) ** 2)

    def loss_seq(stage_list):
        return jnp.sum(sequential(stage_list, jnp.asarray(x)) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stacked)
    g_seq = jax.jit(jax.grad(loss_seq))(stages)
    for i in range(4):
        np.testing.assert_allclose(np.asarray(g_pipe["w"][i]),
                                   np.asarray(g_seq[i]["w"]),
                                   rtol=5e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g_pipe["b"][i]),
                                   np.asarray(g_seq[i]["b"]),
                                   rtol=5e-4, atol=1e-5)


def test_pipeline_single_stage_passthrough(rng):
    mesh = build_mesh(MeshConfig(data=8))
    d = 8
    stages = make_stages(rng, 1, d)
    x = rng.standard_normal((8, d)).astype(np.float32)
    stacked = stack_stage_params([{k: jnp.asarray(v) for k, v in stages[0].items()}],
                                 mesh)
    got = np.asarray(pipeline_apply(stage_fn, stacked, x, mesh, 4))
    expect = np.asarray(sequential(stages, jnp.asarray(x)))
    np.testing.assert_allclose(got, expect, rtol=1e-5)


def test_pipeline_rejects_indivisible_microbatches(rng):
    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    stages = make_stages(rng, 2, 8)
    stacked = stack_stage_params([{k: jnp.asarray(v) for k, v in s.items()}
                                  for s in stages], mesh)
    x = np.zeros((8, 8), np.float32)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_apply(stage_fn, stacked, x, mesh, 3)


# ---------------------------------------------------------------------------
# PipelinedTransformerLM: the full-model training mode (embed -> pipelined
# blocks -> head), gradients exact vs the non-pipelined Transformer
# ---------------------------------------------------------------------------

def _lm_fixtures(rng, n_layers=4, pipe=2, seq=16, batch=8):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=pipe, data=8 // pipe))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                               n_layers=n_layers, d_ff=64, max_seq=seq,
                               dtype=jnp.float32)
    plain = Transformer(config)
    piped = PipelinedTransformerLM(plain, mesh, num_microbatches=2)
    tokens = rng.integers(0, 64, (batch, seq)).astype(np.int32)
    return plain, piped, mesh, tokens


def _restack_grads(piped, flat_grads):
    """Flat per-layer grads -> the pipelined blocks/ layout, for
    comparison — the model's own checkpoint-restack transform."""
    return {name: np.asarray(value) for name, value in
            piped.restack_params(flat_grads).items()}


def test_pipelined_lm_loss_matches_plain(rng):
    plain, piped, mesh, tokens = _lm_fixtures(rng)
    piped_params = piped.init_params(0)
    plain_params = plain.init_params(0)
    loss_plain = float(jax.jit(plain.loss)(plain_params, tokens))
    loss_piped = float(jax.jit(piped.loss)(piped_params, tokens))
    np.testing.assert_allclose(loss_piped, loss_plain, rtol=1e-5)


def test_pipelined_lm_gradients_match_plain(rng):
    """jax.grad through the GPipe schedule == grad of the sequential model,
    for every parameter (the 'verify gradients equal the non-pipelined
    run' contract)."""
    plain, piped, mesh, tokens = _lm_fixtures(rng)
    plain_params = plain.init_params(0)
    piped_params = piped.init_params(0)
    g_plain = jax.jit(jax.grad(plain.loss))(plain_params, tokens)
    g_piped = jax.jit(jax.grad(piped.loss))(piped_params, tokens)
    expected = _restack_grads(piped, {k: np.asarray(v)
                                      for k, v in g_plain.items()})
    assert set(expected) == set(g_piped)
    for name in sorted(expected):
        np.testing.assert_allclose(
            np.asarray(g_piped[name]), expected[name], rtol=2e-4, atol=1e-5,
            err_msg=f"gradient mismatch for {name}")


def test_pipelined_lm_trains_in_sharded_trainer(rng):
    """ShardedTrainer + pipeline_rule: one step updates the pipe-sharded
    state and matches the equivalent non-pipelined step."""
    from parameter_server_distributed_tpu.parallel.pipeline import (
        pipeline_rule)
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, make_optimizer)
    from parameter_server_distributed_tpu.models.transformer import (
        transformer_rule)

    plain, piped, mesh, tokens = _lm_fixtures(rng)
    trainer = ShardedTrainer(piped.loss, mesh, pipeline_rule(mesh),
                             make_optimizer("sgd", 0.1))
    state = trainer.init_state(piped.init_params(0))
    # block params actually live sharded over pipe
    spec = state.params["blocks/attn/wq"].sharding.spec
    assert spec[0] == "pipe"
    state, metrics = trainer.step(state, tokens)
    assert np.isfinite(float(metrics["loss"]))

    # reference: the plain model on a data-only mesh, same sgd step
    dmesh = build_mesh(MeshConfig(data=8))
    ref = ShardedTrainer(plain.loss, dmesh, transformer_rule(dmesh),
                         make_optimizer("sgd", 0.1))
    ref_state = ref.init_state(plain.init_params(0))
    ref_state, ref_metrics = ref.step(ref_state, tokens)
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(ref_metrics["loss"]), rtol=1e-5)
    got = np.asarray(state.params["blocks/mlp/w1"])[0, 0]
    want = np.asarray(ref_state.params["layer0/mlp/w1"])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


def test_run_training_pipeline_mode(rng):
    """train_main --mesh=pipe:2,data:4 trains the LM end to end."""
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    config = TrainLoopConfig(
        model="small_lm", batch_size=8, steps=6, optimizer="sgd",
        learning_rate=0.5, mesh=MeshConfig(pipeline=2, data=4),
        microbatches=2, log_every=2)
    summary = run_training(config)
    assert summary["steps"] == 6
    assert np.isfinite(summary["final_loss"])


def test_pipeline_rejects_bad_configs(rng):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    with pytest.raises(ValueError, match="divide"):
        PipelinedTransformerLM(
            Transformer(TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                          n_layers=3, d_ff=64,
                                          dtype=jnp.float32)), mesh)
    with pytest.raises(ValueError, match="Transformer"):
        PipelinedTransformerLM(object(), mesh)


def test_pipelined_lm_remat_gradients_match(rng):
    """config.remat flows into the pipeline stages (jax.checkpoint per
    block) without changing loss or gradients."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    plain, piped, mesh, tokens = _lm_fixtures(rng)
    remat_model = Transformer(dataclasses.replace(plain.config, remat=True))
    piped_remat = PipelinedTransformerLM(remat_model, mesh,
                                         num_microbatches=2)
    params = piped.init_params(0)
    g_a = jax.jit(jax.grad(piped.loss))(params, tokens)
    g_b = jax.jit(jax.grad(piped_remat.loss))(params, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_b[name]),
                                   np.asarray(g_a[name]), rtol=1e-5,
                                   atol=1e-7, err_msg=name)


def test_pipelined_lm_chunked_loss_matches(rng):
    """config.loss_chunk flows through the pipelined loss: same loss and
    gradients as the unchunked pipelined run."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    plain, piped, mesh, tokens = _lm_fixtures(rng)
    chunked_model = Transformer(dataclasses.replace(plain.config,
                                                    loss_chunk=4))
    piped_chunked = PipelinedTransformerLM(chunked_model, mesh,
                                           num_microbatches=2)
    params = piped.init_params(0)
    la = float(jax.jit(piped.loss)(params, tokens))
    lb = float(jax.jit(piped_chunked.loss)(params, tokens))
    np.testing.assert_allclose(lb, la, rtol=1e-6)
    g_a = jax.jit(jax.grad(piped.loss))(params, tokens)
    g_b = jax.jit(jax.grad(piped_chunked.loss))(params, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_b[name]),
                                   np.asarray(g_a[name]), rtol=2e-5,
                                   atol=1e-7, err_msg=name)


# ---------------------------------------------------------------------------
# 1F1B schedule: hand-written interleaved fwd/bwd must be grad-exact vs the
# non-pipelined model (same contract the GPipe tests prove for autodiff)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipe,microbatches", [(2, 4), (4, 4), (4, 8)])
def test_pipelined_lm_1f1b_matches_plain(rng, pipe, microbatches):
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    plain, _, mesh, tokens = _lm_fixtures(rng, pipe=pipe,
                                          batch=microbatches * (8 // pipe))
    piped = PipelinedTransformerLM(plain, mesh,
                                   num_microbatches=microbatches,
                                   schedule="1f1b")
    l_plain, g_plain = jax.jit(jax.value_and_grad(plain.loss))(
        plain.init_params(0), tokens)
    l_piped, g_piped = jax.jit(piped.value_and_grad)(piped.init_params(0),
                                                     tokens)
    np.testing.assert_allclose(float(l_piped), float(l_plain), rtol=1e-5)
    expected = _restack_grads(piped, {k: np.asarray(v)
                                      for k, v in g_plain.items()})
    assert set(expected) == set(g_piped)
    for name in sorted(expected):
        np.testing.assert_allclose(
            np.asarray(g_piped[name]), expected[name], rtol=2e-4, atol=1e-5,
            err_msg=f"1f1b gradient mismatch for {name}")


def test_pipelined_lm_1f1b_remat_and_chunked(rng):
    """config.remat (per-block checkpoint inside the stage vjp) and
    loss_chunk both compose with the 1F1B schedule unchanged."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    plain, _, mesh, tokens = _lm_fixtures(rng)
    base = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                  schedule="1f1b")
    params = base.init_params(0)
    l_a, g_a = jax.jit(base.value_and_grad)(params, tokens)
    for override in (dict(remat=True), dict(loss_chunk=4)):
        variant_model = Transformer(dataclasses.replace(plain.config,
                                                        **override))
        variant = PipelinedTransformerLM(variant_model, mesh,
                                         num_microbatches=2,
                                         schedule="1f1b")
        l_b, g_b = jax.jit(variant.value_and_grad)(params, tokens)
        np.testing.assert_allclose(float(l_b), float(l_a), rtol=1e-5)
        for name in g_a:
            np.testing.assert_allclose(
                np.asarray(g_b[name]), np.asarray(g_a[name]), rtol=2e-5,
                atol=1e-6, err_msg=f"{override}: {name}")


def test_pipelined_lm_1f1b_trains_in_sharded_trainer(rng):
    """ShardedTrainer with the 1F1B grad_fn: one sgd step equals the
    GPipe-scheduled step (same grads -> same update)."""
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM, pipeline_rule)
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, make_optimizer)

    plain, piped_gpipe, mesh, tokens = _lm_fixtures(rng)
    piped_1f1b = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                        schedule="1f1b")
    kw = dict(mesh=mesh, rule=pipeline_rule(mesh),
              optimizer=make_optimizer("sgd", 0.1))
    t_a = ShardedTrainer(piped_gpipe.loss, kw["mesh"], kw["rule"],
                         kw["optimizer"])
    t_b = ShardedTrainer(piped_1f1b.loss, kw["mesh"], kw["rule"],
                         kw["optimizer"], grad_fn=piped_1f1b.value_and_grad)
    s_a = t_a.init_state(piped_gpipe.init_params(0))
    s_b = t_b.init_state(piped_1f1b.init_params(0))
    s_a, m_a = t_a.step(s_a, tokens)
    s_b, m_b = t_b.step(s_b, tokens)
    np.testing.assert_allclose(float(m_b["loss"]), float(m_a["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m_b["grad_norm"]),
                               float(m_a["grad_norm"]), rtol=2e-4)
    for name in s_a.params:
        np.testing.assert_allclose(np.asarray(s_b.params[name]),
                                   np.asarray(s_a.params[name]), rtol=2e-4,
                                   atol=1e-6, err_msg=name)


def test_pipeline_rejects_bad_schedule(rng):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    model = Transformer(TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                          n_layers=2, d_ff=64,
                                          dtype=jnp.float32))
    with pytest.raises(ValueError, match="schedule"):
        PipelinedTransformerLM(model, mesh, schedule="pipedream")


def test_run_training_pipeline_1f1b_mode(rng):
    """train_main --mesh=pipe:2,data:4 --pipeline-schedule=1f1b trains."""
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    config = TrainLoopConfig(
        model="small_lm", batch_size=8, steps=4, optimizer="sgd",
        learning_rate=0.5, mesh=MeshConfig(pipeline=2, data=4),
        microbatches=2, pipeline_schedule="1f1b", log_every=2)
    summary = run_training(config)
    assert summary["steps"] == 4
    assert np.isfinite(summary["final_loss"])


# ---------------------------------------------------------------------------
# Interleaved 1F1B (virtual stages): Megatron round-robin chunk schedule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipe,virtual,microbatches", [(2, 2, 4), (4, 2, 4),
                                                       (2, 4, 6), (4, 2, 6)])
def test_pipelined_lm_interleaved_matches_plain(rng, pipe, virtual,
                                                microbatches):
    """virtual_stages > 1: loss and every gradient equal the non-pipelined
    model — covers ragged microbatch groups (M % P != 0) too."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=pipe, data=8 // pipe))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=8,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    plain = Transformer(config)
    piped = PipelinedTransformerLM(plain, mesh,
                                   num_microbatches=microbatches,
                                   schedule="1f1b",
                                   virtual_stages=virtual)
    tokens = rng.integers(
        0, 64, (microbatches * (8 // pipe), 16)).astype(np.int32)
    l_plain, g_plain = jax.jit(jax.value_and_grad(plain.loss))(
        plain.init_params(0), tokens)
    params = piped.init_params(0)
    l_eval = float(jax.jit(piped.loss)(params, tokens))  # V-pass GPipe fwd
    np.testing.assert_allclose(l_eval, float(l_plain), rtol=1e-5)
    l_piped, g_piped = jax.jit(piped.value_and_grad)(params, tokens)
    np.testing.assert_allclose(float(l_piped), float(l_plain), rtol=1e-5)

    lc = piped.layers_per_stage
    for layer in range(config.n_layers):
        stage, j = divmod(layer, lc)
        c, r = divmod(stage, pipe)
        for suffix in ("mlp/w1", "attn/wq", "ln1/scale"):
            np.testing.assert_allclose(
                np.asarray(g_piped[f"blocks/{suffix}"])[r, c, j],
                np.asarray(g_plain[f"layer{layer}/{suffix}"]),
                rtol=2e-4, atol=1e-5,
                err_msg=f"layer {layer} (stage {stage} -> rank {r} "
                        f"chunk {c} slot {j}) {suffix}")
    for name in ("embed/tok", "lm_head/w", "final_ln/scale"):
        np.testing.assert_allclose(np.asarray(g_piped[name]),
                                   np.asarray(g_plain[name]), rtol=2e-4,
                                   atol=1e-5, err_msg=name)


def test_interleaved_rejects_bad_configs(rng):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    model = Transformer(TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                          n_layers=8, d_ff=64,
                                          dtype=jnp.float32))
    with pytest.raises(ValueError, match="1f1b"):
        PipelinedTransformerLM(model, mesh, virtual_stages=2)  # gpipe
    with pytest.raises(ValueError, match="divide"):
        PipelinedTransformerLM(model, mesh, schedule="1f1b",
                               virtual_stages=3)  # 8 % (2*3) != 0


def test_run_training_interleaved_mode(rng):
    """--mesh=pipe:2,data:4 --pipeline-schedule=1f1b --virtual-stages=2."""
    import dataclasses

    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    config = TrainLoopConfig(
        model="small_lm4", batch_size=8, steps=3, optimizer="sgd",
        learning_rate=0.5, mesh=MeshConfig(pipeline=2, data=4),
        microbatches=2, pipeline_schedule="1f1b", virtual_stages=2,
        log_every=2)
    summary = run_training(config)
    assert summary["steps"] == 3
    assert np.isfinite(summary["final_loss"])


@pytest.mark.parametrize("virtual", [1, 2])
def test_flat_params_roundtrip(rng, virtual):
    """flat_params inverts init_params' restack in both layouts, so a
    pipeline-trained checkpoint loads into the plain Transformer."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    plain = Transformer(config)
    piped = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                   schedule="1f1b", virtual_stages=virtual)
    flat = plain.init_params(0)
    back = piped.flat_params(piped.init_params(0))
    assert set(back) == set(flat)
    for name in flat:
        np.testing.assert_array_equal(np.asarray(back[name]),
                                      np.asarray(flat[name]), err_msg=name)
    # and the plain model actually runs on the round-tripped store
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    l_a = float(jax.jit(plain.loss)(flat, tokens))
    l_b = float(jax.jit(plain.loss)(back, tokens))
    np.testing.assert_allclose(l_b, l_a, rtol=1e-6)


def test_pipeline_trained_checkpoint_serves_plain_generation(rng, tmp_path,
                                                             capsys):
    """End to end: train under the interleaved-1F1B pipeline, flatten the
    store with flat_params, write the reference-format host checkpoint,
    and decode from it with the plain pst-generate CLI — the
    train-pipelined / serve-unwrapped round trip."""
    from parameter_server_distributed_tpu.checkpoint import codec
    from parameter_server_distributed_tpu.cli import generate_main
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM, pipeline_rule)
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, make_optimizer)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    model, batches = get_model_and_batches("small_lm4", 8, seed=0)
    piped = PipelinedTransformerLM(model, mesh, num_microbatches=2,
                                   schedule="1f1b", virtual_stages=2)
    trainer = ShardedTrainer(piped.loss, mesh, pipeline_rule(mesh),
                             make_optimizer("sgd", 0.1),
                             grad_fn=piped.value_and_grad)
    state = trainer.init_state(piped.init_params(0))
    for _ in range(2):
        state, metrics = trainer.step(state, next(batches))
    assert np.isfinite(float(metrics["loss"]))

    flat = piped.flat_params({k: np.asarray(v)
                              for k, v in state.params.items()})
    path = str(tmp_path / "piped.ckpt")
    codec.save(path, epoch=1, iteration=2, params=flat)

    rc = generate_main.main([
        "--model=small_lm4", f"--ckpt={path}", "--tokens=1,2,3",
        "--max-new=4"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.strip()  # decoded token ids printed


# ----------------------------------------------------------- pipeline x MoE

def test_pipelined_moe_matches_per_microbatch_reference(rng):
    """pipe x MoE (moe_every=1, gpipe): the pipelined loss must equal the
    mean over microbatches of the plain MoE model's loss on each
    microbatch — expert capacity (and therefore token dropping) is a
    per-microbatch statistic under pipelining, exactly as it is under any
    microbatched MoE schedule."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    piped = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                   schedule="gpipe")
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    piped_params = piped.init_params(0)
    plain_params = plain.init_params(0)

    loss_piped = float(jax.jit(piped.loss)(piped_params, tokens))
    # reference: the plain model on each (data shard, microbatch) piece —
    # data rank d holds rows [2d, 2d+2), microbatch m is its m-th row
    pieces = [tokens[row:row + 1] for row in range(tokens.shape[0])]
    loss_ref = float(np.mean([jax.jit(plain.loss)(plain_params, piece)
                              for piece in pieces]))
    np.testing.assert_allclose(loss_piped, loss_ref, rtol=1e-5)


def test_pipelined_moe_gradients_flow_to_experts(rng):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, expert=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    piped = PipelinedTransformerLM(Transformer(config), mesh,
                                   num_microbatches=2, schedule="gpipe")
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    params = piped.init_params(0)
    grads = jax.jit(jax.grad(piped.loss))(params, tokens)
    assert "blocks/moe/w1" in grads
    for name in ("blocks/moe/w1", "blocks/moe/w2", "blocks/moe/router/w"):
        assert float(np.abs(np.asarray(grads[name])).max()) > 0, name


def test_pipeline_moe_rejections(rng):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    interleaved = Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_seq=16,
        moe_every=2, moe_experts=4))
    with pytest.raises(ValueError, match="homogeneous"):
        PipelinedTransformerLM(interleaved, mesh)
    # 1F1B x MoE composes since round 5 (aux threads through the
    # backward wave) — construction must NOT raise
    all_moe = Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_seq=16,
        moe_every=1, moe_experts=4))
    piped = PipelinedTransformerLM(all_moe, mesh, schedule="1f1b")
    assert piped.schedule == "1f1b"


def test_pipelined_moe_expert_sharded_matches_replicated(rng):
    """pipe x EXPERT 2-D sharding: every block's expert weights split over
    the mesh's expert axis (each rank computes its local experts' partial
    output, psum over 'expert' combines).  A pure factorization — must be
    numerically identical to the expert-replicated pipeline and therefore
    to the per-microbatch plain reference."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    plain_params = plain.init_params(0)

    mesh_ep = build_mesh(MeshConfig(pipeline=2, expert=2, data=2))
    piped_ep = PipelinedTransformerLM(plain, mesh_ep, num_microbatches=2,
                                      schedule="gpipe")
    loss_ep = float(jax.jit(piped_ep.loss)(piped_ep.init_params(0), tokens))

    # comparison mesh replaces 'expert' with the (pipeline-unused)
    # 'tensor' axis so the data split — and therefore the per-microbatch
    # expert capacity — is IDENTICAL; only the expert factorization differs
    mesh_rep = build_mesh(MeshConfig(pipeline=2, tensor=2, data=2))
    piped_rep = PipelinedTransformerLM(plain, mesh_rep, num_microbatches=2,
                                       schedule="gpipe")
    loss_rep = float(jax.jit(piped_rep.loss)(piped_rep.init_params(0),
                                             tokens))
    np.testing.assert_allclose(loss_ep, loss_rep, rtol=1e-5)

    # gradients flow to the sharded expert weights
    grads = jax.jit(jax.grad(piped_ep.loss))(piped_ep.init_params(0), tokens)
    for name in ("blocks/moe/w1", "blocks/moe/w2", "blocks/moe/router/w"):
        assert float(np.abs(np.asarray(grads[name])).max()) > 0, name


def test_pipelined_moe_1f1b_matches_gpipe(rng):
    """1F1B x MoE: the hand-written schedule threads the aux-loss
    accumulator (each valid unit's aux read off the backward vjp's primal,
    cotangent seeded with moe_aux_coef), so loss AND gradients must match
    GPipe-by-autodiff on the same microbatch split — the two schedules
    are different orderings of identical math."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    gp = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                schedule="gpipe")
    fb = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                schedule="1f1b")
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    params = gp.init_params(0)
    loss_g, grads_g = jax.jit(gp.value_and_grad)(params, tokens)
    loss_f, grads_f = jax.jit(fb.value_and_grad)(params, tokens)
    np.testing.assert_allclose(float(loss_f), float(loss_g), rtol=1e-5)
    assert set(grads_f) == set(grads_g)
    for name in grads_g:
        np.testing.assert_allclose(np.asarray(grads_f[name]),
                                   np.asarray(grads_g[name]),
                                   rtol=5e-4, atol=1e-6, err_msg=name)
    # router/expert gradients actually flow under 1F1B
    for name in ("blocks/moe/w1", "blocks/moe/w2", "blocks/moe/router/w"):
        assert float(np.abs(np.asarray(grads_f[name])).max()) > 0, name


def test_pipelined_moe_1f1b_expert_axis_rejected(rng):
    """1F1B x MoE x expert sharding is explicitly out of scope: the manual
    schedule seeds jax.vjp cotangents mid-shard_map, which breaks the
    unreduced-cotangent convention the expert psum transpose relies on
    (measured: expert grads come out exactly ep x too large).  GPipe owns
    expert parallelism — and its grads are verified correct below."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    mesh_ep = build_mesh(MeshConfig(pipeline=2, expert=2, data=2))
    fb_ep = PipelinedTransformerLM(plain, mesh_ep, num_microbatches=2,
                                   schedule="1f1b")
    with pytest.raises(ValueError, match="gpipe"):
        fb_ep.value_and_grad(fb_ep.init_params(0), tokens)


def test_pipelined_moe_expert_sharded_grads_match_replicated(rng):
    """GPipe x MoE x expert sharding, GRADIENT equality (the existing
    sharded-vs-replicated test checks the loss and grad flow only):
    differentiating the whole shard_map pairs the expert-psum transposes
    correctly, so every gradient must match the expert-replicated run."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)

    mesh_ep = build_mesh(MeshConfig(pipeline=2, expert=2, data=2))
    gp_ep = PipelinedTransformerLM(plain, mesh_ep, num_microbatches=2,
                                   schedule="gpipe")
    g_ep = jax.jit(jax.grad(gp_ep.loss))(gp_ep.init_params(0), tokens)

    mesh_rep = build_mesh(MeshConfig(pipeline=2, tensor=2, data=2))
    gp_rep = PipelinedTransformerLM(plain, mesh_rep, num_microbatches=2,
                                    schedule="gpipe")
    g_rep = jax.jit(jax.grad(gp_rep.loss))(gp_rep.init_params(0), tokens)
    for name in ("blocks/moe/w1", "blocks/moe/w2", "blocks/moe/router/w",
                 "blocks/attn/wq", "embed/tok"):
        np.testing.assert_allclose(np.asarray(g_ep[name]),
                                   np.asarray(g_rep[name]),
                                   rtol=5e-4, atol=1e-6, err_msg=name)


def test_pipelined_moe_1f1b_interleaved_matches_plain_1f1b(rng):
    """1F1B x MoE x virtual stages: interleaving re-chunks the SAME layer
    sequence over the same microbatch split, so V=2 must reproduce V=1
    exactly."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=1, moe_experts=4)
    plain = Transformer(config)
    v1 = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                schedule="1f1b")
    v2 = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                schedule="1f1b", virtual_stages=2)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    loss1, grads1 = jax.jit(v1.value_and_grad)(v1.init_params(0), tokens)
    loss2, grads2 = jax.jit(v2.value_and_grad)(v2.init_params(0), tokens)
    np.testing.assert_allclose(float(loss2), float(loss1), rtol=1e-5)
    # layouts differ ([P,Lc] vs [P,V,Lc']) — compare through flat_params
    flat1 = v1.flat_params(grads1)
    flat2 = v2.flat_params(grads2)
    for name in flat1:
        np.testing.assert_allclose(np.asarray(flat2[name]),
                                   np.asarray(flat1[name]),
                                   rtol=5e-4, atol=1e-6, err_msg=name)


def test_pipelined_gpt2_arch_matches_plain(rng):
    """Converted GPT-2-family configs (learned positions + layernorm +
    biases) pipeline under GPipe: the model's own embed adds the
    positional table, the stage helpers carry biases/LN, and loss AND
    gradients (positional table and biases included) match the plain
    model.  The hand-written 1F1B schedule keeps its native-arch guard."""
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.parallel.pipeline import (
        PipelinedTransformerLM)

    mesh = build_mesh(MeshConfig(pipeline=2, data=4))
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               pos_emb="learned", norm="layernorm",
                               bias=True, mlp_act="gelu")
    plain = Transformer(config)
    piped = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                   schedule="gpipe")
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    plain_params = plain.init_params(0)
    piped_params = piped.init_params(0)
    loss_plain = float(jax.jit(plain.loss)(plain_params, tokens))
    loss_piped = float(jax.jit(piped.loss)(piped_params, tokens))
    np.testing.assert_allclose(loss_piped, loss_plain, rtol=1e-5)

    g_plain = jax.jit(jax.grad(plain.loss))(plain_params, tokens)
    g_piped = jax.jit(jax.grad(piped.loss))(piped_params, tokens)
    expected = _restack_grads(piped, {k: np.asarray(v)
                                      for k, v in g_plain.items()})
    assert set(expected) == set(g_piped)
    # the params a raw token-embed pipeline would silently drop
    for name in ("embed/pos", "layer0/attn/bq", "final_ln/bias"):
        assert name in g_plain
    for name in sorted(expected):
        np.testing.assert_allclose(
            np.asarray(g_piped[name]), expected[name], rtol=3e-4,
            atol=1e-5, err_msg=name)

    # 1F1B covers GPT-2-family configs too since round 5: the schedule
    # injects via the model's embed (positional table included) and
    # scatters the positional-table gradient at the embed tick —
    # loss AND grads must match GPipe-by-autodiff exactly
    fb = PipelinedTransformerLM(plain, mesh, num_microbatches=2,
                                schedule="1f1b")
    loss_fb, g_fb = jax.jit(fb.value_and_grad)(piped_params, tokens)
    np.testing.assert_allclose(float(loss_fb), loss_piped, rtol=1e-5)
    for name in sorted(expected):
        np.testing.assert_allclose(
            np.asarray(g_fb[name]), expected[name], rtol=3e-4,
            atol=1e-5, err_msg=f"1f1b {name}")
    # the learned-position overflow guard survives the pipelining (the
    # plain model raises; embed's mode='clip' must not silently engage)
    with pytest.raises(ValueError, match="exceeds the"):
        piped.loss(piped_params,
                   rng.integers(0, 64, (8, 32)).astype(np.int32))
    with pytest.raises(ValueError, match="exceeds the"):
        fb.value_and_grad(piped_params,
                          rng.integers(0, 64, (8, 32)).astype(np.int32))
