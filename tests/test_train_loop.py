"""SPMD training loop + sharded checkpoint/resume tests."""

import json
import os

import numpy as np
import pytest

from parameter_server_distributed_tpu.checkpoint import sharded as sc
from parameter_server_distributed_tpu.cli.train_main import parse_mesh
from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.parallel.train_loop import (
    TrainLoopConfig, run_training)


def test_parse_mesh():
    config = parse_mesh("data:2,fsdp:2,tensor:2")
    assert (config.data, config.fsdp, config.tensor) == (2, 2, 2)
    assert parse_mesh("seq:4,pipe:2").sequence == 4
    assert parse_mesh("").num_devices == 1
    with pytest.raises(ValueError):
        parse_mesh("bogus:2")


def test_run_training_sharded_mesh(tmp_path):
    config = TrainLoopConfig(
        model="mnist_mlp", batch_size=32, steps=24, optimizer="sgd",
        learning_rate=0.05, mesh=MeshConfig(data=4, fsdp=2),
        log_every=4, metrics_path=str(tmp_path / "metrics.jsonl"))
    summary = run_training(config)
    assert summary["steps"] == 24 and summary["dp_size"] == 8
    assert np.isfinite(summary["final_loss"])
    lines = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert lines[-1]["step"] == 24
    assert lines[-1]["loss"] < lines[0]["loss"]  # learning signal


def test_sharded_checkpoint_roundtrip_and_reshard(tmp_path):
    import jax
    import jax.numpy as jnp
    from parameter_server_distributed_tpu.models.mlp import MLP
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.sharding import fsdp_rule
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, make_optimizer)

    model = MLP((16, 32, 8))
    mesh1 = build_mesh(MeshConfig(fsdp=8))
    trainer1 = ShardedTrainer(model.loss, mesh1, fsdp_rule(mesh1),
                              make_optimizer("momentum", 0.1))
    state1 = trainer1.init_state(model.init_params(0))
    rng = np.random.default_rng(0)
    batch = (rng.standard_normal((16, 16)).astype(np.float32),
             rng.integers(0, 8, 16).astype(np.int32))
    state1, _ = trainer1.step(state1, batch)
    path = sc.save_sharded(str(tmp_path), 1, state1)
    assert sc.latest_step(str(tmp_path)) == 1

    # restore into a DIFFERENT mesh/sharding (8-way fsdp -> 2x4)
    mesh2 = build_mesh(MeshConfig(data=4, fsdp=2))
    trainer2 = ShardedTrainer(model.loss, mesh2, fsdp_rule(mesh2),
                              make_optimizer("momentum", 0.1))
    state2 = trainer2.init_state(model.init_params(1))  # different init
    restored = sc.restore_sharded(path, template=state2)
    for k in state1.params:
        np.testing.assert_array_equal(np.asarray(restored.params[k]),
                                      np.asarray(state1.params[k]))
    assert int(np.asarray(restored.step)) == 1
    # restored state trains under the NEW mesh
    state3, metrics = trainer2.step(restored, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_train_loop_resume(tmp_path):
    ckpt_dir = str(tmp_path / "ck")
    base = dict(model="mnist_mlp", batch_size=16, optimizer="sgd",
                learning_rate=0.05, mesh=MeshConfig(data=2),
                checkpoint_dir=ckpt_dir, checkpoint_every=5, log_every=5)
    run_training(TrainLoopConfig(steps=5, **base))
    assert sc.latest_step(ckpt_dir) == 5
    summary = run_training(TrainLoopConfig(steps=10, resume=True, **base))
    assert summary["steps"] == 10
    assert sc.latest_step(ckpt_dir) == 10


@pytest.mark.parametrize("attention,mesh", [
    ("ring", MeshConfig(sequence=2, data=4)),
    ("ulysses", MeshConfig(sequence=2, data=2, fsdp=2)),
    ("dense", MeshConfig(data=2, fsdp=2, tensor=2)),
])
def test_run_training_attention_selection(attention, mesh):
    """--attention reaches run_training for every implementation: the LM
    trains on the corresponding mesh and the loss decreases."""
    config = TrainLoopConfig(
        model="small_lm", batch_size=8, steps=6, optimizer="sgd",
        learning_rate=0.5, attention=attention, mesh=mesh, log_every=2)
    summary = run_training(config)
    assert summary["steps"] == 6
    assert np.isfinite(summary["final_loss"])


def test_seq_mesh_drops_loss_chunk(monkeypatch):
    """Under sequence parallelism the chunked-cross-entropy scan would
    slice per-device shards out of the seq-sharded activations and
    serialize the LM head, so run_training disables it (per-device logits
    are already O(S/N * vocab) there); a seq-less mesh keeps it."""
    from parameter_server_distributed_tpu.models import registry as reg
    from parameter_server_distributed_tpu.parallel import train_loop as tl

    seen = {}
    real = reg.get_model_and_batches

    def spy(*args, **kwargs):
        model, batches = real(*args, **kwargs)
        import dataclasses
        model.config = dataclasses.replace(model.config, loss_chunk=8)
        seen["model"] = model
        return model, batches

    monkeypatch.setattr(tl, "get_model_and_batches", spy)
    config = TrainLoopConfig(
        model="small_lm", batch_size=4, steps=1, optimizer="sgd",
        attention="ring", mesh=MeshConfig(sequence=2, data=4))
    summary = run_training(config)
    assert np.isfinite(summary["final_loss"])
    assert seen["model"].config.loss_chunk == 0

    monkeypatch.setattr(tl, "get_model_and_batches", spy)
    summary = run_training(TrainLoopConfig(
        model="small_lm", batch_size=8, steps=1, optimizer="sgd",
        mesh=MeshConfig(data=8)))
    assert np.isfinite(summary["final_loss"])
    assert seen["model"].config.loss_chunk == 8


@pytest.mark.parametrize("mesh,q_shape,arm", [
    (MeshConfig(), (64, 1024, 16, 64), "kernel"),
    (MeshConfig(fsdp=2, tensor=2), (64, 1024, 20, 64), "sharded_kernel"),
], ids=["one-device", "fsdp2-tensor2"])
def test_run_training_leaves_the_default_path_to_the_model(monkeypatch, mesh,
                                                           q_shape, arm):
    """run_training hands the model its mesh through ``on_mesh``: under
    ``dense`` the model keeps ``attention_fn`` None, and its default path
    takes the arm tests/test_fused_attention.py::ARMS names for that mesh
    (the backend pretended a TPU once the CPU's steps are done)."""
    from parameter_server_distributed_tpu.models import registry as reg
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.parallel import train_loop as tl

    seen = {}
    real = reg.get_model_and_batches

    def spy(*args, **kwargs):
        seen["model"], batches = real(*args, **kwargs)
        return seen["model"], batches

    monkeypatch.setattr(tl, "get_model_and_batches", spy)
    summary = run_training(TrainLoopConfig(
        model="small_lm", batch_size=4, steps=1, optimizer="sgd", mesh=mesh))
    assert np.isfinite(summary["final_loss"])
    model = seen["model"]
    assert model.attention_fn is None
    assert model.mesh is not None and model.mesh.size == mesh.num_devices
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert model.default_arm(q_shape, q_shape, 0) == arm


def test_attention_flag_rejected_for_non_transformer():
    config = TrainLoopConfig(model="mnist_mlp", attention="ring", steps=1,
                             mesh=MeshConfig(data=8))
    with pytest.raises(ValueError, match="transformer"):
        run_training(config)


def test_checkpoint_retention(tmp_path):
    """--ckpt-keep prunes all but the newest N committed checkpoints."""
    config = TrainLoopConfig(
        model="mnist_mlp", batch_size=16, steps=12, optimizer="sgd",
        learning_rate=0.05, mesh=MeshConfig(data=8),
        checkpoint_dir=str(tmp_path), checkpoint_every=2,
        checkpoint_keep=2, log_every=6)
    run_training(config)
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert steps == [10, 12]
    # the survivors restore fine
    assert sc.latest_step(str(tmp_path)) == 12


def test_checkpoint_retention_with_final_fallback_save(tmp_path):
    """steps not a multiple of ckpt-every: the end-of-run fallback save
    must not leave keep+1 checkpoints behind."""
    config = TrainLoopConfig(
        model="mnist_mlp", batch_size=16, steps=13, optimizer="sgd",
        learning_rate=0.05, mesh=MeshConfig(data=8),
        checkpoint_dir=str(tmp_path), checkpoint_every=2,
        checkpoint_keep=2, log_every=6)
    run_training(config)
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert steps == [12, 13]


def test_eval_loop(tmp_path):
    """--eval-every runs held-out evaluation: eval_loss lands in the
    summary and the JSONL metrics, and evaluation never perturbs training
    (same final_loss with eval on and off)."""
    metrics = tmp_path / "m.jsonl"
    base = dict(model="small_lm", batch_size=8, steps=4, optimizer="sgd",
                learning_rate=0.1, mesh=MeshConfig(data=2), log_every=2)
    with_eval = run_training(TrainLoopConfig(
        **base, eval_every=2, eval_steps=2, metrics_path=str(metrics)))
    assert np.isfinite(with_eval["eval_loss"])
    lines = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert any("eval_loss" in entry for entry in lines)

    without = run_training(TrainLoopConfig(**base))
    assert without["final_loss"] == pytest.approx(with_eval["final_loss"])
    assert "eval_loss" not in without

    # gradient accumulation: eval scans the same microbatch split, and
    # the mean of equal-size microbatch means equals the full-batch mean
    # (same eval cadence -> same eval-stream batches as the accum=1 run)
    accum = run_training(TrainLoopConfig(
        **base, accum_steps=2, eval_every=2, eval_steps=2))
    assert accum["eval_loss"] == pytest.approx(with_eval["eval_loss"],
                                               rel=1e-4)


def test_checkpoint_averaging(tmp_path):
    """average_checkpoints: uniform f32 mean of the last K params, newest
    step's metadata, stored dtype preserved."""
    run_training(TrainLoopConfig(
        model="mnist_mlp", batch_size=16, steps=6, optimizer="sgd",
        learning_rate=0.1, mesh=MeshConfig(data=2),
        checkpoint_dir=str(tmp_path), checkpoint_every=2, log_every=6))
    import jax.numpy as jnp

    s4 = sc.restore_sharded(str(tmp_path / "step_4"))
    s6 = sc.restore_sharded(str(tmp_path / "step_6"))
    step, avg = sc.average_checkpoints(str(tmp_path), 2)
    assert step == 6
    p4 = s4["params"] if isinstance(s4, dict) else s4.params
    p6 = s6["params"] if isinstance(s6, dict) else s6.params
    pa = avg["params"] if isinstance(avg, dict) else avg.params
    for name in pa:
        expect = (np.asarray(p4[name], np.float32)
                  + np.asarray(p6[name], np.float32)) / 2
        np.testing.assert_allclose(np.asarray(pa[name], np.float32), expect,
                                   rtol=1e-6, err_msg=name)
        assert jnp.asarray(pa[name]).dtype == jnp.asarray(p6[name]).dtype

    none_step, none_state = sc.average_checkpoints(str(tmp_path / "nope"), 3)
    assert none_step is None and none_state is None


def test_params_ema_tracks_and_extracts():
    """params_ema keeps a Polyak shadow of the parameters inside the
    optimizer state: the recursion matches a hand computation, the
    shadow survives chaining (clip + sgd + ema), extract_ema finds it
    through the nested chain state, and invalid decays are rejected."""
    import jax.numpy as jnp
    import optax

    from parameter_server_distributed_tpu.parallel.train_step import (
        extract_ema, make_optimizer, params_ema)

    decay = 0.9
    opt = make_optimizer("sgd", 0.5, clip_norm=10.0, ema_decay=decay)
    params = {"w": jnp.asarray([2.0, -1.0], jnp.float32)}
    state = opt.init(params)
    expect_ema = np.asarray(params["w"])
    for step in range(4):
        grads = {"w": jnp.asarray([0.5, 0.5], jnp.float32)}
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        expect_ema = decay * expect_ema + (1 - decay) * np.asarray(
            params["w"])
    ema = extract_ema(state)
    np.testing.assert_allclose(np.asarray(ema["w"]), expect_ema, rtol=1e-6)
    # and the raw updates were NOT perturbed by the ema stage
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.asarray([2.0, -1.0]) - 4 * 0.25,
                               rtol=1e-6)

    assert extract_ema(make_optimizer("sgd", 0.1).init(params)) is None
    with pytest.raises(ValueError, match="decay"):
        params_ema(1.0)
    with pytest.raises(ValueError, match="decay"):
        params_ema(0.0)

    # bf16 regression: the shadow is kept in FLOAT32 — at decay 0.999
    # a bf16 shadow's per-step correction is below its half-ulp and
    # would round back to the init value forever
    opt16 = make_optimizer("sgd", 0.5, ema_decay=0.999)
    p16 = {"w": jnp.asarray([2.0], jnp.bfloat16)}
    s16 = opt16.init(p16)
    for _ in range(50):
        upd, s16 = opt16.update({"w": jnp.asarray([0.5], jnp.bfloat16)},
                                s16, p16)
        p16 = optax.apply_updates(p16, upd)
    ema16 = extract_ema(s16)
    assert ema16["w"].dtype == jnp.float32
    assert float(ema16["w"][0]) != 2.0  # the shadow actually moved


def test_train_loop_ema_eval(tmp_path):
    """run_training with --ema reports ema_eval_loss next to eval_loss,
    and the EMA tree rides the checkpoint: a --resume run (template
    restore preserves the typed EmaState) still reports it."""
    config = dict(
        model="mnist_mlp", batch_size=16, steps=8, optimizer="adam",
        learning_rate=1e-3, ema=0.9, eval_every=8, eval_steps=2,
        checkpoint_dir=str(tmp_path), checkpoint_every=8, log_every=4)
    summary = run_training(TrainLoopConfig(**config))
    assert np.isfinite(summary["eval_loss"])
    assert np.isfinite(summary["ema_eval_loss"])
    # resume at the final step: 0 further updates, the EMA evaluated is
    # exactly the checkpointed shadow
    summary2 = run_training(TrainLoopConfig(**config, resume=True))
    assert summary2["steps"] == 8
    assert np.isfinite(summary2["ema_eval_loss"])

    # --ema composes with --lora since round 5: freeze_base masks the
    # shadow to exactly the adapters and the EMA eval grafts them onto
    # the frozen base (tests/test_lora.py covers the mechanics; here
    # assert the combination runs end to end and reports the metric)
    summary3 = run_training(TrainLoopConfig(
        model="tiny_lm", batch_size=4, steps=2, lora="2:4", ema=0.9,
        eval_every=2, log_every=2))
    assert np.isfinite(summary3["ema_eval_loss"])
