"""Ring / Ulysses sequence-parallel attention vs dense causal attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig, causal_attention)
from parameter_server_distributed_tpu.ops.ring_attention import (
    make_ring_attention, make_ulysses_attention)
from parameter_server_distributed_tpu.parallel.mesh import build_mesh
from parameter_server_distributed_tpu.parallel.train_step import (
    ShardedTrainer, make_optimizer)
from parameter_server_distributed_tpu.models.transformer import transformer_rule


def qkv(rng, b=4, s=32, h=4, d=16):
    shape = (b, s, h, d)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


@pytest.mark.parametrize("seq_shards", [2, 4, 8])
def test_ring_matches_dense(seq_shards, rng):
    mesh = build_mesh(MeshConfig(sequence=seq_shards,
                                 data=8 // seq_shards))
    q, k, v = qkv(rng)
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    ring = make_ring_attention(mesh)
    out = np.asarray(jax.jit(ring)(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)


def test_ring_with_tensor_parallel_heads(rng):
    mesh = build_mesh(MeshConfig(sequence=2, tensor=2, data=2))
    q, k, v = qkv(rng)
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    out = np.asarray(jax.jit(make_ring_attention(mesh))(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seq_shards", [2, 4])
def test_ulysses_matches_dense(seq_shards, rng):
    mesh = build_mesh(MeshConfig(sequence=seq_shards,
                                 data=8 // seq_shards))
    q, k, v = qkv(rng)
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    out = np.asarray(jax.jit(make_ulysses_attention(mesh))(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)


def test_ring_attention_long_sequence_gradients(rng):
    """Gradients must flow through the ring (backward ppermutes)."""
    mesh = build_mesh(MeshConfig(sequence=4, data=2))
    q, k, v = qkv(rng, b=2, s=64, h=2, d=8)
    ring = make_ring_attention(mesh)

    def f_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def f_dense(q, k, v):
        return jnp.sum(causal_attention(q, k, v) ** 2)

    g_ring = jax.jit(jax.grad(f_ring))(q, k, v)
    g_dense = jax.jit(jax.grad(f_dense))(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_dense),
                               rtol=5e-4, atol=5e-5)


def test_transformer_with_ring_attention_end_to_end(rng):
    """Full sharded LM step with ring attention == dense-attention loss."""
    mesh = build_mesh(MeshConfig(data=2, sequence=4))
    config = TransformerConfig(vocab=64, d_model=64, n_heads=4, n_layers=2,
                               d_ff=128, max_seq=64, dtype=jnp.float32)
    tokens = rng.integers(0, 64, (2, 64)).astype(np.int32)

    plain = Transformer(config)
    params = plain.init_params(0)
    base_loss = float(plain.loss(params, jnp.asarray(tokens)))

    ring_model = Transformer(config, attention_fn=make_ring_attention(mesh),
                             mesh=mesh)
    trainer = ShardedTrainer(ring_model.loss, mesh, transformer_rule(mesh),
                             make_optimizer("sgd", 0.1))
    state = trainer.init_state(params)
    state, metrics = trainer.step(state, tokens)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=2e-4)


def test_sharded_flash_matches_dense(rng):
    """shard_over_batch_and_heads on a 3-axis mesh (data x fsdp x tensor)
    must equal dense causal attention: the kernel (interpreted) runs per
    batch/head shard over the full sequence."""
    from parameter_server_distributed_tpu.models.transformer import (
        shard_over_batch_and_heads)
    from parameter_server_distributed_tpu.ops.pallas.fused_attention import (
        fused_causal_attention)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    q, k, v = qkv(rng, b=4, s=128, h=4, d=64)   # a shard: [1, 128, 2, 64]
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    sharded = shard_over_batch_and_heads(mesh, fused_causal_attention)
    out = np.asarray(jax.jit(sharded)(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)


def test_sharded_flash_lm_step_matches_dense(rng):
    """Full sharded LM train step on a 3-axis mesh with a caller's
    attention under shard_map over batch and heads: loss and updated
    params must match the default-path run (GSPMD's einsum)."""
    from parameter_server_distributed_tpu.models.transformer import (
        shard_over_batch_and_heads)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    config = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=128, dtype=jnp.float32)
    tokens = rng.integers(0, 128, (4, 128)).astype(np.int32)

    results = {}
    for name, attn in (("dense", None),
                       ("sharded", shard_over_batch_and_heads(
                           mesh, causal_attention))):
        model = Transformer(config, attention_fn=attn, mesh=mesh)
        trainer = ShardedTrainer(model.loss, mesh, transformer_rule(mesh),
                                 make_optimizer("sgd", 0.1))
        state = trainer.init_state(model.init_params(0))
        state, metrics = trainer.step(state, tokens)
        results[name] = (float(metrics["loss"]),
                         np.asarray(state.params["layer0/attn/wq"]))
    assert np.isfinite(results["dense"][0])
    np.testing.assert_allclose(results["sharded"][0], results["dense"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(results["sharded"][1], results["dense"][1],
                               rtol=2e-3, atol=2e-5)


def test_select_attention_switch(rng):
    """select_attention: every CLI choice returns a working attention_fn
    (or None for dense) on the appropriate mesh."""
    from parameter_server_distributed_tpu.models.transformer import (
        ATTENTION_CHOICES, select_attention)

    assert ATTENTION_CHOICES == ("dense", "ring", "ulysses")
    assert select_attention("dense", None) is None
    mesh = build_mesh(MeshConfig(sequence=2, data=4))
    assert select_attention("dense", mesh) is None
    q, k, v = qkv(rng)
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    for name in ("ring", "ulysses"):
        fn = select_attention(name, mesh)
        np.testing.assert_allclose(np.asarray(jax.jit(fn)(q, k, v)), dense,
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="unknown attention"):
        select_attention("sliding", mesh)
    with pytest.raises(ValueError, match="needs a mesh"):
        select_attention("ring", None)


@pytest.mark.parametrize("name", ["flash", "xla_flash", "ulysses_flash",
                                  "ulysses_xla_flash"])
def test_a_removed_attention_name_is_refused_with_the_three_that_remain(
        monkeypatch, name):
    """By select_attention and by TrainLoopConfig (so by pst-train before a
    model is built): the message lists dense, ring, ulysses."""
    from parameter_server_distributed_tpu.cli import train_main
    from parameter_server_distributed_tpu.models.transformer import (
        select_attention)
    from parameter_server_distributed_tpu.utils import compile_cache
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig)

    remain = r"\('dense', 'ring', 'ulysses'\)"
    mesh = build_mesh(MeshConfig(sequence=2, data=4))
    with pytest.raises(ValueError, match=remain):
        select_attention(name, mesh)
    with pytest.raises(ValueError, match=remain):
        TrainLoopConfig(model="small_lm", attention=name)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    with pytest.raises(ValueError, match=remain):
        train_main.main(["--model=small_lm", f"--attention={name}"])


def test_the_documents_name_only_attention_choices_that_exist():
    """Every ``--attention=`` value README.md, docs/*.md and pst-train's
    --help name is one of ATTENTION_CHOICES."""
    import glob
    import os
    import re

    from parameter_server_distributed_tpu.cli import train_main
    from parameter_server_distributed_tpu.models.transformer import (
        ATTENTION_CHOICES)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    texts = {"pst-train --help": train_main.__doc__}
    for path in [os.path.join(root, "README.md"),
                 *glob.glob(os.path.join(root, "docs", "*.md"))]:
        with open(path, encoding="utf-8") as handle:
            texts[os.path.relpath(path, root)] = handle.read()
    named = {(where, value)
             for where, text in texts.items()
             for values in re.findall(r"--attention=([a-z_|]+)", text)
             for value in values.split("|")}
    assert {"README.md", "docs/parallelism.md", "pst-train --help"} <= {
        where for where, _ in named}
    assert {pair for pair in named
            if pair[1] not in ATTENTION_CHOICES} == set()


# what a device holds after Ulysses' gather, [B, S, H/n, D] against
# [B, S, KV/n, D]: (case, backend is a TPU, q shape, K/V heads, arm)
AFTER_THE_GATHER = [
    ("long and grouped on a TPU", True, (1, 8192, 7, 128), 1, "kernel"),
    ("the same off a TPU", False, (1, 8192, 7, 128), 1, "blockwise"),
    ("heads that leave half a row of lanes", True, (2, 4096, 3, 64), 1,
     "blockwise"),
    ("short", False, (2, 1024, 4, 64), 4, "dense"),
    ("short on a TPU, several sequences", True, (2, 1024, 4, 64), 4,
     "kernel"),
    ("one short sequence on a TPU", True, (1, 1024, 4, 64), 4, "dense"),
    ("heads of 32 on a TPU, long", True, (2, 2048, 8, 32), 8, "blockwise"),
]


@pytest.mark.parametrize("case,tpu,q_shape,kv_heads,arm", AFTER_THE_GATHER,
                         ids=[a[0] for a in AFTER_THE_GATHER])
def test_a_device_takes_the_arm_its_own_shapes_name(monkeypatch, case, tpu,
                                                    q_shape, kv_heads, arm):
    from parameter_server_distributed_tpu.models import transformer

    monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
    b, s, _, d = q_shape
    assert transformer.device_arm(q_shape, (b, s, kv_heads, d)) == arm


def _value_and_grads(fn, q, k, v):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("seq_shards", [2, 4])
@pytest.mark.parametrize("arm", ["dense", "blockwise", "kernel"])
def test_ulysses_attends_by_the_devices_arm(monkeypatch, rng, arm,
                                            seq_shards):
    """After the gather a device attends by device_arm: the einsum, the
    plain-XLA blocks (``BLOCKWISE_FROM`` lowered) or the kernel (a TPU
    pretended, the kernel interpreted); output AND gradients match the
    einsum over the whole arrays, grouped K/V unexpanded on the wire."""
    from parameter_server_distributed_tpu.models import transformer

    mesh = build_mesh(MeshConfig(sequence=seq_shards, data=8 // seq_shards))
    b, s, h, kv, d = 8 // seq_shards * 2, 128, 16, 8, 64
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, kv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, kv, d)).astype(np.float32)
    if arm == "blockwise":
        monkeypatch.setattr(Transformer, "BLOCKWISE_FROM", 128)
    monkeypatch.setattr(transformer, "_kernel_backend",
                        lambda: arm == "kernel")
    taken = []
    real = transformer.device_arm

    def spy(q_shape, kv_shape, *rest):
        taken.append((q_shape, kv_shape, real(q_shape, kv_shape, *rest)))
        return taken[-1][-1]

    monkeypatch.setattr(transformer, "device_arm", spy)
    val_u, grads_u = _value_and_grads(make_ulysses_attention(mesh), q, k, v)
    # a device's own shapes: its rows of the batch, every position, its
    # share of the heads
    rows = b // (8 // seq_shards)
    assert set(taken) == {((rows, s, h // seq_shards, d),
                           (rows, s, kv // seq_shards, d), arm)}
    val_d, grads_d = _value_and_grads(causal_attention, q, k, v)
    # (a float32 sum over a million squares)
    np.testing.assert_allclose(float(val_u), float(val_d), rtol=1e-4)
    for gu, gd, name in zip(grads_u, grads_d, "qkv"):
        assert gu.shape == gd.shape, name
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_ring_block_remat_gradients_match(rng):
    """The rematted ring block update is numerically invisible: gradients
    equal the dense reference (scores recomputed in backward)."""
    mesh = build_mesh(MeshConfig(sequence=4, data=2))
    q, k, v = qkv(rng, b=2, s=64, h=2, d=8)
    ring = make_ring_attention(mesh)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    val_r, grads_r = jax.jit(
        jax.value_and_grad(lambda *a: loss(ring, *a), argnums=(0, 1, 2)))(q, k, v)
    val_d, grads_d = jax.jit(
        jax.value_and_grad(lambda *a: loss(causal_attention, *a),
                           argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(val_r), float(val_d), rtol=1e-5)
    for gr, gd, name in zip(grads_r, grads_d, "qkv"):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_ring_and_ulysses_gqa_unexpanded_kv(rng):
    """GQA contract: attention fns take kv_heads-sized K/V (the ring
    rotates / Ulysses all-to-alls the small tensors) and match the
    expanded dense reference."""
    from parameter_server_distributed_tpu.models.transformer import repeat_kv

    b, s, h, kv, d = 4, 32, 8, 2, 16
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, kv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, kv, d)).astype(np.float32)
    dense = np.asarray(causal_attention(
        jnp.asarray(q), repeat_kv(jnp.asarray(k), h // kv),
        repeat_kv(jnp.asarray(v), h // kv)))

    mesh = build_mesh(MeshConfig(sequence=4, data=2))
    out_ring = np.asarray(jax.jit(make_ring_attention(mesh))(q, k, v))
    np.testing.assert_allclose(out_ring, dense, rtol=2e-5, atol=2e-5)

    # kv=2 divides seq axis 2: the small-transfer path
    mesh2 = build_mesh(MeshConfig(sequence=2, data=4))
    out_uly = np.asarray(jax.jit(make_ulysses_attention(mesh2))(q, k, v))
    np.testing.assert_allclose(out_uly, dense, rtol=2e-5, atol=2e-5)

    # kv=2 does NOT divide seq axis 4: the expand-first fallback
    mesh4 = build_mesh(MeshConfig(sequence=4, data=2))
    out_uly4 = np.asarray(jax.jit(make_ulysses_attention(mesh4))(q, k, v))
    np.testing.assert_allclose(out_uly4, dense, rtol=2e-5, atol=2e-5)


def test_mqa_with_tensor_parallel_heads(rng):
    """MQA (kv_heads=1) + tensor-sharded heads: kv_heads cannot be sharded
    by the tensor axis, so the wrappers pre-expand K/V — the pre-GQA-
    refactor behavior for this corner (regression test)."""
    from parameter_server_distributed_tpu.models.transformer import (
        repeat_kv, shard_over_batch_and_heads)

    b, s, h, d = 4, 32, 4, 16
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, 1, d)).astype(np.float32)
    v = rng.standard_normal((b, s, 1, d)).astype(np.float32)
    dense = np.asarray(causal_attention(
        jnp.asarray(q), repeat_kv(jnp.asarray(k), h),
        repeat_kv(jnp.asarray(v), h)))

    mesh = build_mesh(MeshConfig(sequence=2, tensor=2, data=2))
    for maker in (make_ring_attention, make_ulysses_attention):
        out = np.asarray(jax.jit(maker(mesh))(q, k, v))
        np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5,
                                   err_msg=maker.__name__)

    fmesh = build_mesh(MeshConfig(tensor=2, data=4))
    out = np.asarray(jax.jit(shard_over_batch_and_heads(
        fmesh, causal_attention))(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)
