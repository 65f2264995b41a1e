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
    """make_sharded_flash_attention on a 3-axis mesh (data x fsdp x tensor)
    must equal dense causal attention — the flash kernel runs per
    batch/head shard over the full sequence."""
    from parameter_server_distributed_tpu.models.transformer import (
        make_sharded_flash_attention)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    q, k, v = qkv(rng, b=4, s=128, h=4, d=16)  # seq 128: real kernel path
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    flash = make_sharded_flash_attention(mesh)
    out = np.asarray(jax.jit(flash)(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=5e-4, atol=5e-4)


def test_sharded_flash_lm_step_matches_dense(rng):
    """Full sharded LM train step on a 2-axis mesh with the pallas flash
    kernel: loss and updated params must match the dense-attention run
    (mesh + flash at the same time)."""
    from parameter_server_distributed_tpu.models.transformer import (
        make_sharded_flash_attention)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    config = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=128, dtype=jnp.float32)
    tokens = rng.integers(0, 128, (4, 128)).astype(np.int32)

    results = {}
    for name, attn in (("dense", None),
                       ("flash", make_sharded_flash_attention(mesh))):
        model = Transformer(config, attention_fn=attn, mesh=mesh)
        trainer = ShardedTrainer(model.loss, mesh, transformer_rule(mesh),
                                 make_optimizer("sgd", 0.1))
        state = trainer.init_state(model.init_params(0))
        state, metrics = trainer.step(state, tokens)
        results[name] = (float(metrics["loss"]),
                         np.asarray(state.params["layer0/attn/wq"]))
    assert np.isfinite(results["dense"][0])
    np.testing.assert_allclose(results["flash"][0], results["dense"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(results["flash"][1], results["dense"][1],
                               rtol=2e-3, atol=2e-5)


def test_select_attention_switch(rng):
    """select_attention: every CLI choice returns a working attention_fn
    (or None for dense) on the appropriate mesh."""
    from parameter_server_distributed_tpu.models.transformer import (
        flash_attention_auto, select_attention)

    assert select_attention("dense", None) is None
    assert select_attention("flash", None) is flash_attention_auto
    mesh = build_mesh(MeshConfig(sequence=2, data=4))
    q, k, v = qkv(rng)
    dense = np.asarray(causal_attention(*map(jnp.asarray, (q, k, v))))
    for name in ("ring", "ulysses", "ulysses_flash", "ulysses_xla_flash"):
        fn = select_attention(name, mesh)
        np.testing.assert_allclose(np.asarray(jax.jit(fn)(q, k, v)), dense,
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="unknown attention"):
        select_attention("sliding", mesh)
    with pytest.raises(ValueError, match="needs a mesh"):
        select_attention("ring", None)


def test_ulysses_flash_inner_kernel_and_gradients(rng):
    """make_ulysses_attention(inner=flash): the pallas kernel runs on each
    device's gathered full sequence; output AND gradients match the dense
    composition."""
    from parameter_server_distributed_tpu.models.transformer import (
        flash_attention_auto)

    mesh = build_mesh(MeshConfig(sequence=2, data=4))
    q, k, v = qkv(rng)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    uf = make_ulysses_attention(mesh, inner=flash_attention_auto)
    val_f, grads_f = jax.jit(
        jax.value_and_grad(lambda *a: loss(uf, *a), argnums=(0, 1, 2)))(q, k, v)
    val_d, grads_d = jax.jit(
        jax.value_and_grad(lambda *a: loss(causal_attention, *a),
                           argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(val_f), float(val_d), rtol=1e-5)
    for gf, gd, name in zip(grads_f, grads_d, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
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
        make_sharded_flash_attention, repeat_kv)

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
    out = np.asarray(jax.jit(make_sharded_flash_attention(fmesh))(q, k, v))
    np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)
