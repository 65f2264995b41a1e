"""The default path's attention: which arm ``Transformer.attend`` takes for
a shape (``default_arm``, over a device's own ``device_arm``), and the
blockwise kernel behind the ``kernel`` arm against ``causal_attention``
(interpret mode on the CPU; ``tests/test_chip_compile.py`` compiles it for
the chip)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.models import transformer
from parameter_server_distributed_tpu.models.transformer import (
    LayerSpec, Transformer, TransformerConfig, causal_attention)
from parameter_server_distributed_tpu.ops.pallas import ATTN_KERNEL_KEPT
from parameter_server_distributed_tpu.ops.pallas.fused_attention import (
    block_for, fits, fused_causal_attention)
from parameter_server_distributed_tpu.parallel.mesh import build_mesh


def _mesh(**axes):
    config = MeshConfig(**axes)
    return build_mesh(config, devices=jax.devices()[:config.num_devices])


# (case, backend is a TPU, mesh axes or None, q shape, K/V heads, window, arm)
ARMS = [
    ("the cell's step", True, None, (64, 1024, 16, 64), 16, 0, "kernel"),
    ("the benchmark's check, 2 x 256", True, None, (2, 256, 16, 64), 16, 0,
     "kernel"),
    ("a short serving bucket, one prompt", True, None, (1, 1024, 16, 64), 16,
     0, "dense"),
    ("a long serving bucket, one prompt", True, None, (1, 2048, 28, 128), 4,
     0, "kernel"),
    ("grouped heads of 128, two prompts", True, None, (2, 512, 28, 128), 4, 0,
     "kernel"),
    ("no TPU", False, None, (64, 1024, 16, 64), 16, 0, "dense"),
    ("no TPU, long", False, None, (1, 4096, 28, 128), 4, 0, "blockwise"),
    ("a window that binds", True, None, (1, 8192, 28, 128), 4, 4096,
     "blockwise"),
    ("a short window that binds", True, None, (2, 256, 16, 64), 16, 128,
     "dense"),
    ("positions that do not tile", True, None, (2, 200, 16, 64), 16, 0,
     "dense"),
    ("heads of 32", True, None, (2, 256, 16, 32), 16, 0, "dense"),
    ("an odd count of K/V heads of 64", True, None, (2, 256, 6, 64), 3, 0,
     "dense"),
    ("a mesh of one device", True, {}, (64, 1024, 16, 64), 16, 0, "kernel"),
    ("fsdp 2 x tensor 2", True, {"fsdp": 2, "tensor": 2},
     (64, 1024, 20, 64), 20, 0, "sharded_kernel"),
    ("data 2 x fsdp 2", True, {"data": 2, "fsdp": 2}, (64, 1024, 16, 64),
     16, 0, "sharded_kernel"),
    ("a seq axis", True, {"sequence": 2}, (64, 1024, 16, 64), 16, 0,
     "dense"),
    ("a pipe axis", True, {"pipeline": 2}, (64, 1024, 16, 64), 16, 0,
     "dense"),
    ("heads that leave a shard half a row", True, {"tensor": 4},
     (64, 1024, 20, 64), 20, 0, "dense"),
    ("a batch the mesh does not divide", True, {"fsdp": 4},
     (2, 256, 16, 64), 16, 0, "dense"),
    ("one short sequence a shard", True, {"fsdp": 4}, (4, 1024, 16, 64), 16,
     0, "dense"),
    ("one long sequence a shard", True, {"fsdp": 4}, (4, 2048, 16, 64), 16,
     0, "sharded_kernel"),
    ("a mesh without a TPU", False, {"fsdp": 2, "tensor": 2},
     (64, 1024, 20, 64), 20, 0, "dense"),
]


@pytest.mark.parametrize("case,tpu,axes,q_shape,kv_heads,window,arm", ARMS,
                         ids=[a[0] for a in ARMS])
def test_the_default_arm_follows_from_what_attend_sees(
        monkeypatch, case, tpu, axes, q_shape, kv_heads, window, arm):
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
    b, s, h, d = q_shape
    model = Transformer(
        TransformerConfig(vocab=64, d_model=h * d, n_heads=h, n_layers=1,
                          d_ff=64, max_seq=s, n_kv_heads=kv_heads,
                          head_dim=d),
        mesh=None if axes is None else _mesh(**axes))
    assert model.default_arm(q_shape, (b, s, kv_heads, d), window) == arm


def test_k_over_other_positions_than_q_stays_off_the_kernel(monkeypatch):
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    model = Transformer(TransformerConfig(vocab=64, d_model=128, n_heads=2,
                                          n_layers=1, d_ff=64, max_seq=512))
    assert model.default_arm((1, 256, 2, 64), (1, 512, 2, 64), 0) == "dense"
    assert not fits((1, 256, 2, 64), (1, 512, 2, 64))
    assert fits((1, 256, 2, 64), (1, 256, 2, 64))


def test_blocks_come_from_the_sequence_length():
    assert [block_for(s) for s in (128, 384, 1024, 1536, 2304, 16384)] == [
        128, 384, 1024, 512, 256, 512]
    q = jnp.zeros((1, 200, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="does not take"):
        fused_causal_attention(q, q, q)
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="blocks"):
        fused_causal_attention(q, q, q, block_q=192)


def _operands(rng, b, s, h, kv, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    weight = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    return q, k, v, weight


def _out_and_grads(attend, q, k, v, weight, checkpoint=False):
    def loss(q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    if checkpoint:
        loss = jax.checkpoint(loss)
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, (0, 1, 2), has_aux=True))(q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *grads)]


# head size, query heads, K/V heads, positions, blocks (None: from the
# shape): one block in strips; blocks on and below the diagonal, each in
# strips of its kind; blocks of unequal sides; three blocks a side; one
# K/V head under four query heads
KERNEL_SHAPES = [
    pytest.param(64, 2, 2, 512, None, id="64-two-heads-a-row"),
    pytest.param(64, 2, 2, 512, (256, 256), id="64-strips-of-blocks"),
    pytest.param(64, 4, 2, 256, (128, 256), id="64-grouped"),
    pytest.param(128, 2, 2, 256, (256, 128), id="128"),
    pytest.param(128, 6, 2, 384, (128, 128), id="128-grouped"),
    pytest.param(128, 4, 1, 256, None, id="128-multi-query"),
]


@pytest.mark.parametrize("d,h,kv,s,blocks", KERNEL_SHAPES)
@pytest.mark.parametrize("checkpoint", [False, True],
                         ids=["plain", "under-checkpoint"])
def test_the_kernel_is_the_einsum_in_float32(rng, d, h, kv, s, blocks,
                                             checkpoint):
    q, k, v, weight = _operands(rng, 2, s, h, kv, d, jnp.float32)
    block_q, block_k = blocks or (None, None)
    got = _out_and_grads(
        lambda q, k, v: fused_causal_attention(q, k, v, block_q=block_q,
                                               block_k=block_k),
        q, k, v, weight, checkpoint)
    want = _out_and_grads(causal_attention, q, k, v, weight)
    # dK and dV come back K/V-sized: grouped heads are never expanded
    assert [a.shape for a in got] == [q.shape, q.shape, k.shape, v.shape]
    for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == e.shape, name
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("d,h,kv,s,blocks", KERNEL_SHAPES)
def test_the_kernel_in_bf16_is_within_the_einsums_own_rounding(
        rng, d, h, kv, s, blocks):
    """Both against the float32 einsum on the same bf16 operands: the
    kernel's error is no larger than the bf16 einsum's own (it keeps dP in
    float32 where the einsum's backward rounds it), with room for the one
    rounding it makes in another order (probabilities cast before the
    division)."""
    q, k, v, weight = _operands(rng, 2, s, h, kv, d, jnp.bfloat16)
    exact = _out_and_grads(
        lambda q, k, v: causal_attention(
            *(x.astype(jnp.float32) for x in (q, k, v))), q, k, v, weight)
    got = _out_and_grads(fused_causal_attention, q, k, v, weight)
    einsum = _out_and_grads(causal_attention, q, k, v, weight)
    assert [a.shape for a in got] == [q.shape, q.shape, k.shape, v.shape]
    for name, a, e, x in zip(("out", "dq", "dk", "dv"), got, einsum, exact):
        scale = np.linalg.norm(x)
        kernel_error = np.linalg.norm(a - x) / scale
        einsum_error = np.linalg.norm(e - x) / scale
        assert kernel_error < 1.5 * einsum_error + 1e-4, (
            name, kernel_error, einsum_error)
        assert kernel_error < 0.01, (name, kernel_error)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("pattern", ["full", "window-and-full"])
def test_a_model_on_the_kernel_arm_trains_as_on_the_einsum(monkeypatch, rng,
                                                           scan, pattern):
    """Loss and every gradient of a small model (remat on, with and
    without ``scan_layers``) through the kernel arm against the same model
    on the einsum; a window layer keeps the arm it had."""
    layers = (None if pattern == "full" else
              (LayerSpec(window=64, rope=True), LayerSpec(rope=False)))
    config = TransformerConfig(
        vocab=96, d_model=128, n_heads=2, n_layers=2, d_ff=128, max_seq=128,
        n_kv_heads=2, dtype=jnp.float32, remat=True, scan_layers=scan,
        pattern=layers or ())
    model = Transformer(config)
    params = model.init_params(3)
    tokens = jnp.asarray(rng.integers(0, 96, (2, 128)), jnp.int32)
    arms = []
    real = model.default_arm

    def spy(q_shape, kv_shape, window):
        arms.append(real(q_shape, kv_shape, window))
        return arms[-1]

    monkeypatch.setattr(model, "default_arm", spy)
    results = {}
    for tpu in (False, True):
        monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
        del arms[:]
        results[tpu] = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
        seen = set(arms)
        if tpu:
            assert "kernel" in seen
            assert ("dense" in seen) == (pattern != "full")
        else:
            assert seen == {"dense"}
    (loss_e, grads_e), (loss_k, grads_k) = results[False], results[True]
    np.testing.assert_allclose(float(loss_k), float(loss_e), rtol=1e-6)
    for name in grads_e:
        np.testing.assert_allclose(
            np.asarray(grads_k[name]), np.asarray(grads_e[name]),
            rtol=2e-4, atol=2e-6, err_msg=name)


def test_the_sharded_arm_is_the_kernel_on_every_shard(monkeypatch, rng):
    """fsdp 2 x tensor 2 on the CPU's virtual devices: ``attend`` under a
    mesh runs the kernel under ``shard_map`` and gives the einsum's output
    and gradients."""
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    mesh = _mesh(fsdp=2, tensor=2)
    config = TransformerConfig(vocab=64, d_model=256, n_heads=4, n_layers=1,
                               d_ff=64, max_seq=128, dtype=jnp.float32)
    model = Transformer(config, mesh=mesh)
    q, k, v, weight = _operands(rng, 4, 128, 4, 4, 64, jnp.float32)
    assert model.default_arm(q.shape, k.shape, 0) == "sharded_kernel"
    with mesh:
        got = _out_and_grads(
            lambda q, k, v: model.attend(q, k, v, LayerSpec()), q, k, v,
            weight)
    want = _out_and_grads(causal_attention, q, k, v, weight)
    for name, a, e in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5, err_msg=name)


def _stacked_by_the_forward_loop(grad_jaxpr, n_layers):
    """The shapes a layer of what the forward ``scan`` of a scanned
    model's ``grad(loss)`` stacks for the backward one: what the layer's
    ``jax.checkpoint`` keeps."""
    forward = next(eqn for eqn in grad_jaxpr.jaxpr.eqns
                   if eqn.primitive.name == "scan")
    return sorted(var.aval.shape[1:] for var in forward.outvars
                  if var.aval.ndim > 2 and var.aval.shape[0] == n_layers)


# (case, backend is a TPU, mesh axes or None, arm, kept beside the input)
KEPT = [
    ("kernel", True, None, "kernel", ["o", "lse"]),
    ("sharded-kernel", True, {"fsdp": 2, "tensor": 2}, "sharded_kernel",
     ["o", "lse", "mixer_out"]),
    ("einsum", False, None, "dense", []),
    ("einsum-on-a-tensor-axis", False, {"fsdp": 2, "tensor": 2}, "dense",
     ["mixer_out"]),
]


@pytest.mark.parametrize("case,tpu,axes,arm,kept", KEPT,
                         ids=[a[0] for a in KEPT])
def test_full_remat_keeps_the_kernels_output_and_row_sums(
        monkeypatch, rng, case, tpu, axes, arm, kept):
    """A 2-layer scanned, rematted model in float32: where the blockwise
    kernel attends (alone or under ``shard_map``), "full" keeps the
    kernel's output and its rows' logsumexp a layer, so the backward holds
    three kernel calls and not four; loss and every gradient are those of
    the step whose ``jax.checkpoint`` keeps nothing (the kept values ARE
    the recomputed ones); no ``[B, H, S, S]`` value is kept either way.
    Where the einsum attends the names do not exist and nothing more is
    kept than before."""
    batch, seq, heads, d = 4, 128, 4, 64
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
    mesh = None if axes is None else _mesh(**axes)
    config = TransformerConfig(
        vocab=64, d_model=heads * d, n_heads=heads, n_layers=2, d_ff=128,
        max_seq=seq, dtype=jnp.float32, remat=True, scan_layers=True)
    model = Transformer(config, mesh=mesh)
    assert model.default_arm((batch, seq, heads, d), (batch, seq, heads, d),
                             0) == arm
    params = model.init_params(5)
    tokens = jnp.asarray(rng.integers(0, 64, (batch, seq)), jnp.int32)

    def step():
        jaxpr = jax.make_jaxpr(jax.grad(model.loss))(params, tokens)
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
        return jaxpr, float(loss), jax.tree.map(np.asarray, grads)

    jaxpr, loss, grads = step()
    with monkeypatch.context() as patch:
        patch.setattr(Transformer, "_remat_policy", lambda self: None)
        bare_jaxpr, bare_loss, bare_grads = step()

    # a shard_map hands the backward each device's own block, the blocks
    # of all devices stacked on the first axis
    tp = mesh.shape["tensor"] if arm == "sharded_kernel" else 1
    shapes = {"o": (batch * tp, seq, heads * d // tp),
              "lse": (batch * tp, heads * d // tp // 128, 128 // d, seq),
              "mixer_out": (batch, seq, heads * d)}
    layer_input = (batch, seq, heads * d)
    assert _stacked_by_the_forward_loop(bare_jaxpr, 2) == [layer_input]
    assert _stacked_by_the_forward_loop(jaxpr, 2) == sorted(
        [layer_input] + [shapes[name] for name in kept])
    kernel, text = arm != "dense", str(jaxpr)
    for name in ATTN_KERNEL_KEPT:
        assert (f"name={name}" in text) == kernel
    assert (str(bare_jaxpr).count("pallas_call"),
            text.count("pallas_call")) == ((4, 3) if kernel else (0, 0))
    np.testing.assert_allclose(loss, bare_loss, rtol=1e-6)
    assert set(grads) == set(bare_grads)
    for name in grads:
        np.testing.assert_allclose(grads[name], bare_grads[name], rtol=1e-6,
                                   atol=1e-9, err_msg=name)
