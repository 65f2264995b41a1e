"""Model zoo tests: ResNet and Transformer forward/loss/training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.models.mlp import MLP, billion_param_mlp, mnist_mlp
from parameter_server_distributed_tpu.models.resnet import ResNet, resnet18, resnet50
from parameter_server_distributed_tpu.models.transformer import (
    LayerSpec, Transformer, TransformerConfig, small_lm, transformer_rule)
from parameter_server_distributed_tpu.parallel.mesh import build_mesh
from parameter_server_distributed_tpu.parallel.train_step import (
    ShardedTrainer, make_optimizer)


def test_mlp_num_params():
    assert mnist_mlp().num_params() == 784 * 256 + 256 + 256 * 10 + 10
    assert billion_param_mlp().num_params() > 1_000_000_000


def test_resnet18_structure():
    model = resnet18()
    # 18 = 1 stem + 2*2*4 convs + 1 head
    conv_names = [n for n in model.param_shapes() if "/conv" in n or n == "stem/conv/w"]
    assert len(conv_names) == 17
    assert model.num_params() > 10_000_000  # ~11M


def test_resnet50_structure():
    model = resnet50()
    assert model.num_params() > 23_000_000  # ~25.5M
    assert model.param_shapes()["head/w"] == (2048, 1000)


def test_vit_forward_and_training(rng):
    """Tiny ViT end to end: patchify shapes, bidirectional attention,
    CLS-pooled classification, and loss decreasing under SGD."""
    from parameter_server_distributed_tpu.models.vit import ViT, ViTConfig

    model = ViT(ViTConfig(image_size=8, patch_size=4, num_classes=4,
                          d_model=32, n_heads=2, n_layers=2, d_ff=64))
    assert model.config.n_patches == 4 and model.config.seq_len == 5
    params = model.init_params(0)
    x = rng.standard_normal((8, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 4, 8).astype(np.int32)
    assert model.apply(params, x).shape == (8, 4)
    loss_fn = jax.jit(jax.value_and_grad(model.loss))
    losses = []
    for _ in range(15):
        loss, grads = loss_fn(params, (x, y))
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses

    # mean pooling is a config switch, not a new model
    import dataclasses as dc
    mean = ViT(dc.replace(model.config, pool="mean"))
    assert mean.apply(params, x).shape == (8, 4)
    with pytest.raises(ValueError, match="pool"):
        ViTConfig(pool="max")
    with pytest.raises(ValueError, match="divide"):
        ViTConfig(image_size=30, patch_size=4)


def test_vit_registry_and_sharded_training(rng):
    """The registry entries build with their data streams, and a ViT
    store shards under the TRANSFORMER rule (the suffix-compatible
    naming contract in models/vit.py's docstring) for mesh training."""
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)
    from parameter_server_distributed_tpu.models.transformer import (
        transformer_rule)
    from parameter_server_distributed_tpu.models.vit import ViT, ViTConfig

    model, batches = get_model_and_batches("vit_tiny_cifar", 8)
    x, y = next(batches)
    assert x.shape == (8, 32, 32, 3) and model.num_params() > 2e6

    small = ViT(ViTConfig(image_size=8, patch_size=4, num_classes=4,
                          d_model=32, n_heads=2, n_layers=2, d_ff=64))
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    trainer = ShardedTrainer(small.loss, mesh, transformer_rule(mesh),
                             optimizer=make_optimizer("adam", 1e-3))
    state = trainer.init_state(small.init_params(0))
    xb = rng.standard_normal((8, 8, 8, 3)).astype(np.float32)
    yb = rng.integers(0, 4, 8).astype(np.int32)
    losses = []
    for _ in range(6):
        state, metrics = trainer.step(state, (xb, yb))
        loss = metrics["loss"] if isinstance(metrics, dict) else metrics
        losses.append(float(jax.device_get(loss)))
    assert losses[-1] < losses[0], losses
    # the Megatron rule actually sharded the 2-D weights
    wq = state.params["layer0/attn/wq"]
    assert len(wq.sharding.device_set) > 1


def test_tiny_resnet_forward_and_training():
    model = ResNet(stages=(1, 1), bottleneck=False, num_classes=4, width=8)
    params = model.init_params(0)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 4, 8).astype(np.int32)
    logits = model.apply(params, x)
    assert logits.shape == (8, 4)
    loss_fn = jax.jit(jax.value_and_grad(model.loss))
    losses = []
    for _ in range(12):
        loss, grads = loss_fn(params, (x, y))
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses


def test_tiny_bottleneck_resnet_forward():
    model = ResNet(stages=(1, 1), bottleneck=True, num_classes=4, width=8)
    params = model.init_params(0)
    x = np.zeros((2, 8, 8, 3), np.float32)
    assert model.apply(params, x).shape == (2, 4)


def test_bf16_resnet_trains_with_f32_inputs():
    """ResNet-50's mixed-precision path: bf16 weights, f32 images —
    regression for a dtype mismatch at the second conv (f32 conv output
    fed to a bf16-weight conv)."""
    model = ResNet(stages=(1, 1), bottleneck=True, num_classes=4, width=8,
                   small_inputs=False, dtype=jnp.bfloat16)
    params = model.init_params(0)
    x = np.random.default_rng(0).standard_normal((2, 16, 16, 3)).astype(np.float32)
    y = np.array([1, 2], np.int32)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, (x, y))
    assert np.isfinite(float(loss))
    assert grads["stem/conv/w"].dtype == jnp.bfloat16
    assert np.isfinite(np.float32(np.asarray(grads["head/w"]))).all()


def test_transformer_shapes_and_loss_at_init():
    model = small_lm(vocab=64, seq=32)
    params = model.init_params(0)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 32)).astype(np.int32)
    logits = model.apply(params, jnp.asarray(tokens))
    assert logits.shape == (2, 32, 64)
    loss = float(model.loss(params, tokens))
    # random init => loss ~= ln(vocab)
    assert abs(loss - np.log(64)) < 0.35, loss


def test_transformer_causality():
    """Changing a future token must not change earlier logits."""
    model = small_lm(vocab=64, seq=16)
    params = model.init_params(0)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (1, 16)).astype(np.int32)
    logits1 = np.asarray(model.apply(params, jnp.asarray(tokens)))
    tokens2 = tokens.copy()
    tokens2[0, -1] = (tokens2[0, -1] + 1) % 64
    logits2 = np.asarray(model.apply(params, jnp.asarray(tokens2)))
    np.testing.assert_allclose(logits1[0, :-1], logits2[0, :-1],
                               rtol=1e-4, atol=1e-5)
    assert not np.allclose(logits1[0, -1], logits2[0, -1])


def test_transformer_learns_repetition():
    model = small_lm(vocab=16, seq=16)
    params = model.init_params(0)
    # highly predictable data: token[t+1] = token[t] + 1 mod 16
    base = np.arange(16, dtype=np.int32) % 16
    tokens = np.stack([np.roll(base, -s) for s in range(8)]).astype(np.int32)
    loss_fn = jax.jit(jax.value_and_grad(model.loss))
    losses = []
    for _ in range(30):
        loss, grads = loss_fn(params, tokens)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
        losses.append(float(loss))
    assert losses[-1] < 0.5, losses[-5:]


def test_transformer_sharded_tp_sp_training():
    """Full sharded training: dp=2 x tensor=2 x seq=2 mesh, Megatron TP rule,
    activation seq sharding; numerics must match the unsharded step."""
    mesh = build_mesh(MeshConfig(data=2, tensor=2, sequence=2))
    config = TransformerConfig(vocab=64, d_model=64, n_heads=4, n_layers=2,
                               d_ff=128, max_seq=32, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (4, 32)).astype(np.int32)

    plain = Transformer(config)
    params = plain.init_params(0)
    base_loss = float(plain.loss(params, jnp.asarray(tokens)))

    sharded_model = Transformer(config, mesh=mesh)
    trainer = ShardedTrainer(sharded_model.loss, mesh, transformer_rule(mesh),
                             make_optimizer("adam", 1e-3))
    state = trainer.init_state(params)
    # TP sharding placed: wq column-sharded over tensor
    wq = state.params["layer0/attn/wq"]
    assert {s.data.shape for s in wq.addressable_shards} == {(64, 32)}
    state, metrics = trainer.step(state, tokens)
    np.testing.assert_allclose(float(metrics["loss"]), base_loss, rtol=2e-4)
    state, metrics2 = trainer.step(state, tokens)
    assert float(metrics2["loss"]) < base_loss  # one adam step helped


def test_remat_loss_and_gradients_match_non_remat(rng):
    """jax.checkpoint rematerialization must be numerically invisible:
    same loss, same gradients, only the backward memory profile changes."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    plain = Transformer(config)
    remat = Transformer(dataclasses.replace(config, remat=True))
    params = plain.init_params(0)

    loss_a = float(jax.jit(plain.loss)(params, tokens))
    loss_b = float(jax.jit(remat.loss)(params, tokens))
    np.testing.assert_allclose(loss_b, loss_a, rtol=1e-6)

    g_a = jax.jit(jax.grad(plain.loss))(params, tokens)
    g_b = jax.jit(jax.grad(remat.loss))(params, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_b[name]),
                                   np.asarray(g_a[name]), rtol=1e-5,
                                   atol=1e-7, err_msg=name)


def _names_full_remat_keeps(model, monkeypatch):
    """The names ``model._remat_policy()`` asks ``jax.checkpoint`` to keep."""
    asked = []
    real = jax.checkpoint_policies.save_only_these_names
    with monkeypatch.context() as patch:
        patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                      lambda *names: asked.append(names) or real(*names))
        assert model._remat_policy() is not None
    return asked[-1]


def test_remat_dots_policy_matches_full(rng):
    """remat_policy='dots' (save projection/MLP dot outputs, recompute
    only the attention einsums) must be numerically identical to the
    full-recompute policy — the policy changes WHAT the backward pass
    recomputes, never the math.  Covers unrolled and scan layouts, and
    checks the credited-FLOPs accounting only credits the attention
    recompute under 'dots'."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    for scan in (False, True):
        config = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                   n_layers=2, d_ff=64, max_seq=16,
                                   dtype=jnp.float32, remat=True,
                                   scan_layers=scan)
        full = Transformer(config)
        dots = Transformer(dataclasses.replace(config, remat_policy="dots"))
        params = full.init_params(0)
        g_a = jax.jit(jax.grad(full.loss))(params, tokens)
        g_b = jax.jit(jax.grad(dots.loss))(params, tokens)
        for name in g_a:
            np.testing.assert_allclose(np.asarray(g_b[name]),
                                       np.asarray(g_a[name]), rtol=1e-5,
                                       atol=1e-7, err_msg=f"scan={scan} {name}")
        # credited accounting: full credits the whole recompute forward
        # (8P + 16 attn), dots only the attention einsums (6P + 16 attn)
        base = full.flops_per_sample()
        assert dots.flops_per_sample() == base
        assert (full.flops_per_sample(remat_credited=True)
                > dots.flops_per_sample(remat_credited=True) > base)

    with pytest.raises(ValueError, match="remat_policy"):
        TransformerConfig(remat_policy="bogus")


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def test_remat_full_with_the_kernels_names_kept_matches_keeping_nothing(
        rng, monkeypatch, scan):
    """remat_policy='full' asks jax.checkpoint to keep the blockwise
    attention kernel's two results by name (``_remat_policy``).  On the
    CPU's arm no kernel runs, nothing carries the names and the policy
    keeps nothing: loss and gradients are those of the policy forced to
    ``None`` (keep nothing, the rule before the names existed), so the
    names are harmless wherever another arm attends."""
    from parameter_server_distributed_tpu.ops.pallas import ATTN_KERNEL_KEPT

    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    model = Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=16,
        dtype=jnp.float32, remat=True, scan_layers=scan))
    params = model.init_params(0)
    assert _names_full_remat_keeps(model, monkeypatch) == ATTN_KERNEL_KEPT
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    monkeypatch.setattr(Transformer, "_remat_policy", lambda self: None)
    bare_loss, bare_grads = jax.jit(jax.value_and_grad(model.loss))(
        params, tokens)
    np.testing.assert_allclose(float(loss), float(bare_loss), rtol=1e-6)
    for name in bare_grads:
        np.testing.assert_allclose(np.asarray(grads[name]),
                                   np.asarray(bare_grads[name]), rtol=1e-6,
                                   atol=1e-9, err_msg=name)


def test_remat_generation_still_exact(rng):
    """collect_kv (generation prefill) bypasses remat; decoding from a
    remat-configured model matches the plain model token for token."""
    import dataclasses

    from parameter_server_distributed_tpu.models.generation import generate
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=64, dtype=jnp.float32)
    plain = Transformer(config)
    remat = Transformer(dataclasses.replace(config, remat=True))
    params = plain.init_params(0)
    prompt = rng.integers(0, 64, (2, 8)).astype(np.int32)
    out_a = np.asarray(generate(plain, params, prompt, 8))
    out_b = np.asarray(generate(remat, params, prompt, 8))
    np.testing.assert_array_equal(out_a, out_b)


def test_registry_dtype_and_remat_plumbing():
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)

    model, _ = get_model_and_batches("small_lm", 4, dtype="bf16", remat=True)
    assert model.config.dtype == jnp.bfloat16
    assert model.config.remat
    model, _ = get_model_and_batches("resnet18_cifar", 4, dtype="bf16")
    assert model.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="dtype"):
        get_model_and_batches("mnist_mlp", 4, dtype="bf16")
    with pytest.raises(ValueError, match="remat"):
        get_model_and_batches("mlp_1b", 4, remat=True)
    with pytest.raises(ValueError, match="unknown dtype"):
        get_model_and_batches("small_lm", 4, dtype="fp8")


def test_gqa_transformer_trains_and_matches_mha_when_equal(rng):
    """n_kv_heads=n_heads is exactly MHA (same shapes, same loss); a real
    GQA config has smaller wk/wv, finite loss, and gradients through them."""
    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    import dataclasses
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)

    mha = Transformer(config)
    same = Transformer(dataclasses.replace(config, n_kv_heads=4))
    params = mha.init_params(0)
    np.testing.assert_allclose(
        float(jax.jit(same.loss)(params, tokens)),
        float(jax.jit(mha.loss)(params, tokens)), rtol=1e-6)

    gqa = Transformer(dataclasses.replace(config, n_kv_heads=2))
    assert gqa.param_shapes()["layer0/attn/wk"] == (32, 16)
    gparams = gqa.init_params(0)
    loss, grads = jax.jit(jax.value_and_grad(gqa.loss))(gparams, tokens)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["layer0/attn/wk"]).max()) > 0

    with pytest.raises(ValueError, match="n_kv_heads"):
        Transformer(dataclasses.replace(config, n_kv_heads=3))


def test_chunked_cross_entropy_matches_unchunked(rng):
    """loss_chunk must be numerically invisible: same loss, same gradients
    — only peak logits memory changes."""
    import dataclasses

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    plain = Transformer(config)
    chunked = Transformer(dataclasses.replace(config, loss_chunk=4))
    params = plain.init_params(0)

    la = float(jax.jit(plain.loss)(params, tokens))
    lb = float(jax.jit(chunked.loss)(params, tokens))
    np.testing.assert_allclose(lb, la, rtol=1e-6)

    g_a = jax.jit(jax.grad(plain.loss))(params, tokens)
    g_b = jax.jit(jax.grad(chunked.loss))(params, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_b[name]),
                                   np.asarray(g_a[name]), rtol=2e-5,
                                   atol=1e-7, err_msg=name)

    bad = Transformer(dataclasses.replace(config, loss_chunk=5))
    with pytest.raises(ValueError, match="divide"):
        jax.jit(bad.loss)(params, tokens)


def test_scan_layers_matches_unrolled(rng):
    """scan_layers is a layout/compile-time change only: with the same
    weights (converted via stack_layers) the loss and gradients match the
    unrolled model; unstack_layers round-trips the store."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        stack_layers, unstack_layers)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=3,
                               d_ff=64, max_seq=16, dtype=jnp.float32)
    tokens = rng.integers(0, 64, (4, 16)).astype(np.int32)
    plain = Transformer(config)
    scanned = Transformer(dataclasses.replace(config, scan_layers=True))
    params = plain.init_params(0)
    stacked = stack_layers(params, config.n_layers)

    assert set(stacked) == set(scanned.param_shapes())
    assert scanned.num_params() == plain.num_params()
    back = unstack_layers(stacked)
    assert set(back) == set(params)
    for name in params:
        np.testing.assert_array_equal(np.asarray(back[name]),
                                      np.asarray(params[name]))

    loss_a = float(jax.jit(plain.loss)(params, tokens))
    loss_b = float(jax.jit(scanned.loss)(stacked, tokens))
    np.testing.assert_allclose(loss_b, loss_a, rtol=1e-6)

    # atol covers f32 reassociation noise: scan accumulates the embed
    # grad layer-by-layer in a different order than the unrolled sum
    g_a = stack_layers(jax.jit(jax.grad(plain.loss))(params, tokens),
                       config.n_layers)
    g_b = jax.jit(jax.grad(scanned.loss))(stacked, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_b[name]),
                                   np.asarray(g_a[name]), rtol=2e-5,
                                   atol=2e-6, err_msg=name)

    # remat composes with scan (checkpointed scan body), still exact
    remat_scan = Transformer(dataclasses.replace(
        config, scan_layers=True, remat=True))
    loss_c = float(jax.jit(remat_scan.loss)(stacked, tokens))
    np.testing.assert_allclose(loss_c, loss_a, rtol=1e-6)
    g_c = jax.jit(jax.grad(remat_scan.loss))(stacked, tokens)
    for name in g_a:
        np.testing.assert_allclose(np.asarray(g_c[name]),
                                   np.asarray(g_a[name]), rtol=2e-5,
                                   atol=2e-6, err_msg=name)


def test_scan_layers_generation_matches_unrolled(rng):
    """KV-cached decode (prefill collect_kv + per-layer layer_view) works
    on the stacked layout and matches the unrolled model token-exactly."""
    import dataclasses

    from parameter_server_distributed_tpu.models.generation import generate
    from parameter_server_distributed_tpu.models.transformer import (
        stack_layers)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=32, dtype=jnp.float32)
    plain = Transformer(config)
    scanned = Transformer(dataclasses.replace(config, scan_layers=True))
    params = plain.init_params(0)
    stacked = stack_layers(params, config.n_layers)
    prompt = rng.integers(0, 64, (2, 5)).astype(np.int32)

    out_a = np.asarray(generate(plain, params, prompt, max_new_tokens=8))
    out_b = np.asarray(generate(scanned, stacked, prompt, max_new_tokens=8))
    np.testing.assert_array_equal(out_a, out_b)


def test_scan_layers_sharded_training():
    """The stacked store trains under a dp x tp mesh: transformer_rule
    shards the trailing weight dims and leaves the scanned layer dim
    whole."""
    from jax.sharding import PartitionSpec

    model = small_lm(scan_layers=True)
    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    rule = transformer_rule(mesh)
    spec = rule("blocks/attn/wq", (2, 128, 128))
    assert spec == PartitionSpec(None, "fsdp", "tensor")
    spec = rule("blocks/mlp/w2", (2, 512, 128))
    assert spec == PartitionSpec(None, "tensor", "fsdp")

    trainer = ShardedTrainer(model.loss, mesh, rule,
                             make_optimizer("adam", 1e-3))
    state = trainer.init_state(model.init_params(0))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 1024, (8, 256)).astype(np.int32)
    losses = []
    for _ in range(3):
        state, metrics = trainer.step(state, tokens)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_scan_layers_rejects_moe():
    with pytest.raises(ValueError, match="homogeneous"):
        Transformer(TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                      n_layers=2, d_ff=64, moe_every=2,
                                      scan_layers=True))


def test_registry_seq_override():
    """seq_len builds the LM at the requested context length, the
    synthetic token stream follows, and non-LM models reject it."""
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)

    model, batches = get_model_and_batches("small_lm", 2, seq_len=512)
    assert model.config.max_seq == 512
    batch = next(batches)
    assert batch.shape == (2, 512)
    with pytest.raises(ValueError, match="sequence length"):
        get_model_and_batches("mnist_mlp", 2, seq_len=512)


def test_flops_per_sample_accounting():
    """PaLM-convention FLOPs: 6P + 12*L*d*S per token; remat-credited adds
    the recompute forward (8P + 16*L*d*S).  MoE counts ACTIVE-expert
    FLOPs: P_active excludes the (E - top_k) experts a token never
    runs."""
    import dataclasses

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    config = TransformerConfig(vocab=128, d_model=64, n_heads=4, n_layers=2,
                               d_ff=128, max_seq=32, dtype=jnp.float32)
    model = Transformer(config)
    base = model.flops_per_sample()
    seq = config.max_seq
    assert base == (6.0 * model.num_params() * seq
                    + 12.0 * config.n_layers * config.d_model * seq * seq)
    credited = model.flops_per_sample(remat_credited=True)
    assert credited == (8.0 * model.num_params() * seq
                        + 16.0 * config.n_layers * config.d_model * seq * seq)
    moe = Transformer(dataclasses.replace(config, moe_every=2,
                                          moe_experts=4, moe_top_k=1))
    # layer 1 (1-based layer 2) is MoE: 3 of 4 experts inactive per token
    active = moe.num_params() - 1 * 3 * 2 * config.d_model * config.d_ff
    assert moe.flops_per_sample() == (
        6.0 * active * seq
        + 12.0 * config.n_layers * config.d_model * seq * seq)
    # top_k=2 activates one more expert's worth of FLOPs
    moe2 = Transformer(dataclasses.replace(config, moe_every=2,
                                           moe_experts=4, moe_top_k=2))
    assert moe2.flops_per_sample() > moe.flops_per_sample()


def test_vit_flops_accounting_excludes_non_matmul_params():
    """ViT MFU numerator: embed/pos is an add (no FLOPs credit), patch/w
    sees only the n_patches patch tokens (never CLS), and the classifier
    head sees exactly one pooled token."""
    import math

    from parameter_server_distributed_tpu.models.vit import ViT, ViTConfig

    c = ViTConfig(image_size=32, patch_size=8, d_model=64, n_heads=4,
                  n_layers=2, d_ff=128, num_classes=10)
    model = ViT(c)
    shapes = model.param_shapes()
    s, n = c.seq_len, c.n_patches
    block = sum(math.prod(shape) for name, shape in shapes.items()
                if len(shape) == 2
                and name not in ("lm_head/w", "embed/pos", "patch/w"))
    expected = (6.0 * (block * s + math.prod(shapes["patch/w"]) * n
                       + c.d_model * c.num_classes)
                + 12.0 * c.n_layers * c.d_model * s * s)
    assert model.flops_per_sample() == expected
    # the two excluded tables would have inflated the numerator
    assert math.prod(shapes["embed/pos"]) > 0
    assert model.flops_per_sample() < expected + 6.0 * s * math.prod(
        shapes["embed/pos"])


# what of q/k/v's projection a mesh with tensor: 2 can observe: the
# config, whether the store is quantized, and whether one contraction is
# then expected in place of three
@pytest.mark.parametrize("scan", [True, False],
                         ids=["scanned", "unrolled"])
def test_full_remat_keeps_the_mixers_output_on_a_tensor_axis(scan, rng,
                                                             monkeypatch):
    """On ``fsdp 2 x tensor 2`` remat "full" keeps ONE named value a layer
    of this model (the mixer branch's reduced output, so the remat forward
    repeats no all-reduce) beside the blockwise kernel's two, which it asks
    for on every mesh and without one and which nothing carries here (the
    CPU's arm is the einsum); what is kept is what would have been
    recomputed, so the loss and every gradient are those of the same mesh
    with nothing kept."""
    from parameter_server_distributed_tpu.models.transformer import MIXER_OUT
    from parameter_server_distributed_tpu.ops.pallas import ATTN_KERNEL_KEPT
    from parameter_server_distributed_tpu.parallel.mesh import batch_sharding
    from parameter_server_distributed_tpu.parallel.sharding import shard_store

    config = TransformerConfig(
        vocab=256, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=16,
        dtype=jnp.float32, pos_emb="learned", norm="layernorm", bias=True,
        remat=True, scan_layers=scan)
    model = Transformer(config)
    assert _names_full_remat_keeps(model, monkeypatch) == ATTN_KERNEL_KEPT
    model.on_mesh(build_mesh(MeshConfig(fsdp=4), devices=jax.devices()[:4]))
    assert _names_full_remat_keeps(model, monkeypatch) == ATTN_KERNEL_KEPT
    mesh = build_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    model.on_mesh(mesh)
    assert _names_full_remat_keeps(model, monkeypatch) == (
        ATTN_KERNEL_KEPT + (MIXER_OUT,))

    params = shard_store(model.init_params(3), mesh, transformer_rule(mesh))
    tokens = jax.device_put(
        rng.integers(0, config.vocab, (4, 16)).astype(np.int32),
        batch_sharding(mesh))

    def run():
        return (jax.jit(jax.value_and_grad(model.loss))(params, tokens),
                str(jax.make_jaxpr(jax.grad(model.loss))(
                    params, tokens)).count("dot_general"))

    got, dots_kept = run()
    monkeypatch.setattr(Transformer, "_remat_policy", lambda self: None)
    want, dots_full = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)
    # wo's dot is the one product of a layer that is not run again
    assert dots_full - dots_kept == (1 if scan else config.n_layers)


JOINED_QKV = {
    "gpt2": (dict(pos_emb="learned", norm="layernorm", bias=True,
                  scan_layers=True, remat=True), False, True),
    "grouped-rope-qk-norm": (dict(
        n_kv_heads=2, pattern=(LayerSpec(qk_norm=True),)), False, True),
    "odd-kv-width": (dict(pos_emb="learned", head_dim=5, n_kv_heads=1),
                     False, False),
    "int8": ({}, True, False),
}


@pytest.mark.parametrize("case", sorted(JOINED_QKV))
def test_q_k_v_as_one_contraction_on_a_tensor_axis(case, rng):
    """On ``fsdp 2 x tensor 2`` the three projections of a layer's normed
    input are one contraction where every width divides and the weights
    are plain arrays, three dots where not; either way the loss and every
    parameter's gradient are those of the same weights without a mesh, and
    the store keeps its names, shapes and shardings."""
    from jax.sharding import PartitionSpec

    from parameter_server_distributed_tpu.models.quant import quantize_params
    from parameter_server_distributed_tpu.parallel.mesh import batch_sharding
    from parameter_server_distributed_tpu.parallel.sharding import shard_store

    changes, quantized, joined = JOINED_QKV[case]
    config = TransformerConfig(**{**dict(
        vocab=256, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=16,
        dtype=jnp.float32), **changes})
    plain, placed = Transformer(config), Transformer(config)
    mesh = build_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    placed.on_mesh(mesh)
    params = plain.init_params(3)
    # biases and norms away from their zeros and ones, so that a bias or
    # a gain added to the wrong columns would show
    params = {name: value + 0.1 * jax.random.normal(
        jax.random.PRNGKey(i), value.shape, value.dtype)
        for i, (name, value) in enumerate(sorted(params.items()))}
    tokens = rng.integers(0, config.vocab, (4, 16)).astype(np.int32)

    rule = transformer_rule(mesh)
    kv_width = config.kv_heads * config.head_dim
    widths = {"attn/wq": config.attn_dim, "attn/wk": kv_width,
              "attn/wv": kv_width}
    projections = {name: value.shape for name, value in params.items()
                   if name[-7:] in widths}
    assert len(projections) == 3 * (1 if config.scan_layers else 2)
    for name, shape in projections.items():
        assert shape[-2:] == (config.d_model, widths[name[-7:]])
        assert rule(name, shape) == PartitionSpec(
            *[None] * (len(shape) - 2), "fsdp",
            None if shape[-1] % 2 else "tensor")
    assert not [name for name in params if "qkv" in name]

    if quantized:
        # a serving store: no gradient to an int8 leaf, so the loss alone
        params = quantize_params(params)
        on_mesh = params
        run = lambda model: jax.jit(model.loss)
    else:
        on_mesh = shard_store(params, mesh, rule)
        run = lambda model: jax.jit(jax.value_and_grad(model.loss))
    want = run(plain)(params, tokens)
    got = run(placed)(on_mesh, jax.device_put(tokens, batch_sharding(mesh)))
    if not quantized:
        assert {k: v.shape for k, v in got[1].items()} == {
            k: v.shape for k, v in params.items()}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)

    def dots(model):
        return str(jax.make_jaxpr(model.loss)(params, tokens)).count(
            "dot_general")

    bodies = 1 if config.scan_layers else config.n_layers
    assert dots(plain) - dots(placed) == (2 * bodies if joined else 0)
