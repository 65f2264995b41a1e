"""MoE layer: routing semantics, capacity drops, expert-parallel sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.models.moe import (MoEConfig, MoELayer,
                                                         moe_sharding_rule)
from parameter_server_distributed_tpu.parallel.mesh import build_mesh
from parameter_server_distributed_tpu.parallel.sharding import shard_store


def test_moe_output_shape_and_aux(rng):
    layer = MoELayer(MoEConfig(d_model=16, d_ff=32, num_experts=4))
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
    out, aux = layer.apply(params, x)
    assert out.shape == (2, 8, 16)
    assert np.isfinite(float(aux))
    # perfectly balanced routing gives aux == 1; anything routed gives >= 1
    assert float(aux) >= 1.0 - 1e-5


def test_moe_matches_manual_single_expert(rng):
    """With one expert and ample capacity, MoE == a plain gated FFN."""
    layer = MoELayer(MoEConfig(d_model=8, d_ff=16, num_experts=1,
                               capacity_factor=2.0))
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((1, 4, 8)), jnp.float32)
    out, _ = layer.apply(params, x)
    tokens = x.reshape(4, 8)
    # router prob is 1.0 for the single expert
    h = jax.nn.gelu(tokens @ params["moe/w1"][0])
    expect = (h @ params["moe/w2"][0])
    np.testing.assert_allclose(np.asarray(out).reshape(4, 8),
                               np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_moe_capacity_drops_tokens():
    """Tiny capacity: over-capacity tokens produce zero output."""
    config = MoEConfig(d_model=4, d_ff=8, num_experts=2, capacity_factor=0.25)
    layer = MoELayer(config)
    params = layer.init_params(0)
    # force all 8 tokens to expert 0 via a biased router
    params["moe/router/w"] = jnp.zeros((4, 2)).at[:, 0].set(10.0)
    x = jnp.ones((1, 8, 4), jnp.float32)
    cap = layer.capacity(8)
    assert cap == 1
    out, _ = layer.apply(params, x)
    nonzero_tokens = np.count_nonzero(
        np.abs(np.asarray(out).reshape(8, 4)).sum(-1) > 1e-9)
    assert nonzero_tokens == cap


def test_moe_expert_parallel_matches_unsharded(rng):
    mesh = build_mesh(MeshConfig(expert=4, data=2))
    layer = MoELayer(MoEConfig(d_model=16, d_ff=32, num_experts=8))
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
    base_out, base_aux = layer.apply(params, x)

    sharded_params = shard_store(params, mesh, moe_sharding_rule(mesh))
    w1 = sharded_params["moe/w1"]
    assert {s.data.shape for s in w1.addressable_shards} == {(2, 16, 32)}

    @jax.jit
    def run(p, x):
        return layer.apply(p, x)

    out, aux = run(sharded_params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux), float(base_aux), rtol=1e-5)


def test_moe_gradients_flow(rng):
    layer = MoELayer(MoEConfig(d_model=8, d_ff=16, num_experts=4))
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((1, 8, 8)), jnp.float32)

    def loss(p):
        out, aux = layer.apply(p, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    grads = jax.grad(loss)(params)
    for name, g in grads.items():
        assert np.isfinite(np.asarray(g)).all(), name
    # router must receive gradient signal (through the gate)
    assert np.abs(np.asarray(grads["moe/router/w"])).max() > 0


# ---------------------------------------------------------------------------
# MoE inside the Transformer (moe_every)
# ---------------------------------------------------------------------------

def test_moe_transformer_param_shapes_and_training(rng):
    from parameter_server_distributed_tpu.models.transformer import moe_lm

    model = moe_lm()
    shapes = model.param_shapes()
    assert "layer1/moe/router/w" in shapes and "layer3/moe/w2" in shapes
    assert "layer0/mlp/w1" in shapes  # odd layers stay dense
    assert "layer1/mlp/w1" not in shapes

    params = model.init_params(0)
    tokens = jnp.asarray(rng.integers(0, 1024, (4, 32)), jnp.int32)
    loss_grad = jax.jit(jax.value_and_grad(model.loss))
    losses = []
    import optax
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    for _ in range(8):
        loss, grads = loss_grad(params, tokens)
        losses.append(float(loss))
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # router actually received gradient signal
    assert float(jnp.abs(grads["layer1/moe/router/w"]).sum()) > 0


def test_moe_transformer_expert_parallel_matches_single_device(rng):
    """The EP-sharded MoE LM step must equal the unsharded one."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models.transformer import (
        moe_lm, transformer_rule)
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, TrainState, make_optimizer, make_train_step)

    model = moe_lm()
    params = model.init_params(0)
    tokens = np.asarray(rng.integers(0, 1024, (4, 32)), np.int32)

    opt = make_optimizer("sgd", 0.1)
    single_step = jax.jit(make_train_step(model.loss, opt))
    s0 = TrainState.create(params, opt)
    s_single, m_single = single_step(s0, jnp.asarray(tokens))

    mesh = build_mesh(MeshConfig(expert=2, data=2, fsdp=2))
    trainer = ShardedTrainer(model.loss, mesh, transformer_rule(mesh),
                             make_optimizer("sgd", 0.1))
    state = trainer.init_state(model.init_params(0))
    s_shard, m_shard = trainer.step(state, tokens)

    np.testing.assert_allclose(float(m_shard["loss"]), float(m_single["loss"]),
                               rtol=1e-5)
    for name in ("layer1/moe/w1", "layer0/mlp/w1", "layer1/moe/router/w"):
        np.testing.assert_allclose(
            np.asarray(s_shard.params[name]), np.asarray(s_single.params[name]),
            rtol=1e-4, atol=1e-6, err_msg=name)


def test_moe_transformer_cached_generation_matches_full_forward(rng):
    """Token-exact parity holds when no token is capacity-dropped in either
    path: decode is drop-free by design, and moe_capacity=8 makes the
    full forward's capacity exceed the token count.  (Under training
    capacity, dropping is batch-global — dependent on other sequence
    positions — so decode parity for dropped tokens is impossible by
    construction; see Transformer.ffn_residual.)"""
    from parameter_server_distributed_tpu.models.generation import generate
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from tests.test_generation import greedy_by_full_forward

    model = Transformer(TransformerConfig(
        vocab=1024, d_model=128, n_heads=4, n_layers=4, d_ff=512,
        max_seq=64, dtype=jnp.float32, moe_every=2, moe_experts=4,
        moe_capacity=8.0))
    params = model.init_params(1)
    prompt = jnp.asarray(rng.integers(0, 1024, (2, 8)), jnp.int32)
    expected = greedy_by_full_forward(model, params, prompt, 4)
    got = generate(model, params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def test_moe_decode_is_drop_free_under_collisions(rng):
    """Every decode-step token gets its expert output even when all batch
    rows route to the same expert (training capacity would drop some)."""
    from parameter_server_distributed_tpu.models.moe import MoEConfig, MoELayer

    layer = MoELayer(MoEConfig(d_model=16, d_ff=32, num_experts=4,
                               capacity_factor=1.0))
    params = layer.init_params(0)
    # identical rows -> identical routing -> guaranteed collision
    x = jnp.tile(jnp.asarray(rng.standard_normal((1, 1, 16)), jnp.float32),
                 (4, 1, 1))
    dropped, _ = layer.apply(params, x)            # cap=1: rows 2..4 dropped
    kept, _ = layer.apply(params, x, capacity_override=4)
    assert float(jnp.abs(dropped[1:]).sum()) == 0.0  # training-style drop
    assert float(jnp.abs(kept[1:]).sum()) > 0.0      # drop-free inference
    np.testing.assert_allclose(np.asarray(kept[0]), np.asarray(kept[3]),
                               rtol=1e-6)


def test_moe_lm_expert_plus_tensor_parallel_matches_unsharded(rng):
    """MoE transformer step on an expert:2 x tensor:2 x data:2 mesh (expert
    dispatch + within-expert Megatron TP on d_ff) must match the
    single-device run exactly."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig, transformer_rule)
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh
    from parameter_server_distributed_tpu.parallel.train_step import (
        ShardedTrainer, make_optimizer)

    config = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, dtype=jnp.float32,
                               moe_every=2, moe_experts=4)
    tokens = rng.integers(0, 64, (8, 16)).astype(np.int32)
    results = {}
    for label, mesh_config in (("sharded", MeshConfig(expert=2, tensor=2,
                                                      data=2)),
                               ("single", MeshConfig(data=8))):
        mesh = build_mesh(mesh_config)
        model = Transformer(config, mesh=mesh)
        trainer = ShardedTrainer(model.loss, mesh, transformer_rule(mesh),
                                 make_optimizer("sgd", 0.1))
        state = trainer.init_state(model.init_params(0))
        if label == "sharded":
            spec = state.params["layer1/moe/w1"].sharding.spec
            assert spec[0] == "expert" and spec[2] == "tensor", spec
        state, metrics = trainer.step(state, tokens)
        results[label] = (float(metrics["loss"]),
                          np.asarray(state.params["layer1/moe/w1"]))
    np.testing.assert_allclose(results["sharded"][0], results["single"][0],
                               rtol=1e-5)
    np.testing.assert_allclose(results["sharded"][1], results["single"][1],
                               rtol=1e-4, atol=1e-6)


def test_moe_top2_matches_manual_mixture(rng):
    """top_k=2 with ample capacity == the renormalized two-expert
    mixture computed densely per token."""
    layer = MoELayer(MoEConfig(d_model=8, d_ff=16, num_experts=4, top_k=2,
                               capacity_factor=8.0))
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
    out, aux = layer.apply(params, x)

    tokens = np.asarray(x).reshape(8, 8)
    probs = np.asarray(jax.nn.softmax(
        tokens @ np.asarray(params["moe/router/w"]), axis=-1))
    expect = np.zeros_like(tokens)
    for t in range(8):
        top2 = np.argsort(probs[t])[::-1][:2]
        gates = probs[t][top2] / probs[t][top2].sum()
        for g, e in zip(gates, top2):
            h = np.asarray(jax.nn.gelu(
                tokens[t] @ np.asarray(params["moe/w1"][e])))
            expect[t] += g * (h @ np.asarray(params["moe/w2"][e]))
    np.testing.assert_allclose(np.asarray(out).reshape(8, 8), expect,
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def test_moe_top2_gradients_and_expert_parallel(rng):
    """top-2 routing trains under expert sharding and matches the
    unsharded layer."""
    config = MoEConfig(d_model=8, d_ff=16, num_experts=4, top_k=2,
                       capacity_factor=4.0)
    layer = MoELayer(config)
    params = layer.init_params(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 8)), jnp.float32)

    def loss(p, x):
        out, aux = layer.apply(p, x)
        return jnp.sum(out ** 2) + 0.01 * aux

    grads = jax.jit(jax.grad(loss))(params, x)
    for name in ("moe/router/w", "moe/w1", "moe/w2"):
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name

    unsharded, _ = jax.jit(layer.apply)(params, x)
    mesh = build_mesh(MeshConfig(expert=4, data=2))
    sharded_params = shard_store(params, mesh, moe_sharding_rule(mesh))
    sharded, _ = jax.jit(layer.apply)(sharded_params, x)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(unsharded),
                               rtol=1e-5, atol=1e-6)


def test_moe_top_k_validation():
    with pytest.raises(ValueError, match="top_k"):
        MoELayer(MoEConfig(num_experts=4, top_k=5))
    with pytest.raises(ValueError, match="top_k"):
        MoELayer(MoEConfig(num_experts=4, top_k=0))


def test_moe_lm_top2_trains_and_decodes(rng):
    """The top-2 MoE transformer trains and its KV-cached decode stays
    token-exact vs the full forward.  Ample moe_capacity makes the
    training-capacity full forward drop-free too, so the equality is
    seed-robust (decode is always drop-free; the reference forward would
    otherwise drop under an unlucky routing draw)."""
    from parameter_server_distributed_tpu.models.generation import generate
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    model = Transformer(TransformerConfig(
        vocab=64, d_model=128, n_heads=4, n_layers=4, d_ff=512, max_seq=32,
        dtype=jnp.float32, moe_every=2, moe_experts=4, moe_top_k=2,
        moe_capacity=8.0))
    params = model.init_params(0)
    tokens = rng.integers(0, 64, (2, 16)).astype(np.int32)
    loss0 = float(jax.jit(model.loss)(params, tokens))
    assert np.isfinite(loss0)

    prompt = rng.integers(0, 64, (1, 4)).astype(np.int32)
    out = np.asarray(generate(model, params, prompt, max_new_tokens=6))
    # greedy decode must equal re-running the full forward each step
    ids = list(prompt[0])
    apply = jax.jit(model.apply)  # one program a length, not one a primitive
    for _ in range(6):
        logits = apply(params, np.asarray([ids], np.int32))
        ids.append(int(np.asarray(logits)[0, -1].argmax()))
    np.testing.assert_array_equal(out[0], np.asarray(ids[4:]))


def test_moe_350m_preset_shape(rng):
    """The flagship-scale sparse preset: lm_350m trunk, 12 routed layers
    over 8 experts, ~1.07B total params; MFU uses ACTIVE-expert FLOPs
    (top_k of 8 experts per token — the per-token compute is ~the dense
    350M trunk's, which is the point of sparse MoE).  Full-size training
    is a TPU job (the sweep's moe350_b16 row); expert-sharded TRAINING
    coverage for this layout lives in test_moe/test_parallel's small
    twins."""
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)

    model, batches = get_model_and_batches("moe_350m", 2)
    c = model.config
    assert sum(c.is_moe_layer(i) for i in range(c.n_layers)) == 12
    assert 1.0e9 < model.num_params() < 1.2e9
    fps = model.flops_per_sample()
    inactive = 12 * (c.moe_experts - c.moe_top_k) * 2 * c.d_model * c.d_ff
    assert fps == (6.0 * (model.num_params() - inactive) * c.max_seq
                   + 12.0 * c.n_layers * c.d_model * c.max_seq ** 2)
    tokens, = (next(batches),)
    assert tokens.shape == (2, 1024)


# ---- dropless_experts(live=...): the tokens that are somebody's ----

_MODES = {
    # name: (held, groups, groups_kept)
    "plain": (None, 1, 1),
    "held": ((4, 8), 1, 1),
    "held_fewer_than_top_k": ((6, 2), 1, 1),
    "held_group_limit": ((4, 4), 4, 2),
}


def _dropless_case(mode, score, n=40, seed=5):
    """(args, options) of a call at 16 experts, top-3, in float32."""
    from parameter_server_distributed_tpu.models import moe

    held, groups, kept = _MODES[mode]
    d, e, f = 16, 16, 8
    count = e if held is None else held[1]
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    args = (normal(n, d), normal(n, e), normal(count, d, f) / 4,
            normal(count, f, d) / 3, normal(count, d, f) / 4)
    options = dict(top_k=3, act="swiglu", score=score, held=held,
                   groups=groups, groups_kept=kept,
                   bias=0.05 * normal(e) if score == "sigmoid" else None)
    live = jnp.asarray(rng.random(n) < 0.45)
    return moe.dropless_experts, args, options, live


@pytest.mark.parametrize("past_sort_limit", [False, True],
                         ids=["sorted", "counted"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
@pytest.mark.parametrize("mode", list(_MODES))
def test_dropless_experts_routes_the_live_tokens_alone(mode, score,
                                                       past_sort_limit,
                                                       monkeypatch):
    """Under ``live`` a dead token's assignments belong to no group: a live
    token's row is the unmasked call's bit for bit (which other rows share
    its group does not enter it), a dead token's is zeros, and every entry
    of ``loads`` (the held experts', what went elsewhere, the rank places)
    is that of a call on the live tokens alone.  The row count is static:
    the same under any mask.  Past ``_SORT_LIMIT`` the counting sort takes
    one more key and gives the same."""
    from parameter_server_distributed_tpu.models import moe

    if past_sort_limit:
        monkeypatch.setattr(moe, "_SORT_LIMIT", 64)
    fn, args, options, live = _dropless_case(mode, score)
    run = jax.jit(lambda live, *a: fn(*a, live=live, **options))
    whole, whole_loads = jax.jit(lambda *a: fn(*a, **options))(*args)
    out, loads = run(live, *args)
    keep = np.asarray(live)
    assert 5 < keep.sum() < 35
    assert np.array_equal(np.asarray(out)[keep], np.asarray(whole)[keep])
    assert float(np.max(np.abs(np.asarray(whole)[keep]))) > 0.05
    assert not np.asarray(out)[~keep].any()
    x, logits, *weights = args
    _, alone = jax.jit(lambda *a: fn(*a, **options))(
        x[keep], logits[keep], *weights)
    assert np.array_equal(np.asarray(loads), np.asarray(alone))
    assert loads.shape == whole_loads.shape
    assert int(loads.sum()) < int(whole_loads.sum())
    # every token live is the call without a mask; none is zeros and no load
    everyone, every_loads = run(jnp.ones_like(live), *args)
    assert np.array_equal(np.asarray(everyone), np.asarray(whole))
    assert np.array_equal(np.asarray(every_loads), np.asarray(whole_loads))
    nobody, no_loads = run(jnp.zeros_like(live), *args)
    assert not np.asarray(nobody).any() and not np.asarray(no_loads).any()


@pytest.mark.parametrize("mode,digest", [
    ("plain",
     "a8d265bc256e0516ad2c09a1c084a8661db0da9313f4f69e078c4a23b6ea90a0"),
    ("held_group_limit",
     "6b451d60bebc96f2233ad452e257a57d9ba2b2ad633b93c39030087a7b92c676"),
])
def test_dropless_experts_without_a_mask_traces_what_it_traced(mode, digest):
    """``live=None`` is the function PR 59 had, equation for equation (a
    jaxpr's text holds no names): training, ``Transformer.apply`` and
    every caller without a mask keep their programs.  Where a later change
    means to alter them, print the text on both sides, read the difference
    and replace the digests."""
    import hashlib

    fn, args, options, _ = _dropless_case(mode, "sigmoid")
    text = str(jax.make_jaxpr(lambda *a: fn(*a, live=None, **options))(
        *args))
    assert hashlib.sha256(text.encode()).hexdigest() == digest
