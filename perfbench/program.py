"""The seam between the benchmark and the program: everything the benchmark
takes from ``parameter_server_distributed_tpu`` is named here.  The program
is never edited; a configuration is registered under a name of its own in
``models/registry.REGISTRY`` and then resolved by the entry points as any
model is.
"""

from __future__ import annotations

import math

SEED_MODULUS = 2 ** 31 - 1   # jax.random.key takes a 32-bit signed seed


def program_seed(seed: int) -> int:
    return int(seed) % SEED_MODULUS


def registry_snapshot() -> dict:
    """The program's counters, gauges and histograms (``obs/stats``) now."""
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    return obs_stats.REGISTRY.snapshot()


def transformer_config(config: dict, **overrides):
    """A published GPT-2 ``config.json`` as the program's TransformerConfig:
    the path ``models/hf.config_from_hf_gpt2`` documents."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        TransformerConfig)

    assumed = config["assumed"]
    if config["activation_function"] != "gelu_new":
        raise ValueError("the program's gelu is the tanh approximation "
                         "(gelu_new); the configuration asks for "
                         f"{config['activation_function']!r}")
    fields = dict(
        vocab=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config.get("n_inner") or 4 * config["n_embd"],
        max_seq=config["n_positions"],
        dtype=getattr(jnp, assumed["dtype"]),
        pos_emb="learned", norm="layernorm", bias=True,
        norm_eps=float(config["layer_norm_epsilon"]),
        remat=bool(assumed["remat"]), remat_policy=assumed["remat_policy"],
        scan_layers=bool(assumed["scan_layers"]),
        loss_chunk=int(assumed["loss_chunk"]))
    fields.update(overrides)
    return TransformerConfig(**fields)


def register_model(config: dict, batches_fn) -> str:
    """Put the configuration into the program's registry; returns the name
    the entry points resolve.  ``batches_fn(batch_size, seed)`` is the data
    factory the registry hands to the trainer."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.registry import REGISTRY
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)

    def factory(dtype=jnp.bfloat16, remat=None, scan_layers=None,
                remat_policy=None):
        overrides = {"dtype": dtype}
        if remat is not None:
            overrides["remat"] = remat
        if scan_layers is not None:
            overrides["scan_layers"] = scan_layers
        if remat_policy:
            overrides["remat_policy"] = remat_policy
        return Transformer(transformer_config(config, **overrides))

    name = config["program_name"]
    REGISTRY[name] = (factory, batches_fn, "tokens")
    return name


def build_model(config: dict, **overrides):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)

    return Transformer(transformer_config(config, **overrides))


def make_weights(model, seed: int, tie_head: bool = True) -> dict:
    """The program's parameter store, made on the device in ONE jitted call
    from the seed, in the model's own dtype: normal(0, 0.02) matrices as
    GPT-2 initialises them, residual projections scaled by 1/sqrt(2 L),
    positions 0.01, norm gains one, biases zero, and the head tied to the
    token embedding as published (the program stores it as a matrix of its
    own, ``lm_head/w``)."""
    import jax
    import jax.numpy as jnp

    shapes = model.param_shapes()
    names = sorted(shapes)
    dtype = model.config.dtype
    layers = model.config.n_layers

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith(("/bias", "/b1", "/b2", "/bq", "/bk", "/bv",
                                "/bo")):
                out[name] = jnp.zeros(shape, dtype)
            elif name == "lm_head/w" and tie_head:
                continue
            else:
                std = 0.01 if name == "embed/pos" else 0.02
                if name.endswith(("attn/wo", "mlp/w2")):
                    std /= math.sqrt(2.0 * layers)
                out[name] = (std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype)
        if tie_head:
            out["lm_head/w"] = out["embed/tok"].T
        return out

    return build(jax.random.key(program_seed(seed)))


def reference_weights(params: dict, n_layers: int) -> dict:
    """The program's store in the reference's names, as float32.  Accepts
    the stacked (``blocks/...``) and the unrolled (``layer<i>/...``)
    layouts."""
    import jax.numpy as jnp

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    def block(suffix):
        if f"blocks/{suffix}" in params:
            return f32(params[f"blocks/{suffix}"])
        return jnp.stack([f32(params[f"layer{i}/{suffix}"])
                          for i in range(n_layers)])

    names = {"ln1_g": "ln1/scale", "ln1_b": "ln1/bias",
             "ln2_g": "ln2/scale", "ln2_b": "ln2/bias",
             "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
             "wo": "attn/wo", "bq": "attn/bq", "bk": "attn/bk",
             "bv": "attn/bv", "bo": "attn/bo",
             "w1": "mlp/w1", "b1": "mlp/b1", "w2": "mlp/w2", "b2": "mlp/b2"}
    return {"wte": f32(params["embed/tok"]), "wpe": f32(params["embed/pos"]),
            "lnf_g": f32(params["final_ln/scale"]),
            "lnf_b": f32(params["final_ln/bias"]),
            "head": f32(params["lm_head/w"]),
            "blocks": {ours: block(theirs) for ours, theirs in names.items()}}
