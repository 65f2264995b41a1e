"""HuggingFace interop: convert GPT-2-family checkpoints into this
framework's Transformer.

A user migrating to this framework should be able to bring a pretrained
torch checkpoint with them and serve it on TPU through the native stack
(KV-cached generate, continuous batching, int8 quantization, speculative
decoding).  GPT-2 is the canonical test family: its architecture needs
exactly the three compatibility knobs TransformerConfig exposes
(``pos_emb="learned"``, ``norm="layernorm"``, ``bias=True``) plus weight
re-layout:

- HF ``Conv1D`` stores weights [in, out] — the same x @ W convention as
  this package, so attention/MLP matrices copy through without transpose;
  the fused ``c_attn`` [d, 3d] splits into wq/wk/wv columns.
- ``wte`` is tied to the LM head: ``lm_head/w = wte.T``.
- GELU: HF ``gelu_new`` is the tanh approximation — ``jax.nn.gelu``'s
  default, so activations match.
- LayerNorm eps 1e-5 (``config.layer_norm_epsilon``) -> ``norm_eps``.

Verified by logits parity against the torch forward (tests/test_hf.py)
on random-init models — no network needed; the same code path loads real
published weights where a checkout of them exists.

The reference has no model zoo or interop at all (its "gradient" is a
0.01-constant stub — reference src/worker.cpp:316-329); this is added
capability for the serving/fine-tuning story.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax.numpy as jnp
import numpy as np

from .transformer import Transformer, TransformerConfig


def _state_dict_np(hf_model: Any) -> dict:
    """torch state_dict -> numpy.  Upcasts through float32 first: torch
    bf16 tensors (the dtype real checkpoints ship in, and the standard
    torch_dtype=bfloat16 loading path) do not support .numpy()."""
    return {name: t.detach().cpu().float().numpy()
            for name, t in hf_model.state_dict().items()}


def config_from_hf_gpt2(hf_config: Any, *,
                        dtype=jnp.float32,
                        scan_layers: bool = False) -> TransformerConfig:
    """Map a ``transformers.GPT2Config`` onto TransformerConfig.

    Rejects configurations whose math this framework would silently get
    wrong: only the tanh-approximation GELU family is supported (the
    ``jax.nn.gelu`` default); ``n_inner`` is honored when set."""
    act = getattr(hf_config, "activation_function", "gelu_new")
    if act not in ("gelu_new", "gelu_pytorch_tanh"):
        raise ValueError(
            f"unsupported activation_function {act!r}: this framework "
            "applies the tanh-approximate GELU (jax.nn.gelu default), "
            "which matches HF 'gelu_new'/'gelu_pytorch_tanh' only")
    for variant in ("scale_attn_by_inverse_layer_idx",
                    "reorder_and_upcast_attn"):
        if getattr(hf_config, variant, False):
            raise ValueError(
                f"unsupported GPT2Config.{variant}=True: this framework "
                "scales attention scores by 1/sqrt(head_dim) only — "
                "converting would produce silently wrong logits")
    n_inner = getattr(hf_config, "n_inner", None)
    return TransformerConfig(
        vocab=hf_config.vocab_size,
        d_model=hf_config.n_embd,
        n_heads=hf_config.n_head,
        n_layers=hf_config.n_layer,
        d_ff=n_inner if n_inner else 4 * hf_config.n_embd,
        max_seq=hf_config.n_positions,
        dtype=dtype,
        pos_emb="learned",
        norm="layernorm",
        bias=True,
        norm_eps=float(hf_config.layer_norm_epsilon),
        scan_layers=scan_layers,
    )


def from_hf_gpt2(hf_model: Any, *, dtype=jnp.float32,
                 scan_layers: bool = False,
                 ) -> tuple[Transformer, dict[str, jnp.ndarray]]:
    """Convert a ``transformers.GPT2LMHeadModel`` (torch) into
    (Transformer, params).  Pure weight re-layout — no renormalization —
    so logits match the torch forward to float tolerance."""
    cfg = config_from_hf_gpt2(hf_model.config, dtype=dtype,
                              scan_layers=scan_layers)
    model = Transformer(cfg)
    sd = _state_dict_np(hf_model)
    d = cfg.d_model

    def arr(x):
        return jnp.asarray(x, dtype)

    params: dict[str, jnp.ndarray] = {
        "embed/tok": arr(sd["transformer.wte.weight"]),
        "embed/pos": arr(sd["transformer.wpe.weight"]),
        "final_ln/scale": arr(sd["transformer.ln_f.weight"]),
        "final_ln/bias": arr(sd["transformer.ln_f.bias"]),
        # weight tying: the LM head is wte transposed
        "lm_head/w": arr(sd["transformer.wte.weight"].T),
    }
    per_layer: list[dict[str, np.ndarray]] = []
    for i in range(cfg.n_layers):
        hf = f"transformer.h.{i}"
        w_attn = sd[f"{hf}.attn.c_attn.weight"]      # [d, 3d], x @ W layout
        b_attn = sd[f"{hf}.attn.c_attn.bias"]        # [3d]
        layer = {
            "ln1/scale": sd[f"{hf}.ln_1.weight"],
            "ln1/bias": sd[f"{hf}.ln_1.bias"],
            "attn/wq": w_attn[:, :d],
            "attn/wk": w_attn[:, d:2 * d],
            "attn/wv": w_attn[:, 2 * d:],
            "attn/bq": b_attn[:d],
            "attn/bk": b_attn[d:2 * d],
            "attn/bv": b_attn[2 * d:],
            "attn/wo": sd[f"{hf}.attn.c_proj.weight"],
            "attn/bo": sd[f"{hf}.attn.c_proj.bias"],
            "ln2/scale": sd[f"{hf}.ln_2.weight"],
            "ln2/bias": sd[f"{hf}.ln_2.bias"],
            "mlp/w1": sd[f"{hf}.mlp.c_fc.weight"],
            "mlp/b1": sd[f"{hf}.mlp.c_fc.bias"],
            "mlp/w2": sd[f"{hf}.mlp.c_proj.weight"],
            "mlp/b2": sd[f"{hf}.mlp.c_proj.bias"],
        }
        per_layer.append(layer)
    if scan_layers:
        for suffix in per_layer[0]:
            params[f"blocks/{suffix}"] = arr(
                np.stack([layer[suffix] for layer in per_layer]))
    else:
        for i, layer in enumerate(per_layer):
            for suffix, value in layer.items():
                params[f"layer{i}/{suffix}"] = arr(value)

    _check_shapes(model, params)
    return model, params


def _check_shapes(model: Transformer, params: dict) -> None:
    """Shape contract: exactly the parameters the config says exist."""
    expected = model.param_shapes()
    got = {name: tuple(v.shape) for name, v in params.items()}
    if got != expected:
        missing = expected.keys() - got.keys()
        extra = got.keys() - expected.keys()
        wrong = {n for n in expected.keys() & got.keys()
                 if expected[n] != got[n]}
        raise ValueError(
            f"converted store mismatch: missing={sorted(missing)} "
            f"extra={sorted(extra)} wrong_shape={sorted(wrong)}")


def _require_dense(params: Mapping[str, Any]) -> None:
    from .quant import QTensor
    if any(isinstance(v, QTensor) for v in params.values()):
        raise ValueError("cannot export an int8-quantized store; export "
                         "the pre-quantization parameters")


def _layer_view(params: Mapping[str, Any], i: int) -> dict:
    """Per-layer suffix -> numpy array, for either layer layout."""
    if any(name.startswith("blocks/") for name in params):
        return {name[len("blocks/"):]: np.asarray(v[i], np.float32)
                for name, v in params.items() if name.startswith("blocks/")}
    prefix = f"layer{i}/"
    return {name[len(prefix):]: np.asarray(v, np.float32)
            for name, v in params.items() if name.startswith(prefix)}


def to_hf_gpt2(model: Transformer, params: Mapping[str, Any]) -> dict:
    """Export a (possibly fine-tuned here) GPT-2-architecture store back
    to a ``transformers.GPT2LMHeadModel`` state_dict (torch tensors) —
    the round-trip of :func:`from_hf_gpt2`, so checkpoints trained on
    this framework load straight into the torch ecosystem.  Weight tying
    is restored from ``embed/tok`` (GPT-2's lm_head IS wte)."""
    import torch

    _require_dense(params)
    cfg = model.config
    if (cfg.pos_emb, cfg.norm, cfg.bias) != ("learned", "layernorm", True):
        raise ValueError("to_hf_gpt2 exports the GPT-2 architecture "
                         "(pos_emb='learned', norm='layernorm', bias=True)")
    t = lambda x: torch.from_numpy(  # noqa: E731 — copy: a zero-copy
        # view of the live JAX buffer would be non-writable (torch UB on
        # in-place writes / assign=True training)
        np.array(x, np.float32, copy=True))
    # HF GPT-2 ARCHITECTURALLY ties lm_head to wte.  This framework
    # trains them as separate parameters, so a fine-tuned store whose
    # head diverged from embed.T cannot be represented — reject instead
    # of silently dropping the tuned head on export.
    head = np.asarray(params["lm_head/w"], np.float32)
    tok = np.asarray(params["embed/tok"], np.float32)
    if not np.allclose(head, tok.T, rtol=1e-4, atol=1e-5):
        raise ValueError(
            "GPT-2 ties lm_head to wte but this store's lm_head/w has "
            "diverged from embed/tok.T (fine-tuning here unties them); "
            "re-tie (params['lm_head/w'] = params['embed/tok'].T) or "
            "export a LLaMA-architecture model, whose head is untied")
    sd = {
        "transformer.wte.weight": t(params["embed/tok"]),
        "transformer.wpe.weight": t(params["embed/pos"]),
        "transformer.ln_f.weight": t(params["final_ln/scale"]),
        "transformer.ln_f.bias": t(params["final_ln/bias"]),
        "lm_head.weight": t(params["embed/tok"]),     # tied
    }
    for i in range(cfg.n_layers):
        layer = _layer_view(params, i)
        hf = f"transformer.h.{i}"
        sd[f"{hf}.ln_1.weight"] = t(layer["ln1/scale"])
        sd[f"{hf}.ln_1.bias"] = t(layer["ln1/bias"])
        sd[f"{hf}.attn.c_attn.weight"] = t(np.concatenate(
            [layer["attn/wq"], layer["attn/wk"], layer["attn/wv"]], axis=1))
        sd[f"{hf}.attn.c_attn.bias"] = t(np.concatenate(
            [layer["attn/bq"], layer["attn/bk"], layer["attn/bv"]]))
        sd[f"{hf}.attn.c_proj.weight"] = t(layer["attn/wo"])
        sd[f"{hf}.attn.c_proj.bias"] = t(layer["attn/bo"])
        sd[f"{hf}.ln_2.weight"] = t(layer["ln2/scale"])
        sd[f"{hf}.ln_2.bias"] = t(layer["ln2/bias"])
        sd[f"{hf}.mlp.c_fc.weight"] = t(layer["mlp/w1"])
        sd[f"{hf}.mlp.c_fc.bias"] = t(layer["mlp/b1"])
        sd[f"{hf}.mlp.c_proj.weight"] = t(layer["mlp/w2"])
        sd[f"{hf}.mlp.c_proj.bias"] = t(layer["mlp/b2"])
    return sd


def to_hf_llama(model: Transformer, params: Mapping[str, Any], *,
                tie_word_embeddings: bool = False) -> dict:
    """Export a LLaMA-architecture store to a
    ``transformers.LlamaForCausalLM`` state_dict — the round-trip of
    :func:`from_hf_llama` (torch Linear stores [out, in]: transpose
    back).  Set ``tie_word_embeddings=True`` when the DESTINATION model
    ties lm_head to embed_tokens (TinyLlama/Llama-3.2 style): the export
    then verifies the tie still holds and omits the lm_head key —
    emitting it would silently stomp the shared embedding on load (last
    copy into the shared Parameter wins)."""
    import torch

    _require_dense(params)
    cfg = model.config
    if (cfg.pos_emb, cfg.norm, cfg.bias, cfg.mlp_act) != (
            "rope", "rms", False, "swiglu"):
        raise ValueError("to_hf_llama exports the LLaMA architecture "
                         "(rope/rms/bias-free/swiglu)")
    t = lambda x: torch.from_numpy(  # noqa: E731 — copy, as in to_hf_gpt2
        np.array(x, np.float32, copy=True))
    sd = {
        "model.embed_tokens.weight": t(params["embed/tok"]),
        "model.norm.weight": t(params["final_ln/scale"]),
    }
    if tie_word_embeddings:
        head = np.asarray(params["lm_head/w"], np.float32)
        tok = np.asarray(params["embed/tok"], np.float32)
        if not np.allclose(head, tok.T, rtol=1e-4, atol=1e-5):
            raise ValueError(
                "tie_word_embeddings=True but this store's lm_head/w has "
                "diverged from embed/tok.T (fine-tuning unties them); "
                "re-tie or export for an untied destination model")
    else:
        sd["lm_head.weight"] = t(np.asarray(params["lm_head/w"],
                                            np.float32).T)
    for i in range(cfg.n_layers):
        layer = _layer_view(params, i)
        hf = f"model.layers.{i}"
        sd[f"{hf}.input_layernorm.weight"] = t(layer["ln1/scale"])
        sd[f"{hf}.self_attn.q_proj.weight"] = t(layer["attn/wq"].T)
        sd[f"{hf}.self_attn.k_proj.weight"] = t(layer["attn/wk"].T)
        sd[f"{hf}.self_attn.v_proj.weight"] = t(layer["attn/wv"].T)
        sd[f"{hf}.self_attn.o_proj.weight"] = t(layer["attn/wo"].T)
        sd[f"{hf}.post_attention_layernorm.weight"] = t(layer["ln2/scale"])
        sd[f"{hf}.mlp.gate_proj.weight"] = t(layer["mlp/w1"].T)
        sd[f"{hf}.mlp.up_proj.weight"] = t(layer["mlp/w3"].T)
        sd[f"{hf}.mlp.down_proj.weight"] = t(layer["mlp/w2"].T)
    return sd


def config_from_hf_llama(hf_config: Any, *, dtype=jnp.bfloat16,
                         scan_layers: bool = False) -> TransformerConfig:
    """Map a ``transformers.LlamaConfig`` onto TransformerConfig.  The
    LLaMA family IS this framework's native architecture (RoPE in the
    rotate-half convention, RMSNorm, GQA, no biases) plus the SwiGLU MLP
    knob — so the mapping is direct.  Rejects rope_scaling and attention
    bias, whose math this framework does not implement."""
    if getattr(hf_config, "rope_scaling", None):
        raise ValueError("unsupported rope_scaling: this framework "
                         "implements plain RoPE only")
    if getattr(hf_config, "attention_bias", False):
        raise ValueError("unsupported attention_bias=True for the "
                         "LLaMA-family conversion (bias-free attention)")
    act = getattr(hf_config, "hidden_act", "silu")
    if act != "silu":
        raise ValueError(f"unsupported hidden_act {act!r}: the SwiGLU "
                         "path applies silu gating only")
    return TransformerConfig(
        vocab=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_heads=hf_config.num_attention_heads,
        # a config may give the head size apart from hidden / heads
        head_dim=int(getattr(hf_config, "head_dim", 0) or 0),
        n_kv_heads=(hf_config.num_key_value_heads
                    if hf_config.num_key_value_heads
                    != hf_config.num_attention_heads else 0),
        n_layers=hf_config.num_hidden_layers,
        d_ff=hf_config.intermediate_size,
        max_seq=hf_config.max_position_embeddings,
        dtype=dtype,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(hf_config.rms_norm_eps),
        mlp_act="swiglu",
        scan_layers=scan_layers,
    )


def from_hf_llama(hf_model: Any, *, dtype=jnp.bfloat16,
                  scan_layers: bool = False,
                  ) -> tuple[Transformer, dict[str, jnp.ndarray]]:
    """Convert a ``transformers.LlamaForCausalLM`` (torch) into
    (Transformer, params).  torch ``nn.Linear`` stores [out, in], so every
    projection transposes into this package's x @ W layout; gate_proj ->
    mlp/w1, up_proj -> mlp/w3, down_proj -> mlp/w2.  RoPE conventions
    already agree (both rotate-half), so no head permutation is needed."""
    cfg = config_from_hf_llama(hf_model.config, dtype=dtype,
                               scan_layers=scan_layers)
    model = Transformer(cfg)
    sd = _state_dict_np(hf_model)

    def arr(x):
        return jnp.asarray(x, dtype)

    embed = sd["model.embed_tokens.weight"]
    params: dict[str, jnp.ndarray] = {
        "embed/tok": arr(embed),
        "final_ln/scale": arr(sd["model.norm.weight"]),
        "lm_head/w": arr(sd["lm_head.weight"].T
                         if "lm_head.weight" in sd else embed.T),
    }
    per_layer: list[dict[str, np.ndarray]] = []
    for i in range(cfg.n_layers):
        hf = f"model.layers.{i}"
        per_layer.append({
            "ln1/scale": sd[f"{hf}.input_layernorm.weight"],
            "attn/wq": sd[f"{hf}.self_attn.q_proj.weight"].T,
            "attn/wk": sd[f"{hf}.self_attn.k_proj.weight"].T,
            "attn/wv": sd[f"{hf}.self_attn.v_proj.weight"].T,
            "attn/wo": sd[f"{hf}.self_attn.o_proj.weight"].T,
            "ln2/scale": sd[f"{hf}.post_attention_layernorm.weight"],
            "mlp/w1": sd[f"{hf}.mlp.gate_proj.weight"].T,
            "mlp/w3": sd[f"{hf}.mlp.up_proj.weight"].T,
            "mlp/w2": sd[f"{hf}.mlp.down_proj.weight"].T,
        })
    if scan_layers:
        for suffix in per_layer[0]:
            params[f"blocks/{suffix}"] = arr(
                np.stack([layer[suffix] for layer in per_layer]))
    else:
        for i, layer in enumerate(per_layer):
            for suffix, value in layer.items():
                params[f"layer{i}/{suffix}"] = arr(value)
    _check_shapes(model, params)
    return model, params
