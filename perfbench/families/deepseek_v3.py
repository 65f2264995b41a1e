"""DeepSeek-V3 (DeepSeek-AI, ``model_type`` ``deepseek_v3``) as the benchmark
knows it: a published ``config.json`` (``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_scaling``
of type ``yarn``, ``first_k_dense_replace``, ``moe_intermediate_size``,
``n_routed_experts``, ``num_experts_per_tok``, ``n_group``, ``topk_group``,
``n_shared_experts``, ``scoring_func``, ``norm_topk_prob``,
``routed_scaling_factor``, ...) as the program's model, its weights, its
reference (``reference/deepseek_v3.py``) with the names it takes, its
tolerances, its counts and its tiny copy.

The configuration is ONE CHIP'S SHARE of an expert-parallel stage
(``expert_parallel``: ``ranks``, ``rank``, ``first_expert``, ``held``), as
K-EXAONE's and Kimi Linear's: ``n_routed_experts`` is what this chip
holds, ``num_router_experts`` the router's published width.  The program's
model is a PROLOGUE of ``first_k_dense_replace`` layers with a dense SwiGLU
of ``intermediate_size`` and then a one-layer PATTERN whose feed-forward is
the held share of a dropless top-k mixture of SwiGLU experts (the router
over all ``num_router_experts``, a sigmoid score, a stored selection bias,
the selection limited to ``topk_group`` of ``n_group`` groups, gates over
the chosen scores' sum times ``routed_scaling_factor``) with a shared
expert beside it.  EVERY layer's mixer is the program's ``latent`` mixer
with a low-rank query (``q_latent``), rotary on the shared parts
(``latent_rope``) and YaRN (``rope_scaling``); norms on the branches'
inputs.

Counts, convention (PaLM appendix B): a matmul parameter costs 2 FLOPs per
token forward and 4 backward; only the ACTIVE and HELD experts' parameters
count (a token meets 8 of 256, of which 16 / 256 x 8 = 0.5 lies here on
average); the shared expert, the router and the head count, the
embedding's lookup does not; a layer's scores and values cost 12 * (heads
x (128 + 64 / 2)) * keys per token forward + backward; norms, rotations
and activations are not counted.  No training cell runs this family.
"""

from __future__ import annotations

import copy
import functools
import math

from ..program import program_seed

# Two comparisons decide the forward's part of ``correct``, Kimi Linear's
# first two (``families/kimi_linear.py``) and for its reason: a router's near-tie flips (a token's 8th and 9th best of
# 128 open scores + bias, or its 4th and 5th best of 8 groups, lie close and
# the program's bfloat16 stream carries noise), so the reference runs under
# the PROGRAM's selection and the selection itself is held to the
# reference's scores.  Only an expert HELD here moves the result.  All
# readings on the v5e at 1 x 4,096, the cell's own check, weights as
# make_weights draws them, in the harness's flow (the logits from
# ``Transformer.apply`` compiled alone, the selection from the same pass
# compiled inside the reference's program); the controls put the fault into
# the REFERENCE (``reference.forward``'s ``faults``, or its weights), which
# the comparison cannot tell from the same fault in the program
# (scripts/deepseek_controls.py; my chip runs, PR 54; PERF.md section 6).
#
# (a) ``SELECTION_MARGIN``: every expert the program chose and the
#     reference would not must lie within this much of the reference's cut
#     among the groups the program's experts lie in (its 8th best score +
#     bias there), in units of the score, and its GROUP within twice this
#     much of the reference's last kept group (a group's score is the sum
#     of two).
# (b) ``logits_rms`` / ``logits_max``: the program's logits against the
#     reference run WITH the program's selection and the same share of the
#     experts.
#
# Sound, seven readings over six seeds (chiprun_out/pr54_controls.jsonl and
# the cell's first run): RMS 0.00646 every time, largest 0.0366 .. 0.0414,
# the farthest chosen expert 0.0026 .. 0.0032 under the cut, the farthest
# group 0.0028 .. 0.0045 under the last kept group; 136 to 275 of a
# layer's 4,096 tokens took another expert than the reference would.
# Controls, two seeds each (RMS; largest; farthest expert; farthest group):
#   every matrix through an 8-bit float (e4m3), the nearest precision below
#   the configuration's bfloat16: 0.0513, 0.0514; 0.573, 0.566 (the
#   nearest control; its selection is made on the same 8-bit weights, so
#   its margins stay sound: 0.0026, 0.0040; 0.0049, 0.0037).
#   YaRN's m^2 left out of the scale (0.0722 for 0.1352): 0.1439, 0.1437;
#   1.04, 1.14; 0.106, 0.115; 0.098, 0.096.
#   the rotary left off the rows' shared key part: 0.2246, 0.2240; 1.65,
#   1.82; 0.175, 0.207; 0.181, 0.181.
#   the norm inside the query pair left out: 0.2894, 0.2891; 1.69, 1.70;
#   0.200, 0.251; 0.175, 0.182.
#   the group limit left out (plain top-8 of 256): the logits are the
#   program's selection's either way (0.00646; 0.039, 0.041); the farthest
#   expert 0.1055, 0.0851 under the reference's cut: the margin holds it.
# The RMS limit stands 2.8 times above the sound runs' and 2.85 times under
# the nearest control's smallest; the largest difference's 3.6 times above
# the sound runs' largest and 3.8 times under; the selection margin 3.75
# times above the sound runs' farthest expert and 7.1 times under the
# nearest control's (the group limit left out), and in its double units 5.3
# times above the farthest group and 4.0 times under the nearest control's
# (the gain left out).  Through the harness's own comparison the sound
# runs read ``ok`` true and all five controls ``ok`` false, on both seeds.
SELECTION_MARGIN = 0.012
LOGIT_TOLERANCE = 0.018
MAX_TOLERANCE = 0.15
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position (the served tokens come from a THIRD compilation,
# the decode round against the cache: the row restored from the tree at
# its rotated positions, the turn's extension by key block, sixteen rounds
# through the kernel at 128 heads).  Kimi Linear's limit and for its
# reasons (a wrong token lies where a random token lies, 3.4 to 4
# deviations under the reference's best).  Sound, eight replays of 16
# served tokens over eight seeds (my chip runs, PR 54): 0.0 six times,
# 0.0055, 0.0093.
NEAR_TIE_TOLERANCE = 0.7
# No training cell runs this family: what a float32 CPU comparison at the
# tiny size holds (tests/test_deepseek_v3.py); the chip has not read them.
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4
TOLERANCES = {"logits_rms": LOGIT_TOLERANCE, "logits_max": MAX_TOLERANCE,
              "near_tie": NEAR_TIE_TOLERANCE, "gradient": GRADIENT_TOLERANCE,
              "loss": LOSS_TOLERANCE}

# Standard deviation of the random embedding (the head is a matrix of its
# own), SmallThinker's, K-EXAONE's and Kimi Linear's and for their reason:
# what a position has of its own must outweigh what the mixers add to every
# position alike, and the routers' logits then stay near normal(0, 1).
EMBED_STD = 1.0
# Standard deviation of the stored selection bias: K-EXAONE's (PR 40: a
# random correction un-evens the loads it exists to even).
EXPERT_BIAS_STD = 0.005
# The attention's scores and latent, drawn so that the layer is no
# vanishing average (Kimi Linear's lesson, PR 47: at unit scales the softmax
# over 4,096 keys is flat and neither a missing norm nor a wrong scale
# reaches the logits).  ``wq_b`` is drawn at QUERY_STD / sqrt(rank) behind
# its norm and ``wkv_a`` at LATENT_RMS / sqrt(d): the latent leaves its
# projection at an RMS of 2 (which the norm brings to 1), the rotated key
# part keeps 2, and a score's standard deviation is 0.95 x sqrt(128 + 64 x
# 4) x 0.1352 = 2.5, Kimi Linear's: a query at 4,096 keys weighs about
# eight of them.  ``wq_a`` at 2 / sqrt(d), so that leaving its norm out
# doubles every score.
QUERY_STD = 0.95
LATENT_RMS = 2.0


# --------------------------------------------------------------- the model
def held_experts(config: dict) -> tuple[int, int]:
    """(first, count) of the experts this chip holds."""
    share = config["expert_parallel"]
    return share["first_expert"], share["held"]


def rope_scaling(config: dict):
    """The published ``rope_scaling`` as the program's."""
    from parameter_server_distributed_tpu.models.transformer import (
        RopeScaling)

    yarn = config["rope_scaling"]
    return RopeScaling(
        factor=float(yarn["factor"]),
        original_max=int(yarn["original_max_position_embeddings"]),
        beta_fast=float(yarn["beta_fast"]), beta_slow=float(yarn["beta_slow"]),
        mscale=float(yarn["mscale"]),
        mscale_all_dim=float(yarn["mscale_all_dim"]))


def transformer_config(config: dict, **overrides):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, TransformerConfig)

    assumed = config["assumed"]
    first, held = held_experts(config)
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["topk_method"] != "noaux_tc"
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or config["rope_scaling"]["type"] != "yarn"
            or not config["q_lora_rank"] or config["attention_bias"]
            or config["tie_word_embeddings"]
            or config["v_head_dim"] != config["qk_nope_head_dim"]
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or held != config["n_routed_experts"]):
        raise ValueError(
            "the program's DeepSeek-V3 scores by a sigmoid, norms the gates "
            "over the chosen scores, selects under a group limit with a "
            "stored bias, routes every layer after its leading dense ones, "
            "has latent attention with a low-rank query, YaRN rotary on the "
            "shared parts and values as wide as a head's own key part, a "
            "head of its own, and holds n_routed_experts = "
            "expert_parallel.held experts")
    dense = config["first_k_dense_replace"]
    fields = dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        kv_latent=config["kv_lora_rank"],
        qk_shared=config["qk_rope_head_dim"],
        q_latent=config["q_lora_rank"], latent_rope=True,
        rope_theta=float(config["rope_theta"]),
        rope_scaling=rope_scaling(config),
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        moe_experts=config["num_router_experts"], moe_held=(first, held),
        moe_top_k=config["num_experts_per_tok"],
        moe_groups=config["n_group"], moe_groups_kept=config["topk_group"],
        moe_shared_experts=config["n_shared_experts"],
        moe_router_input="ffn", moe_score="sigmoid", moe_expert_bias=True,
        moe_route_scale=float(config["routed_scaling_factor"]),
        prologue=(LayerSpec(mixer="latent", ffn="mlp"),) * dense,
        pattern=(LayerSpec(mixer="latent", ffn="experts"),),
        max_seq=config["max_position_embeddings"],
        dtype=getattr(jnp, assumed["dtype"]), norm="rms",
        norm_eps=float(config["rms_norm_eps"]), bias=False, mlp_act="swiglu",
        remat=bool(assumed["remat"]), remat_policy=assumed["remat_policy"],
        scan_layers=bool(assumed["scan_layers"]),
        loss_chunk=int(assumed["loss_chunk"]))
    fields.update(overrides)
    return TransformerConfig(**fields)


def model(config: dict, **overrides):
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)

    return Transformer(transformer_config(config, **overrides))


# make_weights builds the store as this many programs, side by side
_BUILD_GROUPS = 4


@functools.lru_cache(maxsize=2)
def _weight_builders(config) -> tuple:
    """The jitted programs that draw the store of ``Transformer(config)``
    from a key, each a share of its KINDS of leaf (the leaves of one suffix
    and shape over the layers that hold them, drawn by ONE loop over their
    keys: ``lax.map``, a stack of experts one expert at a time, so that no
    float32 copy of a whole stack is ever held), Kimi Linear's way and for
    its reasons (PR 47).  Kept between calls: a run makes the store twice
    (the server's, then the check's)."""
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)

    shapes = Transformer(config).param_shapes()
    dtype, layers = config.dtype, config.n_layers

    def drawn(key, name, shape):
        """one leaf of kind ``name`` (its suffix) from its own key"""
        def normal(std):
            return (std * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)

        if name.endswith("/scale"):
            return jnp.ones(shape, dtype)
        if name == "embed/tok":
            return normal(EMBED_STD)
        if name.endswith("moe/router/bias"):
            return normal(EXPERT_BIAS_STD)
        std = 1.0 / math.sqrt(shape[-2])
        if name in ("attn/wkv_a", "attn/wq_a"):
            std *= LATENT_RMS
        if name == "attn/wq_b":
            std *= QUERY_STD
        if name.endswith(("attn/wo", "mlp/w2", "moe/w2", "moe/shared/w2")):
            std /= math.sqrt(2.0 * layers)
        return normal(std)

    # the leaves of one suffix and shape, over the layers that hold them
    kinds: dict = {}
    for name in sorted(shapes):
        suffix = name.split("/", 1)[1] if name.startswith("layer") else name
        kinds.setdefault((suffix, shapes[name]), []).append(name)
    numbered = [(i, suffix, shape, names) for i, ((suffix, shape), names)
                in enumerate(sorted(kinds.items()))]

    def builder(share):
        @jax.jit
        def build(key):
            out = {}
            for i, suffix, shape, names in share:
                # [C, in, out]: one [in, out] matrix at a time
                inner = shape[-2:] if len(shape) == 3 else shape
                count = len(names) * (shape[0] if len(shape) == 3 else 1)
                keys = jax.random.split(jax.random.fold_in(key, i), count)
                stack = jax.lax.map(lambda k: drawn(k, suffix, inner), keys)
                stack = stack.reshape(len(names), *shape)
                for j, name in enumerate(names):
                    out[name] = stack[j]
            return out

        return build

    return tuple(builder(numbered[g::_BUILD_GROUPS])
                 for g in range(_BUILD_GROUPS))


def make_weights(model, seed: int) -> dict:
    """The program's parameter store, made on the device from the seed in
    the model's own dtype: matrices normal(0, 1 / sqrt(fan-in)), the
    attention's and the feed-forwards' output projections scaled by 1 /
    sqrt(2 L), the embedding at :data:`EMBED_STD`, the selection bias at
    :data:`EXPERT_BIAS_STD`, the query pair and ``wkv_a`` as
    :data:`QUERY_STD` and :data:`LATENT_RMS` say, norm gains one.
    :data:`_BUILD_GROUPS` jitted calls (:func:`_weight_builders`), each
    from a thread of its own so that the compiler builds them side by side
    on a cold start.  The bits come from the chip's own generator
    (``rbg``)."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    builders = _weight_builders(model.config)
    key = jax.random.key(program_seed(seed), impl="rbg")
    if isinstance(key, jax.core.Tracer):
        # (asked for its shapes only, under a trace: no threads there)
        parts = [build(key) for build in builders]
    else:
        with ThreadPoolExecutor(len(builders)) as pool:
            parts = list(pool.map(lambda build: build(key), builders))
    return {name: leaf for part in parts for name, leaf in part.items()}


# ----------------------------------------------------------- the reference
_NORMS = {"norm_attn": "ln1/scale", "norm_ffn": "ln2/scale"}
_ATTENTION = {"wq_a": "attn/wq_a", "q_gain": "attn/q_norm/scale",
              "wq_b": "attn/wq_b", "wkv_a": "attn/wkv_a",
              "kv_gain": "attn/kv_norm/scale", "wkv_b": "attn/wkv_b",
              "wo": "attn/wo"}
_DENSE = {"w1": "mlp/w1", "w3": "mlp/w3", "w2": "mlp/w2"}
_EXPERTS = {"router": "moe/router/w", "bias": "moe/router/bias",
            "w1": "moe/w1", "w3": "moe/w3", "w2": "moe/w2",
            "shared_w1": "moe/shared/w1", "shared_w3": "moe/shared/w3",
            "shared_w2": "moe/shared/w2"}


def reference_weights(config: dict, params: dict) -> dict:
    """The program's store (or a gradient in its shape) in the reference's
    names.  The SAME buffers, not a float32 copy (bfloat16 values are exact
    in float32, and the reference widens one expert of one layer at a
    time)."""
    def layer(i):
        return {ours: params[f"layer{i}/{theirs}"]
                for names in (_NORMS, _ATTENTION, _DENSE, _EXPERTS)
                for ours, theirs in names.items()
                if f"layer{i}/{theirs}" in params}

    return {"embed": params["embed/tok"], "head": params["lm_head/w"],
            "final_norm": params["final_ln/scale"],
            "layers": [layer(i) for i in range(config["num_hidden_layers"])]}


def program_weights(config: dict, weights: dict) -> dict:
    """:func:`reference_weights` undone: the program's names."""
    params = {"embed/tok": weights["embed"], "lm_head/w": weights["head"],
              "final_ln/scale": weights["final_norm"]}
    for i, layer in enumerate(weights["layers"]):
        names = dict(_NORMS, **_ATTENTION,
                     **(_EXPERTS if "router" in layer else _DENSE))
        params.update({f"layer{i}/{names[ours]}": value
                       for ours, value in layer.items()})
    return params


def _reference_arguments(config: dict) -> dict:
    yarn = config["rope_scaling"]
    return dict(n_head=config["num_attention_heads"],
                head_dim=config["qk_nope_head_dim"],
                rope_dim=config["qk_rope_head_dim"],
                latent=config["kv_lora_rank"],
                eps=float(config["rms_norm_eps"]),
                top_k=config["num_experts_per_tok"],
                scale=float(config["routed_scaling_factor"]),
                groups=config["n_group"], groups_kept=config["topk_group"],
                rope=dict(theta=float(config["rope_theta"]),
                          factor=float(yarn["factor"]),
                          original_max=yarn[
                              "original_max_position_embeddings"],
                          beta_fast=yarn["beta_fast"],
                          beta_slow=yarn["beta_slow"],
                          mscale=yarn["mscale"],
                          mscale_all_dim=yarn["mscale_all_dim"]),
                held=held_experts(config))


def reference_readings(config: dict, weights: dict, tokens, faults=None):
    """(the reference's logits, of the same share of the experts, under the
    PROGRAM's selection: its own forward pass over the same tokens, in its
    own precision; [expert layers, B, 3]: the tokens whose program-chosen
    experts are not the reference's own, how far under the reference's cut
    the farthest of them lies, and how far under its last kept group the
    farthest of their groups).  ``reference_forward`` judges by them; a
    script that reads tolerances prints them."""
    from ..reference import deepseek_v3 as reference

    program = model(config, remat=False)
    chosen = program.expert_selections(program_weights(config, weights),
                                       tokens)
    held = {}
    logits = reference.forward(
        weights, tokens, selection=chosen, faults=faults,
        report=lambda compared: held.update(compared=compared),
        **_reference_arguments(config))
    return logits, held["compared"]


def reference_forward(config: dict, weights: dict, tokens, faults=None):
    """The reference's logits (:func:`reference_readings`), and not a
    number where the program chose an expert that lies farther than
    ``SELECTION_MARGIN`` under the reference's own cut, or one of a group
    farther than twice that under the reference's last kept group (a
    group's score is the sum of two).  No host callback: the program that
    holds the reference is then kept by the compile cache like any other."""
    import jax.numpy as jnp

    logits, compared = reference_readings(config, weights, tokens, faults)
    sound = ((jnp.max(compared[..., 1]) <= SELECTION_MARGIN)
             & (jnp.max(compared[..., 2]) <= 2 * SELECTION_MARGIN))
    return jnp.where(sound, logits, jnp.nan)


def reference_loss(config: dict, weights: dict, tokens):
    """(loss, logits), the reference under its own selection."""
    from ..reference import deepseek_v3 as reference

    return reference.loss(weights, tokens, **_reference_arguments(config))


# -------------------------------------------------------------- the counts
def _attention_params(config: dict) -> int:
    """A layer's attention, 187,107,328 at the published widths: the query
    pair and its norm, kv_a, the latent's norm, kv_b, o."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    size, shared = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, latent = config["q_lora_rank"], config["kv_lora_rank"]
    return (d * rank + rank + rank * heads * (size + shared)
            + d * (latent + shared) + latent + latent * 2 * heads * size
            + heads * size * d)


def _expert_params(config: dict) -> int:
    """One expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, layer: int) -> int:
    """Parameters of layer ``layer`` AS HELD HERE: its attention, two norm
    gains, and the dense SwiGLU or the router (every output), its bias, the
    shared expert and the held experts."""
    d = config["hidden_size"]
    if layer < config["first_k_dense_replace"]:
        ffn = 3 * d * config["intermediate_size"]
    else:
        routed = config["num_router_experts"]
        ffn = (d * routed + routed
               + (config["n_shared_experts"] + config["n_routed_experts"])
               * _expert_params(config))
    return _attention_params(config) + 2 * d + ffn


def param_count(config: dict) -> int:
    """Parameters this chip holds: its layers, its rows of the embedding
    and of the head, the final norm."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + 2 * config["vocab_size"] * d + d)


def active_matmul_params(config: dict) -> float:
    """Weights one token meets in a matmul HERE: every layer's attention,
    the dense layers' SwiGLU, an expert layer's router, its shared expert
    and the held share of its active experts (top-k x held / routed: half
    an expert on average), and the head's rows held here."""
    d = config["hidden_size"]
    total = float(config["vocab_size"] * d)
    here = (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["num_router_experts"])
    for i in range(config["num_hidden_layers"]):
        total += _attention_params(config)
        if i < config["first_k_dense_replace"]:
            total += 3 * d * config["intermediate_size"]
        else:
            total += (d * config["num_router_experts"]
                      + (config["n_shared_experts"] + here)
                      * _expert_params(config))
    return total


def train_flops_per_token(config: dict, seq_len: int) -> float:
    heads, size = config["num_attention_heads"], config["qk_nope_head_dim"]
    return (6.0 * active_matmul_params(config)
            + config["num_hidden_layers"] * 12.0 * heads
            * (size + config["qk_rope_head_dim"] / 2) * seq_len)


def _latent_row_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """One cached position of one layer: 512 of normed latent and 64 of
    the rotated shared key part, 1,152 B."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * dtype_bytes


def slot_bytes(config: dict, max_len: int, dtype_bytes: int = 2) -> dict:
    """Bytes of one cache slot by kind of part: every layer's rows by
    position AS STORED (1,280 B a position and layer: a row is stored in
    whole registers of 128 lanes, 576 -> 640)."""
    lanes = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) // 128
              ) * 128
    return {"full": 0, "window": 0, "state": 0,
            "latent": config["num_hidden_layers"] * max_len * lanes
            * dtype_bytes}


def latent_attn_bytes(config: dict, positions_read: float) -> float:
    """The least a layer's round has to read: the row of every LIVE
    position once (1,152 B; the absorbed form needs it once for all 128
    heads), not the part's size."""
    return _latent_row_bytes(config) * positions_read


def latent_attn_flops(config: dict, positions_read: float) -> float:
    """The products the decode kernel has to make for a live position: the
    128 heads' scores against its 576 lanes and their weighted sum of its
    512 of latent, 2 x 128 x (576 + 512) = 278,528 FLOP (the kernel runs
    both over the 640 lanes as stored: 327,680)."""
    heads = config["num_attention_heads"]
    row = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return 2.0 * heads * (row + config["kv_lora_rank"]) * positions_read


def moe_experts_bytes(config: dict, experts_touched: float,
                      assignments: float, dtype_bytes: int = 2) -> float:
    """Bytes the ``moe/experts`` block has to move for ``assignments``
    (token, choice) rows COMPUTED HERE over ``experts_touched`` (layer,
    held expert) pairs with at least one row, as K-EXAONE's: each touched
    expert's three matrices once (88,080,384 B); each row read for the gate
    and for the up projection, both results written and read back for the
    product, the product written and read by the down projection, and its
    float32 result written."""
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    weights = experts_touched * _expert_params(config) * dtype_bytes
    rows = assignments * (2 * d * dtype_bytes + 4 * width * dtype_bytes
                          + 2 * width * dtype_bytes + d * 4)
    return weights + rows


def vocab_size(config: dict) -> int:
    return config["vocab_size"]


def max_context(config: dict) -> int:
    return config["max_position_embeddings"]


# ------------------------------------------------------------ the tiny copy
def tiny(config: dict) -> dict:
    """A copy at a size a CPU runs in seconds (``run.py --rehearse``): the
    dense layer and two expert layers, a quarter of 16 experts held (half
    of one of 4 groups of which 2 are kept: a token reaches this rank
    through group 0 alone), YaRN with its ramp inside the tiny size's 4
    pairs."""
    config = copy.deepcopy(config)
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, qk_nope_head_dim=16, v_head_dim=16,
                  qk_rope_head_dim=8, kv_lora_rank=32, q_lora_rank=24,
                  intermediate_size=96, moe_intermediate_size=32,
                  num_router_experts=16, n_routed_experts=2,
                  num_experts_per_tok=3, n_group=4, topk_group=2,
                  num_hidden_layers=3, max_position_embeddings=128,
                  vocab_size=512)
    config["rope_scaling"].update(factor=4.0,
                                  original_max_position_embeddings=32)
    config["expert_parallel"].update(ranks=8, rank=1, first_expert=2, held=2)
    config["assumed"].update(dtype="float32", loss_chunk=32)
    return config
