"""K-EXAONE (LG AI Research, ``model_type`` ``exaone_moe``) as the benchmark
knows it: a published ``config.json`` (``layer_types``, ``sliding_window``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``num_experts_per_tok``, ``num_shared_experts``, ``scoring_func``,
``norm_topk_prob``, ``routed_scaling_factor``, ``rope_parameters``, ...) as
the program's model, its weights, its reference (``reference/k_exaone.py``)
with the names it takes, its tolerances, its counts and its tiny copy.

The configuration is ONE CHIP'S SHARE of an expert-parallel stage
(``expert_parallel``: ``ranks``, ``rank``, ``first_expert``, ``held``):
``num_experts`` is what this chip holds, ``num_router_experts`` the router's
published width.  The program's model is a PROLOGUE of
``first_k_dense_replace`` layers with a dense SwiGLU of ``intermediate_size``
and then a layer PATTERN, one period of the ``layer_types`` that follow,
every such layer's feed-forward the held share of a dropless top-k mixture
of SwiGLU experts of ``moe_intermediate_size`` (the router over all
``num_router_experts``, a sigmoid score, a stored selection bias, gates over
the chosen scores' sum times ``routed_scaling_factor``) with a shared expert
beside it; ``sliding_attention`` a window of ``sliding_window`` with rotary,
``full_attention`` causal with none; q and k normed per head; the norms on
the branches' outputs (``assumed.norm_placement``).

Counts, convention (PaLM appendix B): a matmul parameter costs 2 FLOPs per
token forward and 4 backward; only the ACTIVE and HELD experts' parameters
count (a token meets 8 of 128, of which 16 / 128 x 8 = 1 lies here on
average); the shared expert, the router and the head count, the embedding's
lookup does not; attention scores and values cost 12 * head width * keys
per token forward + backward (a window layer's keys its window); norms,
rotary and activations are not counted.  No training cell runs this family.
"""

from __future__ import annotations

import copy
import math

from ..program import program_seed

SLIDING, FULL = "sliding_attention", "full_attention"

# Two comparisons decide ``correct``, as in ``families/lfm2.py`` and for its
# reason: a router's near-tie flips.  A token's 8th and 9th best of 128
# scores + bias lie close, the program's bfloat16 stream carries noise, so
# 2.4% (first expert layer) to 6.1% (last) of a layer's tokens choose another
# expert than the float32 reference would; and two executables of one
# bfloat16 pass do not agree on every such tie.  So the reference runs under
# the PROGRAM's selection, and the selection itself is held to the
# reference's scores.  Only an expert HELD here moves the result (an eighth
# of the flips on average, at a gate of 2.5 / 8 beside a shared expert at
# one), which is why nothing here needs LFM2's third of an output scale.
# All readings on the v5e at 1 x 4,096, the cell's own check, weights as
# make_weights draws them (my chip runs, PR 40; PERF.md section 6).  "As the
# check runs": the harness's, seven runs of the cell (logits from
# ``Transformer.apply`` compiled alone, the selection from the same pass
# compiled inside the reference's program); the controls come from a
# scratch script's flow, the fault in the PROGRAM alone and its selection
# from an executable of its own (sound by that flow, three seeds: RMS
# 0.00782, largest 0.043 .. 0.046, farthest 0.0039 .. 0.0059: the
# harness's own readings).
#
# (a) ``SELECTION_MARGIN``: every expert the program chose and the reference
#     would not must lie within this much of the reference's cut (its 8th
#     best score + bias), in units of the score.  Sound, ten readings: the
#     farthest such expert 0.0038 .. 0.0060 under the cut.  The bias left
#     out of the selection (the router takes the top 8 of the bare scores),
#     two seeds: 24-31% of a layer's tokens differ, the farthest 0.0204 and
#     0.0263.  Every matrix through an 8-bit float (e4m3), the nearest
#     precision below the configuration's bfloat16: 43-54% differ, the
#     farthest 0.057 and 0.061.  A share that claims the experts 16-31 on
#     the weights of 0-15: 0.236 and 0.256.  The limit stands 2.2 times
#     above the sound runs' farthest, 1.6 times under the missing bias's and
#     4.4 times under the 8-bit store's (the bias is a quarter of LFM2's,
#     EXPERT_BIAS_STD, and what it moves is a quarter).
# (b) ``logits_rms`` / ``logits_max``: the program's logits against the
#     reference run WITH the program's selection and the same share of the
#     experts.  Sound, ten readings: RMS 0.00780 .. 0.00782, largest 0.0432
#     .. 0.0472.  Controls, two seeds each (RMS; largest): rotary put on the
#     full layers 0.0708, 0.0717; 0.41, 0.45 (the nearest).  The 8-bit store
#     0.0815, 0.0816; 0.46, 0.49.  The wrong share 0.246, 0.247; 1.58, 1.62.
#     A sliding layer that sees every earlier position 0.265, 0.265; 1.61,
#     1.67.  The norms on the branches' inputs 0.559, 0.560; 3.3, 3.4.  The
#     shared expert left out 0.620, 0.620; 3.6, 3.9.  The missing bias does
#     not move them (0.00782; 0.046): the reference follows the selection,
#     and (a) is what sees it.  Against the reference under its OWN
#     selection the sound program reads RMS 0.0215 .. 0.0216, largest 0.66 ..
#     0.76: a tie's fall, not the arithmetic, and larger than the 8-bit
#     store's largest, which is why the comparison follows the program's
#     selection.  The RMS limit stands 3.1 times above the sound runs'
#     largest and 2.9 times under the nearest control's smallest; the
#     largest difference's 3.0 times above and 2.9 times under.
SELECTION_MARGIN = 0.013
LOGIT_TOLERANCE = 0.024
MAX_TOLERANCE = 0.14
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position.  The served tokens come from a THIRD compilation
# (the decode round against the cache), so some of the 16 replayed positions
# chose another held expert than the selection the reference follows.  Two
# readings (my chip runs, PR 40):
#   sound, nine replays of 16 served tokens: 0.0 six times, 0.002, 0.027,
#     0.119 (with the weights as first drawn, attention's gain at the full
#     and the bias at 0.02, eight replays read 0.0 .. 0.110);
#   a token that has nothing to do with the reference's distribution (a
#     wrong lane, a wrong token) lies where a random token lies: the
#     reference's best logit stands 4.00 .. 4.01 deviations above the mean
#     of a position's logits (every reading), so such a token reads about 4.
# The limit stands 5.9 times above the largest sound replay and 5.7 times
# under the wrong token's.  As in the other families
# it holds the path a token takes through the decode program (the slot's
# token, the embedding, the rings' and the full layers' attention, the
# router, the held experts, the shared expert, the head), NOT the cache's
# indexing: tests/test_k_exaone.py holds the rings (wrapped three times at
# the published window) and the rows, exactly, in float32.
NEAR_TIE_TOLERANCE = 0.7
# No training cell runs this family: what a float32 CPU comparison at the
# tiny size holds (tests/test_k_exaone.py); the chip has not read them.
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4
TOLERANCES = {"logits_rms": LOGIT_TOLERANCE, "logits_max": MAX_TOLERANCE,
              "near_tie": NEAR_TIE_TOLERANCE, "gradient": GRADIENT_TOLERANCE,
              "loss": LOSS_TOLERANCE}

# Standard deviation of the random embedding (the head is a matrix of its
# own), SmallThinker's and for its reason: what a position has of its own
# must outweigh what attention's averages add to every position alike.
EMBED_STD = 1.0
# A branch's output is RMS-normed before it joins the stream, so its
# weights' scale is normed away and the depth's 1 / sqrt(2 L) (which the
# other families put into the output projections) is the GAIN of a layer's
# two output norms here: the 2 L branches together then add as much to the
# stream as the embedding holds, and a router's logits stay normal(0, 1) to
# normal(0, 1.4) from the first expert layer to the last (at gains of one
# the last router's would be normal(0, 4): every leading score saturated,
# and the selection the bias's alone).


def branch_gain(layers: int) -> float:
    return 1.0 / math.sqrt(2.0 * layers)


# The attention branches' output norms are drawn at this share of
# :func:`branch_gain`, and the stored bias at a quarter of LFM2's, because
# this chip holds 16 of 128 experts and the grouped matmul takes 58 us a
# TOUCHED expert (timed alone on the chip over 256 rows and [16, 6144,
# 2048]: 931 us for 16 groups, 701 for 12, 470 for 8, 241 for 4, 195 for
# none, whatever the rows): a round's time follows how evenly the routers
# spread their rows, and under random weights two things made that another
# for every seed.  (1) Attention is an average over a sequence's positions,
# a sixty-fourth of one value's size over 4,096 of them, and a norm on the
# branch's OUTPUT blows that remnant up to unit size again: a vector every
# token of a sequence shares.  The routers turn it into an offset an
# expert: at the full gain an expert's share of a layer's selections spread
# by 13% (first expert layer) to 58% (last) of the mean within one sequence
# with no bias at all, and no stored bias can even it (balanced on one
# sequence to 1% by the deepseek-style update, it read 15-70% on the next:
# that code went).  (2) A bias drawn at normal(0, 0.02) spreads the loads by
# 27% of the mean by itself, the same for every lane.  The 16 held experts'
# share of a layer's rows then read 7.9% to 16.6% for an even 12.5%, the
# held experts touched a round 77 .. 80% for the 87% of even loads, the
# gap's median 20.04 .. 20.79 ms over six seeds and its 95th percentile
# spread by 1.76%, where half the metric's bound is 1.75%.  A trained
# model's attention is no vanishing average and its loads are evened by the
# stored bias.  At a third and a quarter: 85.4% of the held experts touched
# a round, the gap's median 20.91 .. 21.29 ms, the 95th percentile's spread
# 0.89% (six runs each; my chip runs, PR 40).  Attention then carries a
# tenth of the branches' energy (SmallThinker's carries 6%): the readings
# beside TOLERANCES are at these weights.
ATTENTION_SHARE = 1.0 / 3.0


# Standard deviation of the stored selection bias: a quarter of LFM2's
# (``assumed.expert_bias`` in the configuration's file says why).
EXPERT_BIAS_STD = 0.005


# --------------------------------------------------------------- the model
def layer_period(config: dict) -> list[str]:
    """The shortest period of ``layer_types`` that the kept layers after
    the dense ones repeat."""
    kinds = list(config["layer_types"])
    layers = config["num_hidden_layers"]
    if not (len(kinds) == len(config["mlp_layer_types"])
            == len(config["sliding_windows"]) == layers):
        raise ValueError(f"layer_types, mlp_layer_types and sliding_windows "
                         f"hold an entry for each of {layers} layers")
    kinds = kinds[config["first_k_dense_replace"]:]
    for period in range(1, len(kinds) + 1):
        if all(kind == kinds[i % period] for i, kind in enumerate(kinds)):
            return kinds[:period]
    raise ValueError("no layer follows the dense ones")


def held_experts(config: dict) -> tuple[int, int]:
    """(first, count) of the experts this chip holds."""
    share = config["expert_parallel"]
    return share["first_expert"], share["held"]


def layer_windows(config: dict) -> list[int]:
    """Every layer's window: ``sliding_window``, or 0 for a full layer."""
    return [config["sliding_window"] if kind == SLIDING else 0
            for kind in config["layer_types"]]


def transformer_config(config: dict, **overrides):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, TransformerConfig)

    assumed = config["assumed"]
    rope = config["rope_parameters"]
    dense = config["first_k_dense_replace"]
    first, held = held_experts(config)
    sparse = ["dense"] * dense + ["sparse"] * (
        config["num_hidden_layers"] - dense)
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["hidden_act"] != "silu"
            or rope["rope_type"] != "default"
            or config["tie_word_embeddings"]
            or list(config["mlp_layer_types"]) != sparse
            or list(config["sliding_windows"]) != layer_windows(config)
            or held != config["num_experts"]):
        raise ValueError(
            "the program's K-EXAONE scores by a sigmoid, norms the gates "
            "over the chosen scores, selects without groups, has SwiGLU "
            "experts after its leading dense layers, plain rotary, a head "
            "of its own, one window size, and holds num_experts = "
            "expert_parallel.held experts")

    def spec(kind: str, ffn: str):
        if kind == SLIDING:
            return LayerSpec(window=config["sliding_window"], rope=True,
                             qk_norm=True, ffn=ffn)
        if kind == FULL:
            return LayerSpec(rope=False, qk_norm=True, ffn=ffn)
        raise ValueError(f"layer type {kind!r}: the program's K-EXAONE has "
                         f"{SLIDING} and {FULL}")

    fields = dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        moe_experts=config["num_router_experts"], moe_held=(first, held),
        moe_top_k=config["num_experts_per_tok"],
        moe_shared_experts=config["num_shared_experts"],
        moe_router_input="ffn", moe_score="sigmoid", moe_expert_bias=True,
        moe_route_scale=float(config["routed_scaling_factor"]),
        norm_placement=assumed["norm_placement"],
        prologue=tuple(spec(kind, "mlp")
                       for kind in config["layer_types"][:dense]),
        pattern=tuple(spec(kind, "experts") for kind in layer_period(config)),
        max_seq=config["max_position_embeddings"],
        dtype=getattr(jnp, assumed["dtype"]), pos_emb="rope",
        rope_theta=float(rope["rope_theta"]), norm="rms",
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


def make_weights(model, seed: int) -> dict:
    """The program's parameter store, made on the device in ONE jitted call
    from the seed, in the model's own dtype: matrices normal(0, 1 /
    sqrt(fan-in)), the embedding at :data:`EMBED_STD`, the selection bias
    at :data:`EXPERT_BIAS_STD`, a layer's two output norms' gains
    :func:`branch_gain` (attention's at :data:`ATTENTION_SHARE` of it), the
    other gains one.  (No output projection is scaled down by depth: a
    branch's output is normed before it joins the stream.)  A stack of
    experts is drawn one expert at a time (``lax.map``), so that no float32
    copy of a whole stack is ever held."""
    import jax
    import jax.numpy as jnp

    shapes = model.param_shapes()
    names = sorted(shapes)
    dtype = model.config.dtype
    gain = branch_gain(model.config.n_layers)

    def matrix(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    @jax.jit
    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            sub = jax.random.fold_in(key, i)
            if name.endswith("/ln1/scale"):
                out[name] = jnp.full(shape, gain * ATTENTION_SHARE, dtype)
            elif name.endswith("/ln2/scale"):
                out[name] = jnp.full(shape, gain, dtype)
            elif name.endswith("/scale"):
                out[name] = jnp.ones(shape, dtype)
            elif name == "embed/tok":
                out[name] = matrix(sub, shape, EMBED_STD)
            elif name.endswith("moe/router/bias"):
                out[name] = matrix(sub, shape, EXPERT_BIAS_STD)
            else:
                std = 1.0 / math.sqrt(shape[-2])
                if len(shape) == 3:
                    # [C, in, out]: one [in, out] matrix at a time
                    out[name] = jax.lax.map(
                        lambda k: matrix(k, shape[-2:], std),
                        jax.random.split(sub, shape[0]))
                else:
                    out[name] = matrix(sub, shape, std)
        return out

    return build(jax.random.key(program_seed(seed)))


# ----------------------------------------------------------- the reference
_NAMES = {"norm_attn": "ln1/scale", "norm_ffn": "ln2/scale",
          "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
          "q_gain": "attn/q_norm/scale", "k_gain": "attn/k_norm/scale"}
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
                for names in (_NAMES, _DENSE, _EXPERTS)
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
        names = dict(_NAMES, **(_EXPERTS if "router" in layer else _DENSE))
        params.update({f"layer{i}/{names[ours]}": value
                       for ours, value in layer.items()})
    return params


def _reference_arguments(config: dict) -> dict:
    kinds = config["layer_types"]
    return dict(n_head=config["num_attention_heads"],
                n_kv_head=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                eps=float(config["rms_norm_eps"]),
                theta=float(config["rope_parameters"]["rope_theta"]),
                windows=layer_windows(config),
                rotary=[kind == SLIDING for kind in kinds],
                top_k=config["num_experts_per_tok"],
                scale=float(config["routed_scaling_factor"]),
                held=held_experts(config),
                placement=config["assumed"]["norm_placement"])


def _say_selection(compared) -> None:
    """The ``selection_check`` line: per expert layer, summed over the
    sequences."""
    import numpy as np

    from ..harness import say

    compared = np.asarray(compared)                       # [layers, B, 2]
    say(detail="selection_check",
        tokens_with_another_expert=compared[..., 0].sum(1).tolist(),
        farthest_from_the_cut=compared[..., 1].max(1).tolist(),
        margin=SELECTION_MARGIN)


def reference_forward(config: dict, weights: dict, tokens):
    """The reference's logits, of the same share of the experts, under the
    PROGRAM's selection (its own forward pass over the same tokens, in its
    own precision), and not a number where the program chose an expert that
    lies farther than ``SELECTION_MARGIN`` under the reference's own cut
    (its readings go out as a ``selection_check`` line)."""
    import jax
    import jax.numpy as jnp

    from ..reference import k_exaone as reference

    chosen = model(config, remat=False).expert_selections(
        program_weights(config, weights), tokens)
    held = {}

    def report(compared):
        held["worst"] = jnp.max(compared[..., 1])
        jax.debug.callback(_say_selection, compared)

    logits = reference.forward(weights, tokens, selection=chosen,
                               report=report, **_reference_arguments(config))
    return jnp.where(held["worst"] <= SELECTION_MARGIN, logits, jnp.nan)


def reference_loss(config: dict, weights: dict, tokens):
    """(loss, logits), the reference under its own selection."""
    from ..reference import k_exaone as reference

    return reference.loss(weights, tokens, **_reference_arguments(config))


# -------------------------------------------------------------- the counts
def _attention_params(config: dict) -> int:
    """The four projections."""
    d, size = config["hidden_size"], config["head_dim"]
    return (2 * d * config["num_attention_heads"] * size
            + 2 * d * config["num_key_value_heads"] * size)


def _expert_params(config: dict) -> int:
    """One expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, layer: int) -> int:
    """Parameters of layer ``layer`` AS HELD HERE: attention with its two
    per-head gains, two norm gains, and the dense SwiGLU or the router
    (every output), its bias, the shared expert and the held experts."""
    d = config["hidden_size"]
    if layer < config["first_k_dense_replace"]:
        ffn = 3 * d * config["intermediate_size"]
    else:
        routed = config["num_router_experts"]
        ffn = (d * routed + routed
               + (config["num_shared_experts"] + config["num_experts"])
               * _expert_params(config))
    return (_attention_params(config) + 2 * config["head_dim"] + 2 * d + ffn)


def param_count(config: dict) -> int:
    """Parameters this chip holds: its layers, its rows of the embedding
    and of the head, the final norm."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + 2 * config["vocab_size"] * d + d)


def active_matmul_params(config: dict) -> float:
    """Weights one token meets in a matmul HERE: every attention's
    projections, the dense layers' SwiGLU, an expert layer's router, its
    shared expert and the held share of its active experts (top-k x held /
    routed: one expert on average), and the head's rows held here."""
    d = config["hidden_size"]
    total = float(config["vocab_size"] * d)
    here = (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_router_experts"])
    for i in range(config["num_hidden_layers"]):
        total += _attention_params(config)
        if i < config["first_k_dense_replace"]:
            total += 3 * d * config["intermediate_size"]
        else:
            total += (d * config["num_router_experts"]
                      + (config["num_shared_experts"] + here)
                      * _expert_params(config))
    return total


def train_flops_per_token(config: dict, seq_len: int) -> float:
    keys = sum(min(config["sliding_window"], seq_len) if kind == SLIDING
               else seq_len for kind in config["layer_types"])
    return (6.0 * active_matmul_params(config)
            + 12.0 * config["num_attention_heads"] * config["head_dim"]
            * keys)


def slot_bytes(config: dict, max_len: int, dtype_bytes: int = 2) -> dict:
    """Bytes of one cache slot by kind of part: K and V by position of the
    full layers; rings of ``sliding_window`` positions of the others."""
    position = (2 * config["num_key_value_heads"] * config["head_dim"]
                * dtype_bytes)
    kinds = config["layer_types"]
    return {"full": kinds.count(FULL) * max_len * position,
            "window": kinds.count(SLIDING) * position
            * min(config["sliding_window"], max_len),
            "state": 0}


def moe_experts_bytes(config: dict, experts_touched: float,
                      assignments: float, dtype_bytes: int = 2) -> float:
    """Bytes the ``moe/experts`` block has to move for ``assignments``
    (token, choice) rows COMPUTED HERE over ``experts_touched`` (layer,
    held expert) pairs with at least one row: each touched expert's three
    matrices once (75,497,472 B); each row read for the gate and for the up
    projection, both results written and read back for the product, the
    product written and read by the down projection, and its float32
    result written.  The rows routed to experts held elsewhere and the
    experts held elsewhere move nothing here and are not counted."""
    d, width = config["hidden_size"], config["moe_intermediate_size"]
    weights = experts_touched * _expert_params(config) * dtype_bytes
    rows = assignments * (2 * d * dtype_bytes          # x, read twice
                          + 4 * width * dtype_bytes    # gate, up: out + in
                          + 2 * width * dtype_bytes    # hidden: out + in
                          + d * 4)                     # float32 result
    return weights + rows


def vocab_size(config: dict) -> int:
    return config["vocab_size"]


def max_context(config: dict) -> int:
    return config["max_position_embeddings"]


# ------------------------------------------------------------ the tiny copy
def tiny(config: dict) -> dict:
    """A copy at a size a CPU runs in seconds (``run.py --rehearse``): the
    dense layer and one whole period + 1 (sliding x 3, full, sliding x 2),
    a window shorter than a user turn's bucket, a quarter of 16 experts
    held."""
    config = copy.deepcopy(config)
    layers = 6
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=96,
                  moe_intermediate_size=32, num_router_experts=16,
                  num_experts=4, num_experts_per_tok=3,
                  num_hidden_layers=layers, sliding_window=8,
                  layer_types=config["layer_types"][:layers],
                  mlp_layer_types=config["mlp_layer_types"][:layers],
                  max_position_embeddings=128, vocab_size=512)
    config["sliding_windows"] = layer_windows(config)
    config["expert_parallel"].update(ranks=4, rank=1, first_expert=4, held=4)
    config["assumed"].update(dtype="float32", loss_chunk=32)
    return config
