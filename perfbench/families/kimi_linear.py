"""Kimi Linear (Moonshot AI, ``model_type`` ``kimi_linear``) as the benchmark
knows it: a published ``config.json`` (``linear_attn_config`` with its
``kda_layers`` and ``full_attn_layers`` counted from 1, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``first_k_dense_replace``, ``moe_intermediate_size``,
``num_experts_per_token``, ``num_shared_experts``,
``moe_router_activation_func``, ``moe_renormalize``,
``routed_scaling_factor``, ...) as the program's model, its weights, its
reference (``reference/kimi_linear.py``) with the names it takes, its
tolerances, its counts and its tiny copy.

The configuration is ONE CHIP'S SHARE of an expert-parallel stage
(``expert_parallel``: ``ranks``, ``rank``, ``first_expert``, ``held``), as
K-EXAONE's: ``num_experts`` is what this chip holds, ``num_router_experts``
the router's published width.  The program's model is a PROLOGUE of
``first_k_dense_replace`` layers with a dense SwiGLU of ``intermediate_size``
and then a layer PATTERN, one period of the kinds that follow (KDA, KDA,
MLA, KDA), every such layer's feed-forward the held share of a dropless
top-k mixture of SwiGLU experts (the router over all ``num_router_experts``,
a sigmoid score, a stored selection bias, gates over the chosen scores' sum
times ``routed_scaling_factor``) with a shared expert beside it.  A KDA layer
is the program's ``kda`` mixer (``ops/delta_attention.py``), an MLA layer its
``latent`` mixer without rotary (``mla_use_nope``); norms on the branches'
inputs.

Counts, convention (PaLM appendix B): a matmul parameter costs 2 FLOPs per
token forward and 4 backward; only the ACTIVE and HELD experts' parameters
count (a token meets 8 of 256, of which 32 / 256 x 8 = 1 lies here on
average); the shared expert, the router and the head count, the embedding's
lookup does not; an MLA layer's scores and values cost 12 * (heads x (128 +
64 / 2)) * keys per token forward + backward; a KDA layer's three products
with its states 18 * heads * 128 * 128; convolutions, norms and activations
are not counted.  No training cell runs this family.
"""

from __future__ import annotations

import copy
import functools
import math

from ..program import program_seed

KDA, MLA = "kda", "latent"

# Three comparisons decide ``correct``, two as in ``families/k_exaone.py``
# and for its reason: a router's near-tie flips (a token's 8th and 9th best
# of 256 scores + bias lie close and the program's bfloat16 stream carries
# noise), so the reference runs under the PROGRAM's selection and the
# selection itself is held to the reference's scores.  Only an expert HELD
# here moves the result.  All readings on the v5e at 1 x 4,096, the cell's own check,
# weights as make_weights draws them (my chip runs, PR 47; PERF.md section
# 6); the controls put the fault into the REFERENCE (``reference.forward``'s
# ``faults``, or its weights), which the comparison cannot tell from the
# same fault in the program.
#
# Readings "as the check runs": the logits from ``Transformer.apply``
# compiled alone, the selection (and the states of (c)) from the same pass
# compiled inside the reference's program (the harness's flow; a scratch
# script that takes the selection from an executable of its own reads the
# SOUND program at RMS 0.0177 and largest 0.37: two executables of one
# bfloat16 pass fall differently on a few ties, so the controls were read in the harness's
# flow too).  Sound, seventeen readings over ten seeds, weights as
# committed: RMS 0.00923 .. 0.00924, largest 0.0514 .. 0.0564, the farthest
# chosen expert 0.0037 .. 0.0071 under the cut (at the first draw of the
# weights, MLA at unit scales: 0.00908 .. 0.00910, 0.052 .. 0.059).
#
# (a) ``SELECTION_MARGIN``: every expert the program chose and the reference
#     would not must lie within this much of the reference's cut (its 8th
#     best score + bias), in units of the score.  Controls, two seeds each:
#     sqrt(128) for sqrt(192) under the scores' root 0.0220, 0.0231; the
#     delta term dropped 0.169, 0.150; the latent left un-normed 0.156,
#     0.215.  The limit stands 1.76 times above the sound runs' farthest and
#     1.76 times under the nearest control's.
# (b) ``logits_rms`` / ``logits_max``: the program's logits against the
#     reference run WITH the program's selection and the same share of the
#     experts.  Controls, two seeds each (RMS; largest): sqrt(128) for
#     sqrt(192) 0.0409, 0.0409; 0.238, 0.247 (the nearest).  Every matrix
#     through an 8-bit float (e4m3), the nearest precision below the
#     configuration's bfloat16: 0.1110, 0.1112; 0.714, 0.710.  A share that
#     claims the experts 32-63 on the weights of 0-31: 0.1687, 0.1696; 1.01,
#     1.07.  The delta term dropped (the state a decayed sum of k v^T):
#     0.2347, 0.2363; 1.44, 1.43.  The latent left un-normed: 0.2496,
#     0.2492; 1.57, 1.57.  The convolutions left out (a tap of one): 0.3712,
#     0.3712; 2.18, 2.13.  The RMS limit stands 2.06 times above the sound
#     runs' largest and 2.15 times under the nearest control's smallest; the
#     largest difference's 2.13 times above and 1.98 times under.
#     The matrix state kept at bfloat16's seven mantissa bits is NOT caught
#     by these two (RMS 0.00985, 0.00984; largest 0.056, 0.055; farthest
#     0.0047, 0.0054: a fifteenth more than the sound runs'); (c) is for it.
# (c) ``STATE_TOLERANCE``: the FIRST KDA layer's matrix state after the last
#     of the 4,096 tokens, the program's (``expert_selections``' ``kept``)
#     against the reference's scan's, |S - S_ref| / |S_ref| over the layer's
#     32 heads.  The first layer, because its input is the embedding's row,
#     exact on both sides: what the comparison reads is the state's own
#     arithmetic (bfloat16 q, k, v into a float32 state, in chunks, against
#     float32 throughout), not the noise the bfloat16 stream has gathered by
#     a deeper layer (the ninth KDA layer's reads 0.0137 sound and 0.0205
#     with the fault: 1.5 times apart where the first's are 3.8).  Sound,
#     two seeds: 0.00381, 0.00383.  The state kept at bfloat16's mantissa
#     (``reduce_precision`` after every position, the nearest precision
#     below the float32 that ``generation.state_shape`` states): 0.01443,
#     0.01469.  The limit stands 1.96 times above the sound runs' largest
#     and 1.92 times under the control's smallest (my chip runs, PR 47;
#     chiprun_out/pr47b_controls.jsonl).  This float32 state leads the
#     cell's bytes (2.5 GB a round): a later change that stores it narrower
#     reads ``correct`` false here.
SELECTION_MARGIN = 0.0125
STATE_TOLERANCE = 0.0075
LOGIT_TOLERANCE = 0.019
MAX_TOLERANCE = 0.12
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position (the served tokens come from a THIRD compilation,
# the decode round against the cache).  As in the other families it holds
# the path a token takes through the decode program (the slot's token, the
# embedding, both states' update, the absorbed latent attention, the router,
# the held experts, the shared expert, the head), NOT the cache's indexing:
# tests/test_kimi_linear.py holds the states, the rows and the snapshots,
# exactly, in float32.  Two readings (my chip runs, PR 47): sound, twenty
# replays of 16 served tokens: 0.0 thirteen times, 0.005 .. 0.024 five
# times, 0.090, 0.129; a token that has nothing to do with the reference's
# distribution lies where a random token lies: the reference's best logit
# stands 3.36 (the least of 4,096 positions) to 4.02 (their mean)
# deviations above a position's mean.  The limit stands 5.4 times above
# the largest sound replay and 4.8 to 5.7 times under the wrong token's.
NEAR_TIE_TOLERANCE = 0.7
# No training cell runs this family: what a float32 CPU comparison at the
# tiny size holds (tests/test_kimi_linear.py); the chip has not read them.
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4
TOLERANCES = {"logits_rms": LOGIT_TOLERANCE, "logits_max": MAX_TOLERANCE,
              "near_tie": NEAR_TIE_TOLERANCE, "gradient": GRADIENT_TOLERANCE,
              "loss": LOSS_TOLERANCE}

# Standard deviation of the random embedding (the head is a matrix of its
# own), SmallThinker's and K-EXAONE's and for their reason: what a position
# has of its own must outweigh what the mixers add to every position alike,
# and the routers' logits then stay near normal(0, 1) at every depth.
EMBED_STD = 1.0
# Standard deviation of the stored selection bias: K-EXAONE's (PR 40's
# lesson: a random correction un-evens the loads it exists to even, and a
# chip that holds an eighth of the experts then computes another share of
# the rows for every seed; ``assumed.expert_bias`` in the configuration's
# file).
EXPERT_BIAS_STD = 0.005
# A KDA layer's decays, drawn so that a seed's lie where a trained model's
# do, 0.9 to 0.999 a position (the published module draws A uniform(1, 16)
# and dt log-uniform(0.001, 0.1) at its initialisation, a channel keeping
# 0.2 to 0.999; training moves them toward long memory, and a channel that
# forgets half its state a position is no cache to speak of): a head's rate
# exp(A_log) uniform(0.5, 1), a channel's softplus(dt_bias) log-uniform
# (0.002, 0.1), and the token's own term W_fb (W_fa x) at a standard
# deviation of DECAY_TOKEN_STD around it, which moves a decay by a factor of
# e^+-0.3 in its exponent: -g between 0.0007 and 0.135, a channel keeping
# 0.874 to 0.9993 a position.
DECAY_RATE = (0.5, 1.0)
DECAY_STEP = (0.002, 0.1)
DECAY_TOKEN_STD = 0.3
# An MLA layer's scores and latent, drawn so that the layer is no vanishing
# average: at unit scales a query's scores over S keys are normal(0, 1), the
# softmax is nearly flat, the output an average of S values (a 64th of one
# value's size at 4,096) and nothing the layer does reaches the logits
# (read on the chip, PR 47: the latent left un-normed or sqrt(128) for
# sqrt(192) moved the logits' RMS error from 0.0177 to 0.0178 and 0.0191).
# A trained model's attention is peaked.  The query's projection is drawn
# at MLA_QUERY_STD / sqrt(d) and ``wkv_a`` at LATENT_RMS / sqrt(d): the
# latent comes out of its projection at an RMS of 2 (which the norm brings
# to 1: leaving the norm out doubles every head's own key part and value)
# and a score's standard deviation is 1.8 x sqrt((128 + 64 x 4) / 192) =
# 2.5, so a query at 4,096 keys weighs about eight of them.
MLA_QUERY_STD = 1.8
LATENT_RMS = 2.0


# --------------------------------------------------------------- the model
def layer_kinds(config: dict) -> list[str]:
    """Every kept layer's mixer, from ``linear_attn_config`` (its lists
    count from 1)."""
    linear = config["linear_attn_config"]
    layers = config["num_hidden_layers"]
    kda, full = set(linear["kda_layers"]), set(linear["full_attn_layers"])
    if (kda | full != set(range(1, layers + 1))) or kda & full:
        raise ValueError(f"kda_layers and full_attn_layers name each of the "
                         f"{layers} layers once, counted from 1")
    return [KDA if i in kda else MLA for i in range(1, layers + 1)]


def layer_period(config: dict) -> list[str]:
    """The shortest period the kept layers after the dense ones repeat."""
    kinds = layer_kinds(config)[config["first_k_dense_replace"]:]
    for period in range(1, len(kinds) + 1):
        if all(kind == kinds[i % period] for i, kind in enumerate(kinds)):
            return kinds[:period]
    raise ValueError("no layer follows the dense ones")


def held_experts(config: dict) -> tuple[int, int]:
    """(first, count) of the experts this chip holds."""
    share = config["expert_parallel"]
    return share["first_expert"], share["held"]


def transformer_config(config: dict, **overrides):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, TransformerConfig)

    assumed = config["assumed"]
    linear = config["linear_attn_config"]
    dense = config["first_k_dense_replace"]
    first, held = held_experts(config)
    if (config["moe_router_activation_func"] != "sigmoid"
            or not config["moe_renormalize"] or not config["mla_use_nope"]
            or config["num_expert_group"] != 1 or config["topk_group"] != 1
            or config["hidden_act"] != "silu" or config["moe_layer_freq"] != 1
            or config["q_lora_rank"] is not None
            or config["tie_word_embeddings"]
            or linear["num_heads"] != config["num_attention_heads"]
            or linear["head_dim"] != config["qk_nope_head_dim"]
            or config["v_head_dim"] != config["qk_nope_head_dim"]
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or assumed["gate_rank"] != linear["head_dim"]
            or held != config["num_experts"]):
        raise ValueError(
            "the program's Kimi Linear scores by a sigmoid, norms the gates "
            "over the chosen scores, selects without groups, routes every "
            "layer after its leading dense ones, has NoPE latent attention "
            "with a full-rank query, one head count and one head width for "
            "both mixers and the gates' rank, a head of its own, and holds "
            "num_experts = expert_parallel.held experts")
    kinds = layer_kinds(config)
    fields = dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"],
        kv_latent=config["kv_lora_rank"],
        qk_shared=config["qk_rope_head_dim"],
        conv_kernel=linear["short_conv_kernel_size"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        moe_experts=config["num_router_experts"], moe_held=(first, held),
        moe_top_k=config["num_experts_per_token"],
        moe_shared_experts=config["num_shared_experts"],
        moe_router_input="ffn", moe_score="sigmoid", moe_expert_bias=True,
        moe_route_scale=float(config["routed_scaling_factor"]),
        prologue=tuple(LayerSpec(mixer=kind, ffn="mlp")
                       for kind in kinds[:dense]),
        pattern=tuple(LayerSpec(mixer=kind, ffn="experts")
                      for kind in layer_period(config)),
        max_seq=config["model_max_length"],
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
    float32 copy of a whole stack is ever held; the program the compiler
    sees has a loop a kind, not a generator a leaf: 300 of them took it 92
    s of a cold run's set-up; my chip run, PR 47).  Kept between calls: a
    run makes the store twice (the server's, then the check's)."""
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

        def uniform(lo, hi):
            return jax.random.uniform(key, shape, jnp.float32, lo, hi)

        if name.endswith("/scale"):
            return jnp.ones(shape, dtype)
        if name == "embed/tok":
            return normal(EMBED_STD)
        if name.endswith("moe/router/bias"):
            return normal(EXPERT_BIAS_STD)
        if name.endswith("decay/a_log"):
            return jnp.log(uniform(*DECAY_RATE)).astype(dtype)
        if name.endswith("decay/dt_bias"):
            step = jnp.exp(uniform(*map(math.log, DECAY_STEP)))
            return jnp.log(jnp.expm1(step)).astype(dtype)
        std = 1.0 / math.sqrt(shape[-2])
        if name.endswith("decay/wb"):
            std *= DECAY_TOKEN_STD
        if name == "attn/wkv_a":
            std *= LATENT_RMS
        if name == "attn/wq" and shape[-1] != config.attn_dim:
            std *= MLA_QUERY_STD        # (a latent layer's: heads of D + s)
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

    # (the three stacks of experts, the last kinds, fall to three programs)
    return tuple(builder(numbered[g::_BUILD_GROUPS])
                 for g in range(_BUILD_GROUPS))


def make_weights(model, seed: int) -> dict:
    """The program's parameter store, made on the device from the seed in
    the model's own dtype: matrices normal(0, 1 / sqrt(fan-in)) (a
    convolution's fan-in its taps), the mixers' and the feed-forwards'
    output projections scaled by 1 / sqrt(2 L), the embedding at
    :data:`EMBED_STD`, the selection bias at :data:`EXPERT_BIAS_STD`, the
    decays' parameters as :data:`DECAY_RATE`, :data:`DECAY_STEP` and
    :data:`DECAY_TOKEN_STD` say, norm gains one.  :data:`_BUILD_GROUPS`
    jitted calls (:func:`_weight_builders`), each from a thread of its own
    so that the compiler builds them side by side on a cold start (one
    program of all the kinds took it 27 s of 36; my chip runs, PR 47).  The
    bits come from the chip's own generator (``rbg``): 3.2 billion values
    through threefry took 9 to 16 s a call."""
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
_KDA = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
        "conv_q": "attn/conv_q", "conv_k": "attn/conv_k",
        "conv_v": "attn/conv_v", "decay_a": "attn/decay/wa",
        "decay_b": "attn/decay/wb", "a_log": "attn/decay/a_log",
        "dt_bias": "attn/decay/dt_bias", "gate_a": "attn/gate/wa",
        "gate_b": "attn/gate/wb", "beta": "attn/beta/w",
        "o_gain": "attn/o_norm/scale"}
_MLA = {"wq": "attn/wq", "wkv_a": "attn/wkv_a",
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
                for names in (_NORMS, _KDA, _MLA, _DENSE, _EXPERTS)
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
        names = dict(_NORMS, **(_MLA if "wkv_a" in layer else _KDA),
                     **(_EXPERTS if "router" in layer else _DENSE))
        params.update({f"layer{i}/{names[ours]}": value
                       for ours, value in layer.items()})
    return params


def _reference_arguments(config: dict) -> dict:
    return dict(n_head=config["num_attention_heads"],
                head_dim=config["qk_nope_head_dim"],
                shared_dim=config["qk_rope_head_dim"],
                latent=config["kv_lora_rank"],
                eps=float(config["rms_norm_eps"]),
                top_k=config["num_experts_per_token"],
                scale=float(config["routed_scaling_factor"]),
                held=held_experts(config))


def reference_readings(config: dict, weights: dict, tokens, faults=None):
    """(the reference's logits, of the same share of the experts, under the
    PROGRAM's selection: its own forward pass over the same tokens, in its
    own precision; [expert layers, B, 2]: the tokens whose program-chosen
    experts are not the reference's own and how far under the reference's
    cut the farthest of them lies; [KDA layers]: how far the matrix state
    the program holds after the last token lies from the reference's, as a
    share of the reference's norm).  ``reference_forward`` judges by them;
    a script that reads tolerances prints them."""
    import jax.numpy as jnp

    from ..reference import kimi_linear as reference

    program = model(config, remat=False)
    kept: list = []
    chosen = program.expert_selections(program_weights(config, weights),
                                       tokens, kept=kept)
    held, states = {}, []
    logits = reference.forward(
        weights, tokens, selection=chosen, faults=faults, states=states,
        report=lambda compared: held.update(compared=compared),
        **_reference_arguments(config))
    ours = [kept[i][1] for i in program.config.layers_of(KDA)]
    apart = jnp.stack([
        jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b) ** 2) / jnp.sum(b ** 2))
        for a, b in zip(ours, states)])
    return logits, held["compared"], apart


def reference_forward(config: dict, weights: dict, tokens, faults=None):
    """The reference's logits (:func:`reference_readings`), and not a
    number where the program chose an expert that lies farther than
    ``SELECTION_MARGIN`` under the reference's own cut, or where the FIRST
    KDA layer's matrix state lies farther than ``STATE_TOLERANCE`` from the
    reference's.  No host callback: the program that holds the reference
    is then kept by the compile cache like any other (with one, a warm run
    built both references again, 68 s of it; my chip runs, PR 47)."""
    import jax.numpy as jnp

    logits, compared, apart = reference_readings(config, weights, tokens,
                                                 faults)
    sound = ((jnp.max(compared[..., 1]) <= SELECTION_MARGIN)
             & (apart[0] <= STATE_TOLERANCE))
    return jnp.where(sound, logits, jnp.nan)


def reference_loss(config: dict, weights: dict, tokens):
    """(loss, logits), the reference under its own selection."""
    from ..reference import kimi_linear as reference

    return reference.loss(weights, tokens, **_reference_arguments(config))


# -------------------------------------------------------------- the counts
def _mixer_params(config: dict, kind: str) -> int:
    """A mixer's parameters: a KDA layer's 39,514,272 (q, k, v and o, three
    conv kernels, the two low-rank gates, beta, A_log, dt_bias, the output
    norm), an MLA layer's 29,114,880 (q, kv_a, the latent's norm, kv_b, o)
    at the published widths."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    size = config["qk_nope_head_dim"]
    inner = heads * size
    if kind == KDA:
        rank = config["assumed"]["gate_rank"]
        taps = config["linear_attn_config"]["short_conv_kernel_size"]
        return (4 * d * inner + 3 * taps * inner
                + 2 * (d * rank + rank * inner) + d * heads + heads + inner
                + size)
    latent, shared = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return (d * heads * (size + shared) + d * (latent + shared) + latent
            + latent * 2 * inner + inner * d)


def _expert_params(config: dict) -> int:
    """One expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, layer: int) -> int:
    """Parameters of layer ``layer`` AS HELD HERE: its mixer, two norm
    gains, and the dense SwiGLU or the router (every output), its bias, the
    shared expert and the held experts."""
    d = config["hidden_size"]
    if layer < config["first_k_dense_replace"]:
        ffn = 3 * d * config["intermediate_size"]
    else:
        routed = config["num_router_experts"]
        ffn = (d * routed + routed
               + (config["num_shared_experts"] + config["num_experts"])
               * _expert_params(config))
    return _mixer_params(config, layer_kinds(config)[layer]) + 2 * d + ffn


def param_count(config: dict) -> int:
    """Parameters this chip holds: its layers, its rows of the embedding
    and of the head, the final norm."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + 2 * config["vocab_size"] * d + d)


def active_matmul_params(config: dict) -> float:
    """Weights one token meets in a matmul HERE: every mixer's projections,
    the dense layers' SwiGLU, an expert layer's router, its shared expert
    and the held share of its active experts (top-k x held / routed: one
    expert on average), and the head's rows held here."""
    d = config["hidden_size"]
    total = float(config["vocab_size"] * d)
    here = (config["num_experts_per_token"] * config["num_experts"]
            / config["num_router_experts"])
    for i, kind in enumerate(layer_kinds(config)):
        total += _mixer_params(config, kind)
        if i < config["first_k_dense_replace"]:
            total += 3 * d * config["intermediate_size"]
        else:
            total += (d * config["num_router_experts"]
                      + (config["num_shared_experts"] + here)
                      * _expert_params(config))
    return total


def train_flops_per_token(config: dict, seq_len: int) -> float:
    heads, size = config["num_attention_heads"], config["qk_nope_head_dim"]
    kinds = layer_kinds(config)
    return (6.0 * active_matmul_params(config)
            + kinds.count(MLA) * 12.0 * heads
            * (size + config["qk_rope_head_dim"] / 2) * seq_len
            + kinds.count(KDA) * 18.0 * heads * size * size)


def _state_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """One KDA layer's two states of one lane: the [3, 12288] shift
    register (73,728 B) and the [32, 128, 128] float32 matrix (2,097,152)."""
    heads, size = config["num_attention_heads"], config["qk_nope_head_dim"]
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    return ((taps - 1) * 3 * heads * size * dtype_bytes
            + heads * size * size * 4)


def _latent_row_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """One cached position of one MLA layer: 512 of normed latent and 64
    of the shared key part, 1,152 B."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * dtype_bytes


def slot_bytes(config: dict, max_len: int, dtype_bytes: int = 2) -> dict:
    """Bytes of one cache slot by kind of part: the MLA layers' rows by
    position AS STORED (1,280 B a position and layer); the KDA layers' two
    states, whatever the context's length."""
    kinds = layer_kinds(config)
    # (a row is stored in whole registers of 128 lanes: 576 -> 640)
    lanes = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) // 128
              ) * 128
    return {"full": 0, "window": 0,
            "latent": kinds.count(MLA) * max_len * lanes * dtype_bytes,
            "state": kinds.count(KDA) * _state_bytes(config, dtype_bytes)}


def linear_attn_bytes(config: dict, state_updates: float) -> float:
    """The least a KDA layer's round has to move: each (slot, layer) pair
    of states read once and written once (2 x (2,097,152 + 73,728) B an
    update); the layer's weights, which the scope's time also covers, are
    not counted."""
    return 2 * _state_bytes(config) * state_updates


def latent_attn_bytes(config: dict, positions_read: float) -> float:
    """The least an MLA layer's round has to read: the row of every LIVE
    position once (1,152 B; the absorbed form needs it once for all 32
    heads), not the part's size."""
    return _latent_row_bytes(config) * positions_read


def moe_experts_bytes(config: dict, experts_touched: float,
                      assignments: float, dtype_bytes: int = 2) -> float:
    """Bytes the ``moe/experts`` block has to move for ``assignments``
    (token, choice) rows COMPUTED HERE over ``experts_touched`` (layer,
    held expert) pairs with at least one row, as K-EXAONE's: each touched
    expert's three matrices once (14,155,776 B); each row read for the gate
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
    return config["model_max_length"]


# ------------------------------------------------------------ the tiny copy
def tiny(config: dict) -> dict:
    """A copy at a size a CPU runs in seconds (``run.py --rehearse``): the
    dense KDA layer and one whole period + 1 (KDA, KDA, MLA, KDA, KDA), a
    quarter of 16 experts held."""
    config = copy.deepcopy(config)
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, qk_nope_head_dim=16, v_head_dim=16,
                  qk_rope_head_dim=8, kv_lora_rank=32, head_dim=16,
                  intermediate_size=96, moe_intermediate_size=32,
                  num_router_experts=16, num_experts=4,
                  num_experts_per_token=3, num_hidden_layers=6,
                  model_max_length=128, vocab_size=512)
    config["linear_attn_config"].update(
        kda_layers=[1, 2, 3, 5, 6], full_attn_layers=[4], num_heads=4,
        head_dim=16)
    config["expert_parallel"].update(ranks=4, rank=1, first_expert=4, held=4)
    config["assumed"].update(dtype="float32", loss_chunk=32, gate_rank=16)
    return config
