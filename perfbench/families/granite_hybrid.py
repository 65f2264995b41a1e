"""Granite 4.0-H (IBM, ``model_type`` ``granitemoehybrid``) as the benchmark
knows it: a published ``config.json`` (``layer_types`` of ``mamba`` and
``attention``, ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_conv_bias``,
``attention_multiplier``, ``embedding_multiplier``, ``residual_multiplier``,
``logits_scaling``, ``shared_intermediate_size``, ...) as the program's
model, its weights, its reference (``reference/granite_hybrid.py``) with the
names it takes, its tolerances, its counts and its tiny copy.

The program's model is a layer PATTERN, one period of ``layer_types``
(mamba x 5, attention, mamba x 4), every layer's feed-forward the dense
SwiGLU of ``shared_intermediate_size`` (``num_local_experts`` 0: the shared
MLP is the only feed-forward part).  A mamba layer is the program's ``ssm``
mixer (``ops/ssd.py``: one decay a head and position, keys and queries
shared by a group's heads, a state [H, P, N]), an attention layer its
``softmax`` mixer without rotary (``position_embedding_type`` ``nope``)
whose scores are multiplied by ``attention_multiplier``
(``TransformerConfig.attn_scale``); norms on the branches' INPUTS and the
three muP scalars MiniCPM-SALA's cell also carries (``embed_scale``,
``residual_scale``, ``logit_scale``).  The head is tied: the program keeps
``lm_head/w`` as a second matrix that holds the embedding's transpose
(``families/gpt2.py``'s way; ROADMAP R8).

Counts, convention (PaLM appendix B): a matmul parameter costs 2 FLOPs per
token forward and 4 backward; the head counts, the embedding's lookup does
not; an attention layer's scores and values cost 12 * 2,048 * keys per token
forward + backward; an ssm layer's two products with its states 12 * heads *
64 * 128; convolutions, norms and activations are not counted.  No training
cell runs this family.
"""

from __future__ import annotations

import copy
import functools
import math

from ..program import program_seed

SSM, FULL = "mamba", "attention"

# Three comparisons decide ``correct``.  All readings on the v5e at 1 x 2,048,
# the cell's own check, weights as make_weights draws them (my chip runs, PR
# 57; ``scripts/granite_controls.py``, chiprun_out/pr57_controls_b.jsonl and
# pr57_controls_b_1069.jsonl; PERF.md section 6); the controls put the fault
# into the REFERENCE (``reference.forward``'s ``faults``), which the
# comparison cannot tell from the same fault in the program.
#
# (a) ``logits_rms`` / ``logits_max``: the program's logits (``Transformer.
#     apply``, bfloat16: 8 chunks of 256 in the 36 ssm layers, 2,048 keys in
#     the four attention layers) against the reference's, over the standard
#     deviation of the reference's logits (0.0236: a tied head over a small
#     embedding).  Sound, nine readings over six seeds (six at 2,048 tokens,
#     three at 1,069, a served request's replay): RMS 0.02335 .. 0.02425,
#     largest 0.2008 .. 0.2409.  Controls, two seeds each (RMS; largest):
#     the state kept at bfloat16's mantissa after every position (the nearest
#     precision below the float32 that ``generation.state_shape`` states)
#     0.1758, 0.1831; 1.984, 2.035.  Scores times 1/8 for 1/64 0.4667, 0.4692;
#     2.812, 2.978.  D left out 0.6895, 0.7032; 6.82, 7.07.  The norm before
#     the gate 0.8579, 0.8660; 5.33, 5.41.  The convolution's bias left out
#     1.1356, 1.1362; 6.62, 6.77.  The limits stand 2.7 times above the sound
#     runs' largest RMS and 2.7 times under the nearest control's smallest,
#     2.9 times above the largest difference and 2.9 times under.
# (b) ``STATE_TOLERANCE``: the FIRST ssm layer's matrix state after the last
#     token, the program's (the model's first layer run alone:
#     ``program_first_state``) against the reference's scan's, |h - h_ref| /
#     |h_ref| over the layer's 64 heads.  The first layer, because its input
#     is the embedding's row, exact on both sides (Olmo Hybrid's reason: what
#     the comparison reads is the state's own arithmetic, bfloat16 x, B, C
#     into a float32 state in chunks against float32 throughout, not the
#     noise the bfloat16 stream has gathered by a deeper layer).  Sound, the
#     same nine readings: 0.001862 .. 0.002068.  The state kept at bfloat16's
#     mantissa: 0.1083, 0.1148 (fifty times the sound reading, where Olmo
#     Hybrid's read four: a head that keeps 0.9993 of its state a position
#     adds 2,048 small writes to a large sum, and a rounded sum loses them);
#     the convolution's bias left out moves the state itself: 0.859, 0.882.
#     The limit stands 7.3 times above the sound runs' largest and 7.2 times
#     under the control's smallest.  The embedding's first draw (1/12, the
#     stream starting at 1) read 0.001836 .. 0.002027 on random tokens and
#     0.00424 on a replay that ended in one token seventeen times (EMBED_STD
#     says why it did): a state fed one input over and over is nearer its
#     rounding's worst case, and the limit has room for it.  WHAT THIS HOLDS
#     is the arithmetic of the program's forward over a sequence (the chunked
#     arm, what a prefill and an extension run); it does NOT hold the round's
#     one-position recurrence, nor the dtype the cache stores the state in:
#     on the chip those go through ``served_ok`` alone and are held exactly,
#     in float32, by tests/test_granite_hybrid.py (PERF.md section 7).
STATE_TOLERANCE = 0.015
LOGIT_TOLERANCE = 0.065
MAX_TOLERANCE = 0.69
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position (the served tokens come from a THIRD compilation,
# the decode round against the cache).  As in the other families it holds the
# path a token takes through the decode program (the slot's token, the
# embedding, both states' update by the one-position recurrence, the
# attention layers' K/V written and attended through the kernel of
# ops/pallas/full_decode.py, the head), NOT the cache's indexing:
# tests/test_granite_hybrid.py holds the states, the rows and the snapshots,
# exactly, in float32.  Olmo Hybrid's limit, and for its reason: a token that
# has nothing to do with the reference's distribution lies three to four
# deviations under the reference's best.
NEAR_TIE_TOLERANCE = 0.7
# No training cell runs this family: what a float32 CPU comparison at the
# tiny size holds (tests/test_granite_hybrid.py); the chip has not read them.
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4
TOLERANCES = {"logits_rms": LOGIT_TOLERANCE, "logits_max": MAX_TOLERANCE,
              "near_tie": NEAR_TIE_TOLERANCE, "gradient": GRADIENT_TOLERANCE,
              "loss": LOSS_TOLERANCE}

# Standard deviation of the random embedding BEFORE ``embedding_multiplier``:
# with the published 12 the stream starts at an RMS of 0.05.  The head is
# TIED: a token's logit for itself is its embedding's own share of the final
# stream times 2,048 E_std / 8, 45 deviations of the other logits at a share
# of one.  At an embedding of 1/12 (the stream starting at 1, MiniCPM-SALA's
# draw, whose head is its own) that share is a half, every position's argmax
# is its own input token, and sixteen served tokens are one token sixteen
# times whatever the cache holds (my chip run, PR 57: 100351 x 16, the
# replay's last prompt token, margin 0.0).  The 80 branches add an RMS near
# 1.6 (an ssm branch 0.22, an MLP 0.13, an attention 0.08 a layer, my
# arithmetic): at 0.05 the embedding's share of the final stream is 0.03, its
# own logit 1.4 deviations, and what a position predicts is what its layers
# made of the context.  Every layer norms its input, so the mixers see unit
# inputs either way.
EMBED_STD = 0.05 / 12.0
# An ssm layer's decays, drawn so that a seed's lie where a trained model's
# do (Olmo Hybrid's draw and reason: the published module draws A uniform(1,
# 16) and dt log-uniform(0.001, 0.1), a head keeping as little as 0.2 of its
# state a position; training moves them toward long memory): a head's rate
# exp(A_log) uniform(0.5, 1), its softplus(dt_bias) log-uniform(0.002, 0.1),
# and the token's own term, W_in's dt columns, at a standard deviation of
# DECAY_TOKEN_STD around it: delta A between -0.0007 and -0.135, a head
# keeping 0.87 to 0.9993 of its state a position.
DECAY_RATE = (0.5, 1.0)
DECAY_STEP = (0.002, 0.1)
DECAY_TOKEN_STD = 0.3
# The skip D near one (the published module starts it at one), and the
# convolution's bias as torch's Conv1d draws it, uniform(+-1 / sqrt(taps)):
# half the taps' sum's own deviation, so a layer without it is another layer.
SKIP = (0.8, 1.2)
# An attention layer's scores are q . k / 64 (``attention_multiplier``), not
# / 8: at unit gain a score's deviation is 0.125, the softmax over 2,048 keys
# is flat, the output an average of the values, and a wrong scale never
# reaches the logits (Olmo Hybrid's FULL_QK_GAIN and its reason).  W_q and
# W_k are drawn at this gain each: a score's deviation is then its square
# over 8, 2.5, and a query at 2,048 keys weighs about eight of them.
FULL_QK_GAIN = math.sqrt(20.0)


# --------------------------------------------------------------- the model
def layer_period(config: dict) -> list[str]:
    """The shortest period the layers repeat."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {SSM, FULL}:
        raise ValueError(f"layer_types names each of the "
                         f"{config['num_hidden_layers']} layers "
                         f"{SSM} or {FULL}")
    for period in range(1, len(kinds) + 1):
        if all(kind == kinds[i % period] for i, kind in enumerate(kinds)):
            return kinds[:period]
    raise ValueError("no layers")


def transformer_config(config: dict, **overrides):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, TransformerConfig)

    assumed = config["assumed"]
    heads = config["num_attention_heads"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
            or not config["tie_word_embeddings"]
            or config["position_embedding_type"] != "nope"
            or config["normalization_function"] != "rmsnorm"
            or config["num_local_experts"] or config["num_experts_per_tok"]
            or config["shared_intermediate_size"]
            != config["intermediate_size"]
            or inner != config["mamba_expand"] * config["hidden_size"]
            or config["hidden_size"] % heads):
        raise ValueError(
            "the program's Granite 4.0-H has SwiGLU feed-forwards of "
            "shared_intermediate_size and no experts, RMS norms, no bias on "
            "a projection, a bias on the convolution, a tied head, no "
            "positions (nope), and mamba_n_heads x mamba_d_head = "
            "mamba_expand x hidden_size")
    kinds = {SSM: LayerSpec(mixer="ssm", rope=False, ffn="mlp"),
             FULL: LayerSpec(mixer="softmax", rope=False, ffn="mlp")}
    fields = dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=heads, head_dim=config["hidden_size"] // heads,
        n_kv_heads=config["num_key_value_heads"],
        attn_scale=float(config["attention_multiplier"]),
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        conv_kernel=config["mamba_d_conv"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["shared_intermediate_size"], prologue=(),
        pattern=tuple(kinds[kind] for kind in layer_period(config)),
        max_seq=config["max_position_embeddings"],
        dtype=getattr(jnp, assumed["dtype"]), norm="rms",
        norm_eps=float(config["rms_norm_eps"]), norm_placement="pre",
        bias=False, mlp_act="swiglu",
        embed_scale=float(config["embedding_multiplier"]),
        residual_scale=float(config["residual_multiplier"]),
        logit_scale=1.0 / config["logits_scaling"],
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
    keys, ``lax.map``: the program the compiler sees has a loop a kind, not
    a generator a leaf; Kimi Linear's builders and for their reason, PR 47).
    The head is not drawn: ``make_weights`` ties it.  Kept between calls: a
    run makes the store twice (the server's, then the check's)."""
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        Transformer)

    shapes = Transformer(config).param_shapes()
    del shapes["lm_head/w"]
    dtype = config.dtype
    steps = config.ssm_heads

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
        if name.endswith("decay/a_log"):
            return jnp.log(uniform(*DECAY_RATE)).astype(dtype)
        if name.endswith("decay/dt_bias"):
            step = jnp.exp(uniform(*map(math.log, DECAY_STEP)))
            return jnp.log(jnp.expm1(step)).astype(dtype)
        if name.endswith("ssm/skip"):
            return uniform(*SKIP).astype(dtype)
        if name.endswith("conv/bias"):
            edge = 1.0 / math.sqrt(config.conv_kernel)
            return uniform(-edge, edge).astype(dtype)
        std = 1.0 / math.sqrt(shape[-2])
        if name.endswith(("attn/wq", "attn/wk")):
            std *= FULL_QK_GAIN
        if name.endswith("ssm/in_proj"):
            # the steps' columns, the last ``ssm_heads``, around dt_bias
            columns = jnp.arange(shape[-1]) >= shape[-1] - steps
            return (std * jnp.where(columns, DECAY_TOKEN_STD, 1.0)
                    * jax.random.normal(key, shape, jnp.float32)
                    ).astype(dtype)
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
                keys = jax.random.split(jax.random.fold_in(key, i),
                                        len(names))
                stack = jax.lax.map(lambda k: drawn(k, suffix, shape), keys)
                for j, name in enumerate(names):
                    out[name] = stack[j]
            return out

        return build

    return tuple(builder(numbered[g::_BUILD_GROUPS])
                 for g in range(_BUILD_GROUPS))


def make_weights(model, seed: int) -> dict:
    """The program's parameter store, made on the device from the seed in
    the model's own dtype: matrices normal(0, 1 / sqrt(fan-in)) (a
    convolution's fan-in its taps), the embedding at :data:`EMBED_STD`, the
    attention layers' W_q and W_k at :data:`FULL_QK_GAIN`, the decays'
    parameters as :data:`DECAY_RATE`, :data:`DECAY_STEP` and
    :data:`DECAY_TOKEN_STD` say, the skip :data:`SKIP`, the convolution's
    bias as torch draws it, the gains one, and the head the embedding's
    transpose, a second matrix (the model's head is tied).
    :data:`_BUILD_GROUPS` jitted calls (:func:`_weight_builders`), each from
    a thread of its own so that the compiler builds them side by side on a
    cold start.  The bits come from the chip's own generator (``rbg``): 3.2
    billion values."""
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
    params = {name: leaf for part in parts for name, leaf in part.items()}
    params["lm_head/w"] = params["embed/tok"].T
    return params


# ----------------------------------------------------------- the reference
_BLOCK = {"norm_mixer": "ln1/scale", "norm_ffn": "ln2/scale",
          "w1": "mlp/w1", "w3": "mlp/w3", "w2": "mlp/w2"}
_SSM = {"w_in": "ssm/in_proj", "conv_w": "ssm/conv/kernel",
        "conv_b": "ssm/conv/bias", "dt_bias": "ssm/decay/dt_bias",
        "a_log": "ssm/decay/a_log", "d_skip": "ssm/skip",
        "gate_gain": "ssm/norm/scale", "w_out": "ssm/out_proj"}
_FULL = {"wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo"}
_NAMES = {**_BLOCK, **_SSM, **_FULL}


def reference_weights(config: dict, params: dict) -> dict:
    """The program's store (or a gradient in its shape) in the reference's
    names.  The SAME buffers, not a float32 copy (bfloat16 values are exact
    in float32, and the reference widens a layer's matrices as it meets
    them).  The tied head is two matrices in the program and here alike."""
    def layer(i):
        return {ours: params[f"layer{i}/{theirs}"]
                for ours, theirs in _NAMES.items()
                if f"layer{i}/{theirs}" in params}

    return {"embed": params["embed/tok"], "head": params["lm_head/w"],
            "final_norm": params["final_ln/scale"],
            "layers": [layer(i) for i in range(config["num_hidden_layers"])]}


def _reference_arguments(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return dict(n_head=heads, n_kv_head=config["num_key_value_heads"],
                head_dim=config["hidden_size"] // heads,
                attn_scale=float(config["attention_multiplier"]),
                ssm_heads=config["mamba_n_heads"],
                ssm_head_dim=config["mamba_d_head"],
                ssm_state=config["mamba_d_state"],
                ssm_groups=config["mamba_n_groups"],
                eps=float(config["rms_norm_eps"]),
                embed_scale=float(config["embedding_multiplier"]),
                residual=float(config["residual_multiplier"]),
                logit_divisor=float(config["logits_scaling"]))


def program_first_state(config: dict, weights: dict, tokens):
    """The FIRST ssm layer's matrix state [B, H, P, N] after the program's
    own forward pass over ``tokens``, in its own precision (``weights`` in
    the reference's names, the program's buffers).  The first layer's state
    depends on nothing after it, so the program that is run is the model's
    first layer alone."""
    program = model(config, remat=False, n_layers=1)
    if program.config.layer_spec(0).mixer != "ssm":
        raise ValueError("the first layer's state is judged: layer 0 has to "
                         "be an ssm layer")
    params = {"embed/tok": weights["embed"], "lm_head/w": weights["head"],
              "final_ln/scale": weights["final_norm"]}
    params.update({f"layer0/{_NAMES[ours]}": value
                   for ours, value in weights["layers"][0].items()})
    _, kept, _ = program._forward(params, tokens, collect_kv=True)
    return kept[0][1]


def reference_readings(config: dict, weights: dict, tokens, faults=None):
    """(the reference's logits; how far the first ssm layer's matrix state
    the program holds after the last token lies from the reference's, as a
    share of the reference's norm).  ``reference_forward`` judges by them; a
    script that reads tolerances prints them."""
    import jax.numpy as jnp

    from ..reference import granite_hybrid as reference

    states: list = []
    logits = reference.forward(weights, tokens, faults=faults, states=states,
                               **_reference_arguments(config))
    ours = program_first_state(config, weights, tokens).astype(jnp.float32)
    return logits, jnp.sqrt(jnp.sum((ours - states[0]) ** 2)
                            / jnp.sum(states[0] ** 2))


def reference_forward(config: dict, weights: dict, tokens, faults=None):
    """The reference's logits (:func:`reference_readings`), and not a number
    where the FIRST ssm layer's matrix state lies farther than
    ``STATE_TOLERANCE`` from the reference's.  No host callback (Kimi
    Linear's reason: the program that holds the reference is then kept by
    the compile cache like any other)."""
    import jax.numpy as jnp

    logits, apart = reference_readings(config, weights, tokens, faults)
    return jnp.where(apart <= STATE_TOLERANCE, logits, jnp.nan)


def reference_loss(config: dict, weights: dict, tokens):
    """(loss, logits) of the reference."""
    from ..reference import granite_hybrid as reference

    return reference.loss(weights, tokens, **_reference_arguments(config))


# -------------------------------------------------------------- the counts
def _ssm_dims(config: dict) -> tuple[int, int]:
    """(inner width, channels through the convolution): 4,096 and 4,352."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    return inner, inner + 2 * config["mamba_n_groups"] * config[
        "mamba_d_state"]


def _mixer_params(config: dict, kind: str) -> int:
    """A mixer's parameters: an ssm layer's 25,847,232 (W_in 2,048 x 8,512;
    the kernel 4 x 4,352 and its bias 4,352; A_log, dt_bias, D 3 x 64; the
    gated norm's gain 4,096; W_out 4,096 x 2,048), an attention layer's
    10,485,760 (q, o 2 x 2,048 x 2,048; k, v 2 x 2,048 x 512) at the
    published widths."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if kind == FULL:
        kv = config["num_key_value_heads"] * (d // heads)
        return 2 * d * d + 2 * d * kv
    inner, conv = _ssm_dims(config)
    steps = config["mamba_n_heads"]
    return (d * (inner + conv + steps) + (config["mamba_d_conv"] + 1) * conv
            + 3 * steps + inner + inner * d)


def layer_params(config: dict, layer: int) -> int:
    """Parameters of layer ``layer``: its mixer, the shared SwiGLU
    (50,331,648) and two norm gains: 76,182,976 ssm, 60,821,504
    attention."""
    d = config["hidden_size"]
    return (_mixer_params(config, config["layer_types"][layer])
            + 3 * d * config["shared_intermediate_size"] + 2 * d)


def param_count(config: dict) -> int:
    """Parameters of the published model: its layers, the embedding (the
    head is tied to it), the final norm: 3,191,396,096."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + config["vocab_size"] * d + d)


def stored_params(config: dict) -> int:
    """Parameters as the program stores them: the tied head is a second
    matrix (``lm_head/w``), 205,520,896 more."""
    return param_count(config) + config["vocab_size"] * config["hidden_size"]


def active_matmul_params(config: dict) -> float:
    """Weights one token meets in a matmul: every layer's but its norms',
    convolution's, decay vectors' and skip's, and the head."""
    d = config["hidden_size"]
    inner, conv = _ssm_dims(config)
    # what of an ssm mixer meets no matmul: the kernel and its bias, A_log,
    # dt_bias and D, the gated norm's gain
    vectors = ((config["mamba_d_conv"] + 1) * conv
               + 3 * config["mamba_n_heads"] + inner)
    total = float(config["vocab_size"] * d)
    for kind in config["layer_types"]:
        total += 3 * d * config["shared_intermediate_size"]
        total += _mixer_params(config, kind) - (vectors if kind == SSM else 0)
    return total


def train_flops_per_token(config: dict, seq_len: int) -> float:
    kinds = config["layer_types"]
    return (6.0 * active_matmul_params(config)
            + kinds.count(FULL) * 12.0 * config["hidden_size"] * seq_len
            + kinds.count(SSM) * 12.0 * _ssm_dims(config)[0]
            * config["mamba_d_state"])


def _state_bytes(config: dict, dtype_bytes: int = 2) -> tuple[int, int]:
    """One ssm layer's two states of one lane: the [3, 4352] shift register
    (26,112 B) and the [64, 64, 128] float32 matrix (2,097,152 B; its last
    axis is whole registers, so the device stores what the array counts)."""
    inner, conv = _ssm_dims(config)
    return ((config["mamba_d_conv"] - 1) * conv * dtype_bytes,
            inner * config["mamba_d_state"] * 4)


def _kv_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """One cached position of one attention layer: K and V of 8 heads of
    64, 2,048 B."""
    heads = config["num_attention_heads"]
    return (2 * config["num_key_value_heads"]
            * (config["hidden_size"] // heads) * dtype_bytes)


def slot_bytes(config: dict, max_len: int, dtype_bytes: int = 2) -> dict:
    """Bytes of one cache slot by kind of part, as the program's arrays
    count them (``nbytes``): the attention layers' K/V by position; the ssm
    layers' two states, whatever the context's length."""
    kinds = config["layer_types"]
    return {"full": kinds.count(FULL) * max_len * _kv_bytes(config,
                                                            dtype_bytes),
            "window": 0, "latent": 0,
            "state": kinds.count(SSM) * sum(_state_bytes(config,
                                                         dtype_bytes))}


def linear_attn_bytes(config: dict, state_updates: float) -> float:
    """The least an ssm layer's round has to move: each (slot, layer) pair
    of states read once and written once (2 x (2,097,152 + 26,112) B an
    update); the layer's weights, which the scope's time also covers, are
    not counted."""
    return 2 * sum(_state_bytes(config)) * state_updates


def ssd_state_bytes(config: dict, state_updates: float) -> float:
    """The least the recurrence alone (``attn/linear/ssd``) has to move:
    each (slot, layer) matrix read once and written once, 2 x 2,097,152 B an
    update; no weights stand under that scope."""
    return 2 * _state_bytes(config)[1] * state_updates


def full_attn_bytes(config: dict, positions_live: float) -> float:
    """What an attention layer's round would have to read if it read LIVE
    rows alone: K and V of every position its lanes hold, once (2,048 B a
    position and layer).  ``attn/full`` covers the cache products alone (the
    projections stand under ``attn_qkv`` / ``attn_out``): no weights are
    counted."""
    return _kv_bytes(config) * positions_live


def vocab_size(config: dict) -> int:
    return config["vocab_size"]


def max_context(config: dict) -> int:
    return config["max_position_embeddings"]


# ------------------------------------------------------------ the tiny copy
def tiny(config: dict) -> dict:
    """A copy at a size a CPU runs in seconds (``run.py --rehearse``): half
    a period + 1 (mamba x 2, attention, mamba), 6 heads of 8 over a state
    of 16 in one group beside 4 softmax heads of 12 over 2 K/V heads."""
    config = copy.deepcopy(config)
    config.update(hidden_size=48, num_attention_heads=4,
                  num_key_value_heads=2, mamba_n_heads=12, mamba_d_head=8,
                  mamba_d_state=16, mamba_n_groups=1,
                  intermediate_size=96, shared_intermediate_size=96,
                  num_hidden_layers=4,
                  layer_types=[SSM, SSM, FULL, SSM],
                  max_position_embeddings=128, vocab_size=512)
    config["assumed"].update(dtype="float32", loss_chunk=32)
    return config
