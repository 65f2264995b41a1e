"""Olmo Hybrid (Allen AI, ``model_type`` ``olmo_hybrid``) as the benchmark
knows it: a published ``config.json`` (``layer_types`` of ``linear_attention``
and ``full_attention``, ``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``, ``rope_parameters``, ...) as the program's model,
its weights, its reference (``reference/olmo_hybrid.py``) with the names it
takes, its tolerances, its counts and its tiny copy.

The program's model is a layer PATTERN, one period of ``layer_types``
(linear, linear, linear, full), every layer's feed-forward the dense SwiGLU.
A linear layer is the program's ``gdn`` mixer (the scalar-decay arm of
``ops/delta_attention.py``: key heads of ``linear_key_head_dim``, value heads
of ``linear_value_head_dim``, a write strength of up to 2), a full layer its
``softmax`` mixer without rotary (``rope_theta`` null) whose q and k are
normed over all heads at once; norms on the branches' OUTPUTS (the Olmo
block).

Counts, convention (PaLM appendix B): a matmul parameter costs 2 FLOPs per
token forward and 4 backward; the head counts, the embedding's lookup does
not; a full layer's scores and values cost 12 * 3,840 * keys per token
forward + backward; a linear layer's three products with its states 18 *
heads * 96 * 192; convolutions, norms and activations are not counted.  No
training cell runs this family.
"""

from __future__ import annotations

import copy
import functools
import math

from ..program import program_seed

LINEAR, FULL = "linear_attention", "full_attention"

# Three comparisons decide ``correct``.  All readings on the v5e at 1 x 2,048,
# the cell's own check, weights as make_weights draws them (my chip runs, PR
# 50; ``scripts/olmo_controls.py``, chiprun_out/pr50_controls.jsonl; PERF.md
# section 6); the controls put the fault into the REFERENCE
# (``reference.forward``'s ``faults``), which the comparison cannot tell from
# the same fault in the program.
#
# (a) ``logits_rms`` / ``logits_max``: the program's logits (``Transformer.
#     apply``, bfloat16: 32 chunks of 64 in the twelve linear layers, 2,048
#     keys in the four full ones) against the reference's, over the standard
#     deviation of the reference's logits.  Sound, twenty-two readings over
#     twenty-two seeds (six here, sixteen runs of the cell): RMS 0.01567 ..
#     0.01593, largest 0.0892 .. 0.1103.  Controls, two seeds each (RMS;
#     largest): sqrt(96) for sqrt(128) under the full layers' scores 0.0503,
#     0.0507; 0.357, 0.330
#     (the nearest these two are meant to catch).  ``beta`` left undoubled
#     0.3013, 0.3026; 1.877, 1.842.  The decay's sign dropped: not a number
#     (a state that grows by up to e^0.13 a position leaves float32's range
#     inside 2,048; ``correct.compare_forward`` asks for a finite reading).
#     The limits stand 1.78 times above the sound runs' largest RMS and 1.78
#     times under the nearest control's smallest, 1.63 times above the
#     largest difference and 1.83 times under.  NOT caught by these two,
#     and (b) is for them: the state kept at bfloat16's mantissa (0.01766,
#     0.01780; 0.111, 0.107: an eighth more than the sound runs') and the
#     rule's products with operands at bfloat16's mantissa (0.01604, 0.01615;
#     0.0993, 0.0973: a fiftieth more).
# (b) ``STATE_TOLERANCE``: the FIRST linear layer's matrix state after the
#     last of the 2,048 tokens, the program's (``Transformer._forward``'s
#     kept states) against the reference's scan's, |S - S_ref| / |S_ref| over
#     the layer's 30 heads.  The first layer, because its input is the
#     embedding's row, exact on both sides: what the comparison reads is the
#     state's own arithmetic (bfloat16 q, k, v into a float32 state, in
#     chunks, against float32 throughout), not the noise the bfloat16 stream
#     has gathered by a deeper layer (the twelfth's reads 0.030 sound, 0.031
#     and 0.037 with the two faults: Kimi Linear's finding again).  Sound,
#     six seeds: 0.003342 .. 0.003464 (a fiftieth apart: the reading hardly
#     depends on the seed).  The rule's three products with every operand at
#     bfloat16's seven mantissa bits: 0.004915, 0.005016.  The state kept at
#     bfloat16's mantissa after every position (the nearest precision below
#     the float32 that ``generation.state_shape`` states): 0.012797,
#     0.013350.  The limit stands 1.18 times above the sound runs' largest
#     and 1.20 times under the nearer control's smallest: little room, but
#     seventeen times the sound readings' own spread, and the program's own
#     error there is its bfloat16 q, k and v, which a product in bfloat16
#     only adds a half to.  WHAT THIS HOLDS is the arithmetic of the
#     program's forward over a sequence (``Transformer._forward``: the chunked
#     arm, what a prefill and an extension run): a later change that
#     multiplies or carries the state narrower THERE reads ``correct`` false.
#     It does NOT hold the round's one-position recurrence, nor the dtype the
#     cache stores the state in (a tenth of the cell's round, 0.43 GB of its
#     cache): on the chip those go through ``served_ok`` alone, sixteen
#     tokens at 0.7 deviations, which a bfloat16 state passes (it moves the
#     logits' RMS from 0.0157 to 0.0177), and are held exactly, in float32,
#     by tests/test_olmo_hybrid.py.  ``jobs/serve.py`` frees the server
#     before the family is asked anything, so the served request's own state
#     cannot be compared here (PERF.md section 7).
# Each control through the harness's own comparison, ``correct.
# compare_forward`` with the fault handed to ``reference_forward`` (my chip
# run, PR 50, ``scripts/olmo_controls.py``, chiprun_out/pr50_controls2.jsonl,
# seed 3000000067): sound ``ok`` true (RMS 0.01573, largest 0.1006); the
# rule's products in bfloat16, the state in bfloat16, ``beta`` undoubled and
# the decay's sign dropped each ``ok`` FALSE (not a number: the state's limit,
# or a state out of float32's range); sqrt(96) for sqrt(128) ``ok`` false by
# both logit limits (0.0505, 0.314).  tests/test_olmo_hybrid.py holds the
# same verdicts at the tiny size.
STATE_TOLERANCE = 0.0041
LOGIT_TOLERANCE = 0.0283
MAX_TOLERANCE = 0.18
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position (the served tokens come from a THIRD compilation,
# the decode round against the cache).  As in the other families it holds the
# path a token takes through the decode program (the slot's token, the
# embedding, both states' update by the one-position recurrence, the full
# layers' K/V written a head's row an index and attended, the head), NOT the
# cache's indexing: tests/test_olmo_hybrid.py holds the states, the rows and
# the snapshots, exactly, in float32.  Readings (my chip runs, PR 50):
# sound, sixteen replays of 16 served tokens (the cell's runs): 0.0 ten
# times, 0.0001 .. 0.036 six times.  A token that has nothing to do with the
# reference's distribution lies where a random token lies, three to four
# deviations under the reference's best (Kimi Linear's reading of the same
# comparison, PR 47: 3.36 to 4.02).  Kimi Linear's limit, and for its reason.
NEAR_TIE_TOLERANCE = 0.7
# No training cell runs this family: what a float32 CPU comparison at the
# tiny size holds (tests/test_olmo_hybrid.py); the chip has not read them.
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4
TOLERANCES = {"logits_rms": LOGIT_TOLERANCE, "logits_max": MAX_TOLERANCE,
              "near_tie": NEAR_TIE_TOLERANCE, "gradient": GRADIENT_TOLERANCE,
              "loss": LOSS_TOLERANCE}

# Standard deviation of the random embedding (the head is a matrix of its
# own), SmallThinker's and for its reason: what a position has of its own
# must outweigh what the mixers add to every position alike.
EMBED_STD = 1.0
# A linear layer's decays, drawn so that a seed's lie where a trained model's
# do, 0.9 to 0.999 a position (Kimi Linear's draw and reason: the published
# module draws A uniform(0, 16) and dt log-uniform(0.001, 0.1) at its
# initialisation, a head keeping as little as 0.2 of its state a position;
# training moves them toward long memory, and a head that forgets half its
# state a position is no cache to speak of): a head's rate exp(A_log)
# uniform(0.5, 1), its softplus(dt_bias) log-uniform(0.002, 0.1), and the
# token's own term x W_a at a standard deviation of DECAY_TOKEN_STD around it:
# -g between 0.0007 and 0.135, a head keeping 0.874 to 0.9993 a position.
DECAY_RATE = (0.5, 1.0)
DECAY_STEP = (0.002, 0.1)
DECAY_TOKEN_STD = 0.3
# A full layer's q and k leave their norms at an RMS of 1 a channel whatever
# their projections' scale (the norm runs over all 3,840 channels), so a
# score q . k / sqrt(128) is normal(0, 1): over 2,048 keys a nearly flat
# softmax, the output an average of the values, and neither a wrong scale
# under the scores' root nor a missing norm reaches the logits (Kimi Linear's
# finding, PR 47).  A trained model's attention is peaked: both gains are
# drawn at FULL_QK_GAIN, a score's standard deviation is then its square,
# 2.56, and a query at 2,048 keys weighs about eight of them.
FULL_QK_GAIN = 1.6


def branch_gain(layers: int) -> float:
    """The gain of a layer's two output norms.  The Olmo block norms a
    branch's OUTPUT, so a depth scale on the output projections (which the
    pre-norm families draw at 1 / sqrt(2 L)) is normed away; it is the
    norms' gain here, K-EXAONE's way: the 2 L branches together then add as
    much to the stream as the embedding holds."""
    return 1.0 / math.sqrt(2.0 * layers)


# --------------------------------------------------------------- the model
def layer_period(config: dict) -> list[str]:
    """The shortest period the kept layers repeat."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - {LINEAR,
                                                                  FULL}:
        raise ValueError(f"layer_types names each of the "
                         f"{config['num_hidden_layers']} layers "
                         f"{LINEAR} or {FULL}")
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
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or config["tie_word_embeddings"]
            or config["rope_parameters"]["rope_theta"] is not None
            or config["num_key_value_heads"] != heads
            or config["linear_num_key_heads"] != heads
            or config["linear_num_value_heads"] != heads
            or config["hidden_size"] % heads):
        raise ValueError(
            "the program's Olmo Hybrid has SwiGLU feed-forwards, no bias, a "
            "head of its own, no rotary base (rope_theta null), and one head "
            "count for the full layers' queries, keys and values and the "
            "linear layers' keys and values")
    kinds = {LINEAR: LayerSpec(mixer="gdn", ffn="mlp"),
             FULL: LayerSpec(mixer="softmax", rope=False, qk_norm="all",
                             ffn="mlp")}
    fields = dict(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=heads, head_dim=config["hidden_size"] // heads,
        n_kv_heads=config["num_key_value_heads"],
        delta_key_dim=config["linear_key_head_dim"],
        delta_value_dim=config["linear_value_head_dim"],
        delta_neg_eigval=bool(config["linear_allow_neg_eigval"]),
        conv_kernel=config["linear_conv_kernel_dim"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], prologue=(),
        pattern=tuple(kinds[kind] for kind in layer_period(config)),
        max_seq=config["max_position_embeddings"],
        dtype=getattr(jnp, assumed["dtype"]), norm="rms",
        norm_eps=float(config["rms_norm_eps"]), norm_placement="post",
        bias=False, mlp_act="swiglu", remat=bool(assumed["remat"]),
        remat_policy=assumed["remat_policy"],
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
    Kept between calls: a run makes the store twice (the server's, then the
    check's)."""
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

        if name in ("ln1/scale", "ln2/scale"):
            return jnp.full(shape, branch_gain(layers), dtype)
        if name in ("attn/q_norm/scale", "attn/k_norm/scale"):
            return jnp.full(shape, FULL_QK_GAIN, dtype)
        if name.endswith("/scale"):
            return jnp.ones(shape, dtype)
        if name == "embed/tok":
            return normal(EMBED_STD)
        if name.endswith("decay/a_log"):
            return jnp.log(uniform(*DECAY_RATE)).astype(dtype)
        if name.endswith("decay/dt_bias"):
            step = jnp.exp(uniform(*map(math.log, DECAY_STEP)))
            return jnp.log(jnp.expm1(step)).astype(dtype)
        std = 1.0 / math.sqrt(shape[-2])
        if name.endswith("decay/w"):
            std *= DECAY_TOKEN_STD
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
    convolution's fan-in its taps), the embedding at :data:`EMBED_STD`, a
    layer's two output norms' gains :func:`branch_gain`, the full layers' q
    and k gains :data:`FULL_QK_GAIN`, the decays' parameters as
    :data:`DECAY_RATE`, :data:`DECAY_STEP` and :data:`DECAY_TOKEN_STD` say,
    the other gains one.  :data:`_BUILD_GROUPS` jitted calls
    (:func:`_weight_builders`), each from a thread of its own so that the
    compiler builds them side by side on a cold start.  The bits come from
    the chip's own generator (``rbg``): 4.1 billion values."""
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
_BLOCK = {"norm_mixer": "ln1/scale", "norm_ffn": "ln2/scale",
          "w1": "mlp/w1", "w3": "mlp/w3", "w2": "mlp/w2",
          "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv",
          "wo": "attn/wo"}
_LINEAR = {"conv_q": "attn/conv_q", "conv_k": "attn/conv_k",
           "conv_v": "attn/conv_v", "wa": "attn/decay/w",
           "a_log": "attn/decay/a_log", "dt_bias": "attn/decay/dt_bias",
           "wb": "attn/beta/w", "wz": "attn/wz",
           "o_gain": "attn/o_norm/scale"}
_FULL = {"q_gain": "attn/q_norm/scale", "k_gain": "attn/k_norm/scale"}
_NAMES = {**_BLOCK, **_LINEAR, **_FULL}


def reference_weights(config: dict, params: dict) -> dict:
    """The program's store (or a gradient in its shape) in the reference's
    names.  The SAME buffers, not a float32 copy (bfloat16 values are exact
    in float32, and the reference widens a layer's matrices as it meets
    them)."""
    def layer(i):
        return {ours: params[f"layer{i}/{theirs}"]
                for ours, theirs in _NAMES.items()
                if f"layer{i}/{theirs}" in params}

    return {"embed": params["embed/tok"], "head": params["lm_head/w"],
            "final_norm": params["final_ln/scale"],
            "layers": [layer(i) for i in range(config["num_hidden_layers"])]}


def _reference_arguments(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return dict(n_head=heads, head_dim=config["hidden_size"] // heads,
                key_dim=config["linear_key_head_dim"],
                value_dim=config["linear_value_head_dim"],
                eps=float(config["rms_norm_eps"]))


def program_states(config: dict, weights: dict, tokens) -> list:
    """Every linear layer's matrix state [B, H, Dk, Dv] after the program's
    own forward pass over ``tokens``, in its own precision, in layer order
    (``weights`` in the reference's names, the program's buffers)."""
    program = model(config, remat=False)
    params = {"embed/tok": weights["embed"], "lm_head/w": weights["head"],
              "final_ln/scale": weights["final_norm"]}
    for i, layer in enumerate(weights["layers"]):
        params.update({f"layer{i}/{_NAMES[ours]}": value
                       for ours, value in layer.items()})
    _, kept, _ = program._forward(params, tokens, collect_kv=True)
    return [kept[i][1] for i in program.config.layers_of("gdn")]


def reference_readings(config: dict, weights: dict, tokens, faults=None):
    """(the reference's logits; [linear layers]: how far the matrix state
    the program holds after the last token lies from the reference's, as a
    share of the reference's norm).  ``reference_forward`` judges by them; a
    script that reads tolerances prints them."""
    import jax.numpy as jnp

    from ..reference import olmo_hybrid as reference

    states: list = []
    logits = reference.forward(weights, tokens, faults=faults, states=states,
                               **_reference_arguments(config))
    apart = jnp.stack([
        jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b) ** 2) / jnp.sum(b ** 2))
        for a, b in zip(program_states(config, weights, tokens), states)])
    return logits, apart


def reference_forward(config: dict, weights: dict, tokens, faults=None):
    """The reference's logits (:func:`reference_readings`), and not a number
    where the FIRST linear layer's matrix state lies farther than
    ``STATE_TOLERANCE`` from the reference's.  No host callback (Kimi
    Linear's reason: the program that holds the reference is then kept by
    the compile cache like any other)."""
    import jax.numpy as jnp

    logits, apart = reference_readings(config, weights, tokens, faults)
    return jnp.where(apart[0] <= STATE_TOLERANCE, logits, jnp.nan)


def reference_loss(config: dict, weights: dict, tokens):
    """(loss, logits) of the reference."""
    from ..reference import olmo_hybrid as reference

    return reference.loss(weights, tokens, **_reference_arguments(config))


# -------------------------------------------------------------- the counts
def _mixer_params(config: dict, kind: str) -> int:
    """A mixer's parameters: a linear layer's 88,750,332 (q, k 2 x 3,840 x
    2,880; v, gate, o 3 x 3,840 x 5,760; decay and beta 2 x 3,840 x 30; three
    conv kernels 4 x 11,520; A_log, dt_bias 30 + 30; the output norm 192), a
    full layer's 58,990,080 (q, k, v, o 4 x 3,840 x 3,840; the q and k
    norms' gains 2 x 3,840) at the published widths."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if kind == FULL:
        return 4 * d * d + 2 * d
    keys = heads * config["linear_key_head_dim"]
    values = heads * config["linear_value_head_dim"]
    return (2 * d * keys + 3 * d * values + 2 * d * heads
            + config["linear_conv_kernel_dim"] * (2 * keys + values)
            + 2 * heads + config["linear_value_head_dim"])


def layer_params(config: dict, layer: int) -> int:
    """Parameters of layer ``layer``: its mixer, the dense SwiGLU
    (126,812,160) and two norm gains: 215,570,172 linear, 185,809,920
    full."""
    d = config["hidden_size"]
    return (_mixer_params(config, config["layer_types"][layer])
            + 3 * d * config["intermediate_size"] + 2 * d)


def param_count(config: dict) -> int:
    """Parameters of the cut: its layers, the embedding, the head, the
    final norm."""
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + 2 * config["vocab_size"] * d + d)


def active_matmul_params(config: dict) -> float:
    """Weights one token meets in a matmul: every layer's but its norms',
    convolutions' and decay vectors', and the head."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    taps = config["linear_conv_kernel_dim"]
    keys = heads * config["linear_key_head_dim"]
    values = heads * config["linear_value_head_dim"]
    total = float(config["vocab_size"] * d)
    for kind in config["layer_types"]:
        total += 3 * d * config["intermediate_size"]
        total += (4 * d * d if kind == FULL else
                  _mixer_params(config, kind) - taps * (2 * keys + values)
                  - 2 * heads - config["linear_value_head_dim"])
    return total


def train_flops_per_token(config: dict, seq_len: int) -> float:
    heads = config["num_attention_heads"]
    kinds = config["layer_types"]
    return (6.0 * active_matmul_params(config)
            + kinds.count(FULL) * 12.0 * config["hidden_size"] * seq_len
            + kinds.count(LINEAR) * 18.0 * heads
            * config["linear_key_head_dim"] * config["linear_value_head_dim"])


def _state_bytes(config: dict, dtype_bytes: int = 2,
                 stored: bool = False) -> int:
    """One linear layer's two states of one lane: the [3, 11520] shift
    register (69,120 B) and the [30, 96, 192] float32 matrix (2,211,840 B;
    AS STORED 2,949,120: the device lays its 192 value channels in 256
    lanes, ``generation.state_shape`` says why)."""
    heads = config["num_attention_heads"]
    keys, values = (config["linear_key_head_dim"],
                    config["linear_value_head_dim"])
    lanes = -(-values // 128) * 128 if stored else values
    return ((config["linear_conv_kernel_dim"] - 1) * heads
            * (2 * keys + values) * dtype_bytes + heads * keys * lanes * 4)


def _kv_bytes(config: dict, dtype_bytes: int = 2) -> int:
    """One cached position of one full layer: K and V of 30 heads of 128,
    15,360 B."""
    return 2 * config["hidden_size"] * dtype_bytes


def slot_bytes(config: dict, max_len: int, dtype_bytes: int = 2) -> dict:
    """Bytes of one cache slot by kind of part, as the program's arrays
    count them (``nbytes``: the matrix unpadded): the full layers' K/V by
    position; the linear layers' two states, whatever the context's
    length."""
    kinds = config["layer_types"]
    return {"full": kinds.count(FULL) * max_len * _kv_bytes(config,
                                                            dtype_bytes),
            "window": 0, "latent": 0,
            "state": kinds.count(LINEAR) * _state_bytes(config, dtype_bytes)}


def linear_attn_bytes(config: dict, state_updates: float) -> float:
    """The least a linear layer's round has to move: each (slot, layer) pair
    of states read once and written once, at the bytes the parts really take
    on the device (2 x (2,949,120 + 69,120) B an update); the layer's
    weights, which the scope's time also covers, are not counted."""
    return 2 * _state_bytes(config, stored=True) * state_updates


def full_attn_bytes(config: dict, positions_live: float) -> float:
    """What a full layer's round would have to read if it read LIVE rows
    alone: K and V of every position its lanes hold, once (15,360 B a
    position and layer).  The round reads its part whole, whatever the lanes
    hold, so this share of the roofline reads low by as much as the lanes are
    empty: that is what it is for.  ``attn/full`` covers the cache products
    alone (the projections stand under ``attn_qkv`` / ``attn_out``): no
    weights are counted."""
    return _kv_bytes(config) * positions_live


def vocab_size(config: dict) -> int:
    return config["vocab_size"]


def max_context(config: dict) -> int:
    return config["max_position_embeddings"]


# ------------------------------------------------------------ the tiny copy
def tiny(config: dict) -> dict:
    """A copy at a size a CPU runs in seconds (``run.py --rehearse``): one
    whole period + 1 (linear x 3, full, linear), key heads of 8 and value
    heads of 16 beside softmax heads of 12."""
    config = copy.deepcopy(config)
    config.update(hidden_size=48, num_attention_heads=4,
                  num_key_value_heads=4, linear_num_key_heads=4,
                  linear_num_value_heads=4, linear_key_head_dim=8,
                  linear_value_head_dim=16, intermediate_size=96,
                  num_hidden_layers=5,
                  layer_types=[LINEAR] * 3 + [FULL, LINEAR],
                  max_position_embeddings=128, vocab_size=512)
    config["assumed"].update(dtype="float32", loss_chunk=32)
    return config
