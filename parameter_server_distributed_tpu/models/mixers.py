"""What a layer kind IS, said once: one record a ``LayerSpec.mixer``.

Everything above reads this table and nothing else names a kind to learn
what it is: ``transformer.py`` (a layer's weights, its products a token,
which method runs its branch, which kernel runs its decode round),
``generation.py`` (what a cache slot keeps of it) and ``serving.py`` (what
its admissions cost to compile).  The arrows point one way, ``ops/`` <-
``mixers`` <- ``transformer`` <- ``generation`` <- ``serving``: a record
names a ``Transformer`` method and a module of ``ops/pallas`` by their
names and imports neither.  A new kind is a record here, its residual
method on ``Transformer`` and its op under ``ops/`` (docs/serving.md,
"Adding a layer kind").

What stays out of the table is how a kind that keeps K/V WRITES them (by
position, by head, as a ring): that is the cache's to know
(``generation.decode_block``'s storage arms).
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Callable

import jax.numpy as jnp

if TYPE_CHECKING:
    from .transformer import LayerSpec, TransformerConfig

Shapes = dict[str, tuple[int, ...]]


@dataclasses.dataclass(frozen=True)
class RoundKernel:
    """The kernel a kind's decode round has of its own
    (``transformer.round_arm`` chooses between it and the plain form)."""
    # the module of ops/pallas that holds it (its ``fits`` says which
    # shapes it takes)
    module: str
    # what the plain form is called: ``dense``, the einsums against the
    # part as it lies, read whole whatever the lanes hold; ``plain``,
    # ops/ssd.py's elementwise pass over every lane's matrix
    plain: str
    # (q shape, part shape) as the chooser is handed them -> the arguments
    # of the module's ``fits``; None: no fit
    shapes: Callable[[tuple, tuple], tuple | None]
    # "": a round the kernel cannot take runs the plain form.  Else the
    # round's one token on ONE TPU device whose shapes the kernel does not
    # take is REFUSED with this sentence (``{kernel}`` the module)
    refusal: str = ""
    # the kernel moves the lanes that hold a request and no other, so a
    # serving round tells the layer which they are (``decode_block``'s
    # ``counts``; ``serving._mask_layers``)
    live_lanes: bool = False


@dataclasses.dataclass(frozen=True)
class Mixer:
    """One kind of ``LayerSpec.mixer``.  ``config`` below is the model's
    ``TransformerConfig``."""
    # what a decode cache keeps of the layer.  ``kv``: K and V by position
    # (by head for a sparse layer, a ring for a window: the cache's
    # storage arms).  ``state``: arrays of a fixed size and no K/V, which
    # cannot be rolled back.  ``latent``: one row a position
    keeps: str
    # (config, spec) -> the layer's norms' gains and its mixer's weights by
    # suffix, in the order the store keeps them (biases and the
    # feed-forward branch are ``Transformer.block_shapes``'s to add)
    shapes: Callable[[TransformerConfig, LayerSpec], Shapes]
    # config -> what ONE slot keeps of a ``state`` layer, a (shape, dtype)
    # an array; None for the other kinds
    state: Callable[[TransformerConfig], tuple] | None = None
    # its states include a float32 matrix a head (what a round's
    # ``serve.linear.*`` counters count: a conv layer's register is none)
    matrix: bool = False
    # behind a short convolution, two states (the convolution's register
    # and the matrix)
    recurrent: bool = False
    # (config, seq) -> the mixer's products a token over a sequence of
    # ``seq``, before ``flops_per_sample``'s multiplier: scores and values
    # over ``seq`` keys of d_model unless the kind says otherwise
    products: Callable[[TransformerConfig, int], float] = (
        lambda c, seq: c.d_model * seq)
    # config -> the widest activation its branch makes a token where that
    # can pass the feed-forward branch's (``serving._prefills_whole``); 0:
    # none wider than d_model
    widest: Callable[[TransformerConfig], int] = lambda c: 0
    # the ``Transformer`` method that runs the WHOLE mixer branch, by name;
    # None: q, k, v -> ``mix`` -> ``attn_residual``.  A ``state`` kind's
    # takes (params, prefix, h, states or None, counts) and returns (h,
    # states); a ``latent`` kind's a whole sequence (params, prefix, h) and
    # returns (h, rows)
    residual: str | None = None
    round_kernel: RoundKernel | None = None
    # its admission programs are dear to compile or to run short, so the
    # server keeps them FEW (``serving._builds_few``)
    few_programs: bool = False


def _between_norms(c, weights: Shapes, after: Shapes | None = None) -> Shapes:
    """``weights`` between the layer's two norms' gains, ``after`` behind
    them: the store's order (``init_params`` draws in it)."""
    return {"ln1/scale": (c.d_model,), **weights,
            "ln2/scale": (c.d_model,), **(after or {})}


def _attention_shapes(c, spec) -> Shapes:
    """q, k, v and the output projection of a softmax, sparse or linear
    layer, and what its ``spec`` adds."""
    kv_dim = (spec.kv_heads or c.kv_heads) * c.head_dim
    extra: Shapes = {}
    if spec.qk_norm == "all":
        extra.update({"attn/q_norm/scale": (c.attn_dim,),
                      "attn/k_norm/scale": (kv_dim,)})
    elif spec.qk_norm:
        extra.update({"attn/q_norm/scale": (c.head_dim,),
                      "attn/k_norm/scale": (c.head_dim,)})
    if spec.gate:
        extra["attn/wg"] = (c.d_model, c.attn_dim)
    if spec.out_norm:
        extra["attn/o_norm/scale"] = (c.head_dim,)
    return _between_norms(c, {"attn/wq": (c.d_model, c.attn_dim),
                              "attn/wk": (c.d_model, kv_dim),
                              "attn/wv": (c.d_model, kv_dim),
                              "attn/wo": (c.attn_dim, c.d_model)}, extra)


def _conv_shapes(c, spec) -> Shapes:
    # (B, C, x) come from one projection; a kernel tap is a row, so that it
    # lies along the lanes like the channels it scales
    return _between_norms(c, {"conv/in_proj": (c.d_model, 3 * c.d_model),
                              "conv/kernel": (c.conv_kernel, c.d_model),
                              "conv/out_proj": (c.d_model, c.d_model)})


def _kda_shapes(c, spec) -> Shapes:
    # the gates' inner width is a head's (the published layer's)
    rank = c.head_dim
    return _between_norms(c, {
        "attn/wq": (c.d_model, c.attn_dim),
        "attn/wk": (c.d_model, c.attn_dim),
        "attn/wv": (c.d_model, c.attn_dim),
        "attn/conv_q": (c.conv_kernel, c.attn_dim),
        "attn/conv_k": (c.conv_kernel, c.attn_dim),
        "attn/conv_v": (c.conv_kernel, c.attn_dim),
        "attn/decay/wa": (c.d_model, rank),
        "attn/decay/wb": (rank, c.attn_dim),
        "attn/decay/a_log": (c.n_heads,),
        "attn/decay/dt_bias": (c.attn_dim,),
        "attn/gate/wa": (c.d_model, rank),
        "attn/gate/wb": (rank, c.attn_dim),
        "attn/beta/w": (c.d_model, c.n_heads),
        "attn/o_norm/scale": (c.head_dim,),
        "attn/wo": (c.attn_dim, c.d_model)})


def _gdn_widths(c) -> tuple[int, int]:
    """(all heads' keys, all heads' values) of a gdn layer."""
    keys, values = c.delta_dims
    return c.n_heads * keys, c.n_heads * values


def _gdn_channels(c) -> int:
    """The channels through a gdn layer's convolutions: q, k and v side by
    side."""
    keys, values = _gdn_widths(c)
    return 2 * keys + values


def _gdn_shapes(c, spec) -> Shapes:
    # key heads and value heads of their own sizes; the decay a head
    # straight from the stream; the output gate full-rank
    keys, values = _gdn_widths(c)
    return _between_norms(c, {
        "attn/wq": (c.d_model, keys),
        "attn/wk": (c.d_model, keys),
        "attn/wv": (c.d_model, values),
        "attn/conv_q": (c.conv_kernel, keys),
        "attn/conv_k": (c.conv_kernel, keys),
        "attn/conv_v": (c.conv_kernel, values),
        "attn/decay/w": (c.d_model, c.n_heads),
        "attn/decay/a_log": (c.n_heads,),
        "attn/decay/dt_bias": (c.n_heads,),
        "attn/beta/w": (c.d_model, c.n_heads),
        "attn/wz": (c.d_model, values),
        "attn/o_norm/scale": (c.delta_dims[1],),
        "attn/wo": (values, c.d_model)})


def _ssm_projected(c) -> int:
    """The columns of an ssm layer's one input projection: the gate, the
    convolution's channels and a step a head, side by side."""
    return sum(c.ssm_dims) + c.ssm_heads


def _ssm_shapes(c, spec) -> Shapes:
    # (z, xBC, dt) come from one projection; a kernel tap is a row
    inner, conv = c.ssm_dims
    return _between_norms(c, {
        "ssm/in_proj": (c.d_model, _ssm_projected(c)),
        "ssm/conv/kernel": (c.conv_kernel, conv),
        "ssm/conv/bias": (conv,),
        "ssm/decay/a_log": (c.ssm_heads,),
        "ssm/decay/dt_bias": (c.ssm_heads,),
        "ssm/skip": (c.ssm_heads,),
        "ssm/norm/scale": (inner,),
        "ssm/out_proj": (inner, c.d_model)})


def _latent_shapes(c, spec) -> Shapes:
    # wq: every head's query, its own part then the shared one (a pair with
    # a norm between under ``q_latent``); wkv_a: the latent and the shared
    # key part; wkv_b: every head's key part and value from the normed
    # latent
    q_dim = c.n_heads * (c.head_dim + c.qk_shared)
    query = ({"attn/wq_a": (c.d_model, c.q_latent),
              "attn/q_norm/scale": (c.q_latent,),
              "attn/wq_b": (c.q_latent, q_dim)} if c.q_latent
             else {"attn/wq": (c.d_model, q_dim)})
    return _between_norms(c, {
        **query,
        "attn/wkv_a": (c.d_model, c.kv_latent + c.qk_shared),
        "attn/kv_norm/scale": (c.kv_latent,),
        "attn/wkv_b": (c.kv_latent, 2 * c.attn_dim),
        "attn/wo": (c.attn_dim, c.d_model)})


# What one slot keeps of a state layer.  A matrix lies by head as its rule
# takes it, whatever its sizes.  Where a gdn layer's Dv fills no whole
# registers (192 of 256 lanes) the device pads it, a third more bytes a
# round; the shapes that would not be padded ([Dk, H * Dv], [H, Dk * Dv])
# cost more than they save in plain XLA: a round then spreads k and q over
# the value lanes as arrays of the state's own size (523 to 560 MB moved a
# layer against 112 at 12 lanes x 30 heads x [96, 192], compiled for a v5e;
# PERF.md section 6, PR 50).  An ssm layer's last axis at 64 heads of 64 and
# a state of 128 is whole registers: nothing is padded.
def _register(c, width: int) -> tuple:
    """The last ``conv_kernel - 1`` inputs of a short convolution over
    ``width`` channels, in the model's dtype."""
    return ((c.conv_kernel - 1, width), c.dtype)


def _head_matrix(c) -> tuple:
    return ((c.n_heads, c.head_dim, c.head_dim), jnp.float32)


def _gdn_state(c) -> tuple:
    return (_register(c, _gdn_channels(c)),
            ((c.n_heads, *c.delta_dims), jnp.float32))


def _ssm_state(c) -> tuple:
    return (_register(c, c.ssm_dims[1]),
            ((c.ssm_heads, c.ssm_head_dim, c.ssm_state), jnp.float32))


MIXERS: dict[str, Mixer] = {
    "softmax": Mixer(
        keeps="kv", shapes=_attention_shapes,
        # q [B, T, H, D] against K or V [B, M, KV / pack, pack * D]: the
        # query rows a row of heads meets are H / KV' (pack * G) of the
        # part's width.  K and V are read once, a block of positions at a
        # time, and no block past a lane's length
        round_kernel=RoundKernel(
            "full_decode", "dense",
            lambda q, part: None if q[2] % part[2] else (
                (q[0], part[2], q[2] // part[2], part[3]), part))),
    "sparse": Mixer(keeps="kv", shapes=_attention_shapes),
    "linear": Mixer(
        keeps="state", shapes=_attention_shapes, matrix=True,
        state=lambda c: (_head_matrix(c),)),
    "conv": Mixer(
        keeps="state", shapes=_conv_shapes, residual="conv_residual",
        # its last gated inputs
        state=lambda c: (_register(c, c.d_model),)),
    "kda": Mixer(
        keeps="state", shapes=_kda_shapes, residual="kda_residual",
        matrix=True, recurrent=True, few_programs=True,
        # (q, k and v go through its convolutions side by side)
        state=lambda c: (_register(c, 3 * c.attn_dim), _head_matrix(c)),
        widest=lambda c: 3 * c.attn_dim,
        # three products with its [D, D] states, which do not grow with S
        products=lambda c, seq: 1.5 * c.attn_dim * c.head_dim),
    "latent": Mixer(
        keeps="latent", shapes=_latent_shapes, residual="latent_residual",
        few_programs=True,
        # its heads' own width and the shared key part for the scores, its
        # heads' for the values
        products=lambda c, seq: seq * (c.attn_dim
                                       + c.n_heads * c.qk_shared / 2),
        # q [B, T, H, W] absorbed, as wide as the rows [B, M, W] of its
        # part: every live row read once for all heads.  The einsums cost
        # eight times the kernel a round (4.5 ms a layer against 0.54 at 64
        # lanes x 16,384 positions; PERF.md, PR 47), and a server that slow
        # would only read as a low roofline: refused
        round_kernel=RoundKernel(
            "latent_decode", "dense",
            lambda q, part: ((q[0],) + tuple(q[2:]), part),
            refusal="a latent layer's decode round on a TPU runs "
                    "ops/pallas/latent_decode.py, which takes heads in "
                    "16s, rows of whole 128-lane registers and a cache of "
                    "whole blocks of {kernel.BLOCK} positions")),
    "gdn": Mixer(
        keeps="state", shapes=_gdn_shapes, residual="gdn_residual",
        matrix=True, recurrent=True, few_programs=True, state=_gdn_state,
        widest=_gdn_channels,
        # three products with its [Dk, Dv] states
        products=lambda c, seq: 1.5 * c.n_heads * math.prod(c.delta_dims)),
    "ssm": Mixer(
        keeps="state", shapes=_ssm_shapes, residual="ssm_residual",
        matrix=True, recurrent=True, few_programs=True, state=_ssm_state,
        widest=_ssm_projected,
        # two products with its [P, N] states: the write, the read
        products=lambda c, seq: c.ssm_dims[0] * c.ssm_state,
        # x [B, T, H, P] against the matrix [B, H, P, N]: the matrix of the
        # lanes that decode, updated where it lies, an idle lane's neither
        # read nor written
        round_kernel=RoundKernel("ssd_decode", "plain",
                                 lambda q, part: (q, part),
                                 live_lanes=True)),
}
