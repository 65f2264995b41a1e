"""Decoder-only Transformer LM, TPU-first.

The framework's flagship long-context model.  The reference has no model or
sequence dimension at all (SURVEY.md §5) — this model is what makes the
mesh's ``tensor`` and ``seq`` axes real:

- tensor parallelism: Megatron-style column-parallel wq/wk/wv/w1 and
  row-parallel wo/w2 (one all-reduce per residual branch, inserted by XLA
  from the shardings);
- sequence parallelism: activations sharded [batch, seq, d] with seq on the
  ``seq`` axis; attention either all-gathers K/V (default GSPMD path) or
  runs ring attention (ops/ring_attention.py) with K/V blocks rotating over
  the ring — O(seq/N) memory per device;
- RoPE positions (no learned position table) so sequence shards are
  position-exact regardless of placement;
- bfloat16 weights/activations, float32 MXU accumulation, float32 softmax.

Parameters are a flat named store like every model here, so the same
transformer flows through the PS protocol, checkpointing, and ShardedTrainer.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import itertools
import math
from functools import partial
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..ops.pallas import ATTN_KERNEL_KEPT
from .mixers import MIXERS, Mixer
from .moe import moe_expert_weight_spec
from .quant import QTensor, wdot

Array = jax.Array


FFN_KINDS = ("mlp", "moe", "experts")
# What a kind of mixer is stands in models/mixers.py, a record each; the
# three names below are read off that table
MIXER_KINDS = tuple(MIXERS)
# the mixers that keep a fixed-size STATE in a decode cache and no K/V
STATE_MIXERS = tuple(kind for kind, mixer in MIXERS.items()
                     if mixer.keeps == "state")
# the recurrent mixers behind a short convolution (the two delta rules and
# the state-space layer): a convolution's register and a matrix a layer
RECURRENT_MIXERS = tuple(kind for kind, mixer in MIXERS.items()
                         if mixer.recurrent)
# jax.ad_checkpoint name of a layer's mixer branch as it joins the residual
# stream (Transformer._residual); Transformer._remat_policy may keep it
MIXER_OUT = "mixer_out"


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN (Peng et al., arXiv:2309.00071) as a model's ``rope_scaling``
    states it: rotary pairs that turn more than ``beta_fast`` times over
    the ``original_max`` positions the model was first trained on keep
    their frequency, those that turn fewer than ``beta_slow`` times are
    slowed ``factor`` times, and the pairs between are blended along a
    linear ramp.  ``mscale`` / ``mscale_all_dim`` give the two gains of
    the method: one on the rotated parts (:attr:`rotary_gain`), one on the
    scores (:attr:`softmax_gain`)."""
    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _mscale(self, weight: float) -> float:
        if self.factor <= 1:
            return 1.0
        return 0.1 * weight * math.log(self.factor) + 1.0

    @property
    def rotary_gain(self) -> float:
        """What cos and sin are multiplied by."""
        if self.mscale and self.mscale_all_dim:
            return self._mscale(self.mscale) / self._mscale(
                self.mscale_all_dim)
        return self._mscale(1.0)

    @property
    def softmax_gain(self) -> float:
        """What the scores' scale is multiplied by: the square of the
        all-dimensions gain (1 without ``mscale_all_dim``)."""
        if not self.mscale_all_dim:
            return 1.0
        return self._mscale(self.mscale_all_dim) ** 2

    def ramp_ends(self, dim: int, theta: float) -> tuple[int, int]:
        """(low, high): the pair the blend starts at and the one it ends
        at, of ``dim // 2`` pairs."""
        def turns_at(turns: float) -> float:
            return (dim * math.log(self.original_max / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        return (max(math.floor(turns_at(self.beta_fast)), 0),
                min(math.ceil(turns_at(self.beta_slow)), dim - 1))

    def frequencies(self, dim: int, theta: float) -> np.ndarray:
        """The ``dim // 2`` pairs' angles a position, float32."""
        pair = np.arange(dim // 2, dtype=np.float64)
        plain = theta ** (-pair / (dim // 2))
        low, high = self.ramp_ends(dim, theta)
        slowed = np.clip((pair - low) / ((high - low) or 0.001), 0.0, 1.0)
        return (plain * (1.0 - slowed) + plain / self.factor * slowed
                ).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of a model's pattern: what its attention sees and what
    its feed-forward branch is.  ``TransformerConfig.pattern`` holds one
    period; layer ``i`` is ``pattern[i % len(pattern)]``, counted from the
    end of ``TransformerConfig.prologue`` where the model has one."""
    # 0: every earlier position; W: the last W (query i sees key j where
    # 0 <= i - j < W), and a cache slot of this layer holds W positions
    window: int = 0
    # rotary positions on q and k.  False: the layer has no position
    # signal of its own (and never has one under pos_emb="learned", where
    # positions enter at the embedding)
    rope: bool = True
    # mlp: the dense MLP.  moe: the capacity-dropping Switch/top-k layer
    # (models/moe.py MoELayer; training fixtures).  experts: dropless
    # sort-and-group routing (models/moe.py dropless_experts) over experts
    # of ``mlp_act``'s form and ``d_expert``'s width (``d_ff``'s where
    # that is 0); what the router reads and how it scores and gates are
    # the config's ``moe_router_input`` and ``moe_score``
    ffn: str = "mlp"
    # what mixes positions.  softmax: causal attention over every earlier
    # position (or the window's).  sparse: the same while the context is
    # shorter than ``config.sparse.dense_len``, from there on over a
    # SELECTION of key blocks (ops/sparse_attention.py); its cache keeps
    # compressed keys beside K/V.  linear: a per-head decayed outer-product
    # state (ops/linear_attention.py): no K/V by position, a fixed-size
    # state instead.  conv: a gated depthwise causal convolution of
    # ``config.conv_kernel`` taps (ops/short_conv.py): no heads, no K/V,
    # its state the last ``conv_kernel - 1`` gated inputs.  kda: a per-head
    # [D, D] state decayed a key channel and corrected by the delta rule
    # (ops/delta_attention.py) behind short convolutions of q, k and v, with
    # a low-rank decay gate and output gate: TWO states, the convolutions'
    # shift register and the matrix.  gdn: the gated delta rule with ONE
    # decay a head (Yang et al.'s gated DeltaNet; the arm of
    # ops/delta_attention.py that forms no [C, C, D] term), key heads of
    # ``config.delta_key_dim`` and value heads of ``config.delta_value_dim``
    # beside the softmax layers' ``head_dim``, a decay projection a head, a
    # full-rank silu output gate and, under ``config.delta_neg_eigval``, a
    # write strength of up to 2; the same two states, the matrix
    # [H, Dk, Dv].  ssm: a state-space layer in its dual form (Mamba-2;
    # ops/ssd.py): ``config.ssm_heads`` heads of ``config.ssm_head_dim``
    # behind ONE projection and one convolution WITH a bias, a step and so
    # a decay a head and position, keys and queries of ``config.ssm_state``
    # shared by the heads of each of ``config.ssm_groups`` groups, a skip a
    # head, and the norm AFTER the silu gate; the same two states, the
    # matrix [H, P, N].  latent: causal attention whose K and V are expanded
    # from ONE normed latent of ``config.kv_latent`` a position, with a
    # key part of ``config.qk_shared`` all heads share beside it (rotated
    # under ``config.latent_rope``, else without rotary anywhere); its
    # cache keeps that row, not K/V
    mixer: str = "softmax"
    # K/V heads of this layer; 0 = the config's
    kv_heads: int = 0
    # RMS norm (a learned gain each) of q and of k, before rotary.  True:
    # on every head, a gain of ``head_dim``; "all": over ALL heads' channels
    # at once, a gain of ``attn_dim`` for q and of the K/V heads' width for
    # k (the Olmo block's)
    qk_norm: bool | str = False
    # the attention's output times sigmoid(W_g x), x its normed input,
    # before the output projection
    gate: bool = False
    # RMS norm (a learned gain) on every head of the attention's output
    out_norm: bool = False

    def __post_init__(self):
        if self.ffn not in FFN_KINDS:
            raise ValueError(f"ffn must be one of {FFN_KINDS}, "
                             f"got {self.ffn!r}")
        if self.mixer not in MIXER_KINDS:
            raise ValueError(f"mixer must be one of {MIXER_KINDS}, "
                             f"got {self.mixer!r}")
        if self.mixer != "softmax" and self.window:
            raise ValueError("a window belongs to a softmax layer")
        if self.qk_norm not in (False, True, "all"):
            raise ValueError(f"qk_norm is False, True (a head) or 'all', "
                             f"got {self.qk_norm!r}")
        if (self.kind.residual is not None
                and (self.kv_heads or self.qk_norm or self.gate
                     or self.out_norm)):
            raise ValueError(
                "a conv layer has no heads, a kda, gdn or ssm layer norms and "
                "gates its output always and a latent layer has one latent "
                "for every "
                "head: kv_heads, qk_norm, gate and out_norm belong to "
                "softmax, sparse and linear layers")
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {self.window}")

    @property
    def kind(self) -> Mixer:
        """What the layer's mixer IS: its record of models/mixers.py."""
        return MIXERS[self.mixer]

    @property
    def moves_live_lanes(self) -> bool:
        """Its decode round moves the states of the lanes that hold a
        request and no other (``RoundKernel.live_lanes``)."""
        kernel = self.kind.round_kernel
        return kernel is not None and kernel.live_lanes

    @property
    def reads_live_lanes(self) -> bool:
        """A serving round tells the layer which lanes hold a request
        (``decode_block``'s ``counts``): its kernel moves those lanes'
        states alone, or its experts route those lanes' tokens alone."""
        return self.moves_live_lanes or self.ffn == "experts"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 32000
    d_model: int = 512
    n_heads: int = 8
    # size of one attention head; 0 = d_model // n_heads.  Where it is
    # given, wq maps d_model -> n_heads * head_dim and wo back
    head_dim: int = 0
    # Grouped-query attention: number of K/V heads (0 = n_heads, i.e. MHA;
    # 1 = multi-query).  Shrinks wk/wv and the decode KV cache by
    # n_heads/n_kv_heads; each K/V head serves a group of query heads.
    n_kv_heads: int = 0
    n_layers: int = 6
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: object = jnp.bfloat16
    rope_theta: float = 10000.0
    # Rematerialization: recompute each layer's activations in the backward
    # pass instead of saving them (jax.checkpoint) — O(1) layers of
    # residuals instead of O(L), the standard long-context memory/FLOPs
    # trade on TPU (HBM is the bottleneck, MXU FLOPs are cheap).
    remat: bool = False
    # What remat may keep: "full" recomputes a layer's arithmetic (O(1)
    # residuals, ~33% extra FLOPs — the forward again) and keeps, besides
    # the layer's input, only what is not cheap arithmetic to repeat (the
    # two cases below); "dots" applies
    # jax.checkpoint_policies.dots_with_no_batch_dims_saveable — the
    # projection/MLP matmul outputs (dot_generals with no batch dims) are
    # SAVED and only the attention score/value einsums (batch dims B, H —
    # the O(S^2) memory hogs) plus elementwise ops are recomputed: the
    # recompute overhead drops from a whole extra forward (~33% of the
    # fwd+bwd budget) to the attention einsums alone (~5% at S=d=1024 —
    # 4·S·d² vs the 72·S·d² + 12·S²·d fwd+bwd per-layer matmul total),
    # for O(L·S·d) saved activations instead of O(1) residuals.
    # On a mesh whose ``tensor`` axis is larger than 1, "full" keeps ONE
    # value a layer across the backward: the mixer branch's output as it
    # joins the residual stream (MIXER_OUT: after the row-parallel output
    # projection's all-reduce, the bias and the cast to ``dtype``).  "full"
    # means "recompute a layer's arithmetic", not "pay the link twice":
    # without it the remat forward repeats that all-reduce, which nothing
    # hides, because the FFN branch needs its result.  The price is
    # B/rows x S x d_model x sizeof(dtype) bytes a layer and chip (rows =
    # the mesh's data x fsdp; 84 MB at 32 x 1,024 x 1,280 in bf16), and the
    # output projection's dot is not recomputed either.
    # Wherever the blockwise kernel attends (ops/pallas/fused_attention.py:
    # the arms ``kernel`` and ``sharded_kernel`` of ``default_arm``, and
    # Ulysses where a device's share takes the kernel), "full" also keeps
    # the kernel's two results that its backward reads, on any mesh and
    # without one (ATTN_KERNEL_KEPT): the output ``o``, B/rows x S x
    # d_model/tensor in ``dtype`` a layer and chip, and the rows'
    # logsumexp, B/rows x H/tensor x S in float32.  They are the one piece
    # of a layer that costs a kernel call to rebuild (q, k and v are a
    # norm and one product away); without them the remat forward runs the
    # whole forward kernel a second time, 6% of a one-chip step.  The
    # price at 64 x 1,024: 134.2 MB + 4.2 MB a layer for GPT-2 medium on
    # one chip (24 layers: 3.3 GB, as much again as the layer inputs
    # whole-layer remat keeps anyway), 41.9 MB + 1.3 MB for GPT-2 large on
    # fsdp 2 x tensor 2 (36 layers: 1.6 GB a chip).  Where another arm
    # attends (the einsum, ``blockwise``, ring, a latent, sparse, linear or
    # state mixer) the names do not exist and nothing more is kept.
    # Who trains at the edge of memory takes a smaller micro-batch; there
    # is no switch.
    remat_policy: str = "full"
    # Chunked cross-entropy: compute the LM head + softmax in sequence
    # chunks of this many positions (0 = whole sequence at once).  Peak
    # logits memory drops from O(S * vocab) to O(chunk * vocab) — at
    # vocab 32k, seq 1024, batch 8 that is ~1 GB -> ~32 MB of f32 logits —
    # with the chunk recomputed in the backward pass (jax.checkpoint).
    # Must divide max_seq.
    loss_chunk: int = 0
    # Mixture-of-experts: every ``moe_every``-th layer (1-based; 0 = dense
    # everywhere) swaps its FFN for a Switch-routed MoE (models/moe.py) with
    # ``moe_experts`` experts; the load-balancing aux loss is added to the
    # LM loss scaled by ``moe_aux_coef``.
    moe_every: int = 0
    moe_experts: int = 8
    # The layer pattern, one period of it (see LayerSpec).  Empty = what
    # the flags above describe: every layer full attention with the dense
    # MLP, each ``moe_every``-th one a ``moe`` layer.  GPT-2 is the
    # one-entry pattern under pos_emb="learned"; a model that alternates
    # window and full layers, or routes every layer, writes its period
    # here.  ``scan_layers`` scans over whole periods.
    pattern: tuple = ()
    # Leading layers OUTSIDE the period, a ``LayerSpec`` each (a model
    # whose first layers keep a dense FFN before its expert layers
    # begin): layer ``i`` is ``prologue[i]`` while there is one, and the
    # pattern's periods start after them.  They run unrolled.
    prologue: tuple = ()
    # experts per token: 1 = Switch (default), 2 = Mixtral-style top-2
    moe_top_k: int = 1
    # width of one of an ``experts`` layer's experts; 0 = ``d_ff``'s (a
    # model whose dense layers and experts differ in width gives both)
    d_expert: int = 0
    # An ``experts`` layer's router.  What it reads: ``attn``, the
    # attention's normed input (the router stands BEFORE attention), or
    # ``ffn``, the feed-forward branch's own normed input.  How it scores:
    # ``softmax`` takes the top-k logits and gates by the softmax over
    # them; ``sigmoid`` scores every expert sigmoid(logit), selects the
    # top-k of score + ``moe/router/bias`` (a stored [E] vector, there
    # under ``moe_expert_bias``; it enters the SELECTION only) and gates
    # by the chosen scores over their sum, times ``moe_route_scale``
    moe_router_input: str = "attn"
    moe_score: str = "softmax"
    moe_expert_bias: bool = False
    moe_route_scale: float = 1.0
    # The experts an ``experts`` layer's weights HOLD, ``(first, count)``:
    # this chip's share of an expert-parallel stage.  The router keeps its
    # ``moe_experts`` outputs, its bias and its top-k, and gates are normed
    # over the chosen (held or not); ``moe/w1|w2|w3`` are [count, ...] and
    # the layer's result is the held experts' part of the sum (nothing
    # stands in for the other ranks or their exchange).  Empty: all
    moe_held: tuple = ()
    # shared experts beside the routed ones of an ``experts`` layer: ONE
    # MLP of ``mlp_act``'s form and this many experts' width on every
    # token (``moe/shared/w1|w2|w3``), added ungated to the routed part
    moe_shared_experts: int = 0
    # taps of a ``conv`` layer's kernel, of a ``kda`` or ``gdn`` layer's
    # three and of an ``ssm`` layer's one
    conv_kernel: int = 3
    # an ``ssm`` layer's sizes: heads and their width (the inner width is
    # their product), the width of the keys and queries a group's heads
    # share, which is the state's last axis, and the number of groups
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 0
    # what a softmax layer's scores q . k are multiplied by; 0: the usual
    # ``head_dim ** -0.5``.  Taken on the QUERY as ``qkv`` makes it, so every
    # form of the attention behind (the einsum, the blockwise kernels, an
    # extension, a round against a cache) keeps its one scale
    attn_scale: float = 0.0
    # a ``gdn`` layer's key and value head sizes (0: ``head_dim``'s), and
    # whether its write strength is doubled, 2 sigmoid(.), so that I - beta
    # k k^T has an eigenvalue in (-1, 1) (``linear_allow_neg_eigval``)
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_neg_eigval: bool = False
    # a ``latent`` layer's compressed K/V: the width of the normed latent a
    # position keeps, and of the key part every head shares beside it
    kv_latent: int = 0
    qk_shared: int = 0
    # the rank of a latent layer's QUERY.  0: one matrix ``attn/wq``.
    # r > 0: the pair ``attn/wq_a`` [d_model, r] and ``attn/wq_b`` [r,
    # heads x (head_dim + qk_shared)] with an RMS norm of r between them
    # (``attn/q_norm/scale``)
    q_latent: int = 0
    # rotary positions on the SHARED parts of a latent layer: on every
    # head's last ``qk_shared`` query channels and on the row's shared key
    # part BEFORE the row is written, so a cache and a prefix store keep
    # rows rotated at their absolute positions.  False: no rotary anywhere
    latent_rope: bool = False
    # YaRN on the model's rotary frequencies (a :class:`RopeScaling`), and
    # its ``softmax_gain`` on a latent layer's scores; None: plain rotary
    rope_scaling: object = None
    # An ``experts`` layer's selection under a GROUP LIMIT (sigmoid scores):
    # the router's experts lie in ``moe_groups`` groups of neighbours, a
    # group scores the sum of its two best score + bias, and only experts
    # of the ``moe_groups_kept`` best groups can be chosen.  1 and 1: none
    moe_groups: int = 1
    moe_groups_kept: int = 1
    # Scan over layers: store block weights stacked with a leading [L]
    # axis (``blocks/<suffix>``) and run the layer loop as one
    # ``lax.scan`` body traced ONCE, instead of n_layers Python-unrolled
    # copies.  Compile time and HLO size stop growing with depth (the
    # 24-layer flagship's jit drops from minutes to one layer's worth);
    # the trade is that XLA cannot specialize or fuse across layer
    # boundaries.  Requires homogeneous layers (no MoE interleaving).
    scan_layers: bool = False
    moe_capacity: float = 1.25
    moe_aux_coef: float = 0.01
    # --- GPT-2-family compatibility knobs (models/hf.py interop).  The
    # defaults are the native architecture (RoPE + RMSNorm, no biases);
    # the flags exist so pretrained-checkpoint families with learned
    # positions / LayerNorm / biased projections convert losslessly.
    pos_emb: str = "rope"         # rope | learned ("embed/pos" table)
    norm: str = "rms"             # rms | layernorm (mean-centering + bias)
    bias: bool = False            # biases on attn/mlp projections
    norm_eps: float = 1e-6
    # where a branch's norm stands.  pre: on the branch's INPUT
    # (x + f(norm(x))).  post: on its OUTPUT before the residual add
    # (x + norm(f(x)): the branch, and an ``experts`` layer's router, read
    # the stream itself); ``ln1`` / ``ln2`` are the same gains either way
    norm_placement: str = "pre"
    # gelu: w2(gelu(w1 x)); swiglu: w2(silu(w1 x) * (w3 x)) — the
    # LLaMA-family gated MLP (w1 = gate_proj, w3 = up_proj); reglu: the
    # same gate with relu.  An ``experts`` layer's experts take this form
    mlp_act: str = "gelu"
    # block selection of the ``sparse`` layers (ops/sparse_attention.py
    # SparseSpec); None where the pattern has none
    sparse: object = None
    # muP scalars: the embedding times ``embed_scale``, every residual
    # branch times ``residual_scale``, the head's input times
    # ``logit_scale``
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    def __post_init__(self):
        if self.pos_emb not in ("rope", "learned"):
            raise ValueError(
                f"pos_emb must be 'rope' or 'learned', got {self.pos_emb!r}")
        if self.norm not in ("rms", "layernorm"):
            raise ValueError(
                f"norm must be 'rms' or 'layernorm', got {self.norm!r}")
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(f"norm_placement must be 'pre' or 'post', got "
                             f"{self.norm_placement!r}")
        if self.mlp_act not in ("gelu", "swiglu", "reglu"):
            raise ValueError(f"mlp_act must be 'gelu', 'swiglu' or "
                             f"'reglu', got {self.mlp_act!r}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(f"remat_policy must be 'full' or 'dots', "
                             f"got {self.remat_policy!r}")
        if not self.head_dim:
            if self.d_model % self.n_heads:
                raise ValueError("d_model must divide by n_heads (or give "
                                 "head_dim)")
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        object.__setattr__(self, "pattern", tuple(self.pattern))
        object.__setattr__(self, "prologue", tuple(self.prologue))
        if any(not isinstance(spec, LayerSpec)
               for spec in self.pattern + self.prologue):
            raise ValueError("pattern and prologue hold LayerSpec entries")
        if self.pattern and self.moe_every:
            raise ValueError("give the layers as a pattern or by "
                             "moe_every, not both")
        if self.moe_router_input not in ("attn", "ffn"):
            raise ValueError(f"moe_router_input must be 'attn' or 'ffn', "
                             f"got {self.moe_router_input!r}")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score must be 'softmax' or 'sigmoid', "
                             f"got {self.moe_score!r}")
        if self.moe_expert_bias and self.moe_score != "sigmoid":
            raise ValueError("the stored bias corrects a sigmoid score's "
                             "selection: moe_expert_bias needs "
                             "moe_score='sigmoid'")
        object.__setattr__(self, "moe_held", tuple(self.moe_held))
        if self.moe_held:
            first, count = self.moe_held
            if not (0 <= first and 1 <= count
                    and first + count <= self.moe_experts):
                raise ValueError(
                    f"moe_held={self.moe_held} is (first, count) of the "
                    f"router's {self.moe_experts} experts")
        if ((self.moe_held or self.moe_shared_experts
             or self.norm_placement == "post")
                and any(spec.ffn == "moe" for spec in self.specs)):
            raise ValueError("a share of the experts, a shared expert and "
                             "a norm on a branch's output belong to "
                             "``experts`` layers, not ``moe``")
        mixers = {spec.mixer for spec in self.specs}
        if "sparse" in mixers and self.sparse is None:
            raise ValueError("a sparse layer needs config.sparse")
        if (mixers & {"conv", *RECURRENT_MIXERS}
                and (self.conv_kernel < 2 or self.bias)):
            raise ValueError(f"a conv, kda, gdn or ssm layer has a kernel of "
                             f"2 taps or more and no bias on a projection, "
                             f"got conv_kernel={self.conv_kernel}, "
                             f"bias={self.bias}")
        sizes = (self.ssm_heads, self.ssm_head_dim, self.ssm_state,
                 self.ssm_groups)
        if (min(sizes) < 1 or self.ssm_heads % self.ssm_groups
                or self.pos_emb == "learned") if "ssm" in mixers else any(
                    sizes):
            raise ValueError(
                f"ssm_heads, ssm_head_dim, ssm_state and ssm_groups are an "
                f"ssm layer's: all positive (the groups dividing the heads) "
                f"in a model that has such a layer and no learned positions, "
                f"0 in any other; got {sizes}")
        if self.attn_scale < 0 or (self.attn_scale
                                   and "softmax" not in mixers):
            raise ValueError("attn_scale is a softmax layer's: 0 (head_dim "
                             "** -0.5) or more, and a model that has such a "
                             "layer")
        if min(self.delta_key_dim, self.delta_value_dim) < 0 or (
                "gdn" not in mixers and (self.delta_key_dim
                                         or self.delta_value_dim
                                         or self.delta_neg_eigval)):
            raise ValueError("delta_key_dim, delta_value_dim and "
                             "delta_neg_eigval are a gdn layer's: sizes of "
                             "0 or more, and a model that has such a layer")
        if "gdn" in mixers and self.pos_emb == "learned":
            raise ValueError("a gdn layer has no learned positions")
        if "latent" in mixers and (self.kv_latent < 1 or self.bias
                                   or self.pos_emb == "learned"):
            raise ValueError("a latent layer needs config.kv_latent, and "
                             "has no bias and no learned positions")
        if (self.q_latent or self.latent_rope) and "latent" not in mixers:
            raise ValueError("q_latent and latent_rope are a latent "
                             "layer's")
        if self.q_latent < 0 or (self.latent_rope and (
                self.qk_shared < 2 or self.qk_shared % 2)):
            raise ValueError("q_latent is a rank of 0 or more, and "
                             "latent_rope turns qk_shared's pairs")
        if self.rope_scaling is not None and (
                not isinstance(self.rope_scaling, RopeScaling)
                or self.pos_emb == "learned"
                or (self.rope_scaling.softmax_gain != 1.0
                    and mixers & {"softmax", "sparse", "linear"})):
            raise ValueError("rope_scaling is a RopeScaling of a rotary "
                             "model, and its gain on the scores is a "
                             "latent layer's")
        if not (1 <= self.moe_groups_kept <= self.moe_groups) or (
                self.moe_groups > 1 and (
                    self.moe_score != "sigmoid"
                    or self.moe_experts % self.moe_groups
                    or self.moe_experts // self.moe_groups < 2
                    or any(spec.ffn == "moe" for spec in self.specs))):
            raise ValueError(
                f"moe_groups={self.moe_groups} groups of two or more of "
                f"the router's {self.moe_experts} experts, "
                f"moe_groups_kept={self.moe_groups_kept} of them kept, "
                f"limit a sigmoid selection of an ``experts`` layer")
        if (mixers - {"softmax"} or self.prologue) and self.scan_layers:
            raise ValueError("scan_layers stacks one kind of cache part a "
                             "layer and scans whole periods: sparse, linear, "
                             "conv, kda, gdn, ssm and latent layers and "
                             "a prologue run unrolled")

    @property
    def attn_dim(self) -> int:
        """Width of the attention's inner side: n_heads * head_dim."""
        return self.n_heads * self.head_dim

    @property
    def delta_dims(self) -> tuple[int, int]:
        """(key, value) head sizes of a ``gdn`` layer."""
        return (self.delta_key_dim or self.head_dim,
                self.delta_value_dim or self.head_dim)

    @property
    def ssm_dims(self) -> tuple[int, int]:
        """(inner width H x P, channels through the convolution: the inner
        width and every group's key and query) of an ``ssm`` layer."""
        inner = self.ssm_heads * self.ssm_head_dim
        return inner, inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def query_gain(self) -> float:
        """What ``qkv`` multiplies a softmax layer's queries by so that the
        scores, divided by sqrt(head_dim) wherever they are formed, come out
        times ``attn_scale``; 1 where that is 0."""
        return self.attn_scale * math.sqrt(self.head_dim) or 1.0

    @property
    def latent_row(self) -> int:
        """Lanes of the row a latent layer's cache keeps a position: the
        latent and the shared key part side by side, padded with zeros to
        whole registers of 128 (512 + 64 -> 640).  A row of 576 the device
        would lay with positions along the lanes, and a round would copy
        the part into rows and back (generation.heads_per_row)."""
        return -(-(self.kv_latent + self.qk_shared) // 128) * 128

    @property
    def gated_mlp(self) -> bool:
        return self.mlp_act in ("swiglu", "reglu")

    @property
    def period(self) -> tuple:
        """One period of the layer pattern, given or from the flags."""
        if self.pattern:
            return self.pattern
        if self.moe_every > 0:
            return ((LayerSpec(),) * (self.moe_every - 1)
                    + (LayerSpec(ffn="moe"),))
        return (LayerSpec(),)

    @property
    def specs(self) -> tuple:
        """Every kind of layer the model has: the prologue's, then one
        period's."""
        return self.prologue + self.period

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def held_experts(self) -> tuple[int, int]:
        """(first, count) of the experts an ``experts`` layer's weights
        hold: ``moe_held``, or all of the router's."""
        return self.moe_held or (0, self.moe_experts)

    def layer_spec(self, i: int) -> LayerSpec:
        if i < len(self.prologue):
            return self.prologue[i]
        period = self.period
        return period[(i - len(self.prologue)) % len(period)]

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def kv_groups(self) -> int:
        """Query heads per K/V head."""
        return self.n_heads // self.kv_heads

    def is_moe_layer(self, i: int) -> bool:
        return self.layer_spec(i).ffn == "moe"

    def layers_of(self, mixer: str) -> tuple[int, ...]:
        """The layers whose mixer is ``mixer``, in order."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_spec(i).mixer == mixer)

    def layers_keeping(self, keeps: str) -> tuple[int, ...]:
        """The layers whose mixer keeps ``keeps`` in a decode cache
        (``Mixer.keeps``: ``kv``, ``state`` or ``latent``), in order."""
        return tuple(i for i in range(self.n_layers)
                     if self.layer_spec(i).kind.keeps == keeps)

    @property
    def state_layers(self) -> tuple[int, ...]:
        """The layers whose mixer keeps a state and no K/V, in order."""
        return self.layers_keeping("state")


def scoped(name: str):
    """Run the decorated block of the model under ``jax.named_scope(name)``:
    every device operation it lowers to then carries the block's path in
    its metadata (a trace, an HLO dump).  Metadata only: the compiled
    program and its compile-cache key do not change."""
    def decorate(fn):
        @functools.wraps(fn)
        def in_scope(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return in_scope
    return decorate


@scoped("loss")
def next_token_nll(logits: Array, tokens: Array) -> Array:
    """Mean next-token cross-entropy from full-sequence logits.  The single
    definition shared by Transformer.loss and the pipelined LM
    (parallel/pipeline.py) so the two training modes can never diverge."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    targets = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, targets[..., None].astype(jnp.int32),
                               axis=-1)
    return jnp.mean(nll)


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array,
               eps: float = 1e-5) -> Array:
    """Mean-centering LayerNorm with bias (the GPT-2-family norm)."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rope(x: Array, positions: Array, theta: float = 10000.0,
         scaling: RopeScaling | None = None) -> Array:
    """Rotary position embedding.  x: [..., seq, heads, head_dim]; a pair
    is a channel of the first half and its partner in the second.
    ``scaling``: the frequencies and the gain on cos and sin are YaRN's
    (:class:`RopeScaling`)."""
    head_dim = x.shape[-1]
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                          / (head_dim // 2))
    else:
        freqs = jnp.asarray(scaling.frequencies(head_dim, theta))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]  # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    if scaling is not None and scaling.rotary_gain != 1.0:
        cos, sin = cos * scaling.rotary_gain, sin * scaling.rotary_gain
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def shard_over_batch_and_heads(mesh: Mesh, inner: Callable,
                               batch_axes: tuple[str, ...] = ("data", "fsdp"),
                               head_axis: str = "tensor") -> Callable:
    """A per-device attention composed with a mesh: shard_map over the
    batch and head axes, each device running ``inner`` on its
    full-sequence [B/n, S, H/n, D] block.  Causal attention is independent
    across batch and heads, so this is exact.

    The sequence axis must NOT be sharded here — XLA all-gathers seq-sharded
    activations to satisfy the in_specs; for a real ``seq`` axis use ring or
    Ulysses attention (ops/ring_attention.py) instead.  Heads must divide by
    the ``tensor`` axis when that axis is >1 (shard_map divisibility)."""
    from jax import shard_map

    heads_spec = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    spec = PartitionSpec(batch_axes, None, heads_spec, None)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def sharded(q, k, v):
        return inner(q, k, v)

    n_tp = mesh.shape.get(head_axis, 1)

    def sharded_gqa(q, k, v):
        k, v = prepare_gqa_kv(q, k, v, n_tp)
        return sharded(q, k, v)

    return sharded_gqa


ATTENTION_CHOICES = ("dense", "ring", "ulysses")


def check_attention(name: str) -> None:
    if name not in ATTENTION_CHOICES:
        raise ValueError(
            f"unknown attention {name!r}; options {ATTENTION_CHOICES}")


def select_attention(name: str, mesh: Mesh | None) -> Callable | None:
    """What a model's ``attention_fn`` is for a name of the ``--attention``
    switch.  A name says how the ``seq`` axis is used; which
    implementation runs on a device follows from the shapes it sees there
    (:func:`device_arm`).

    dense   — the sequence is not split: None, the model's default path
              (``Transformer.attend``)
    ring    — split over the mesh's ``seq`` axis, K/V rotated (ppermute)
    ulysses — split over ``seq``, an all-to-all swaps seq <-> heads and a
              device attends whole sequences of its share of the heads by
              :func:`device_arm`"""
    check_attention(name)
    if name == "dense":
        return None
    if mesh is None:
        raise ValueError(f"--attention={name} needs a mesh with a seq axis")
    from ..ops.ring_attention import (make_ring_attention,
                                      make_ulysses_attention)
    make = make_ring_attention if name == "ring" else make_ulysses_attention
    return make(mesh)


def _kernel_backend() -> bool:
    """Whether the default backend compiles the pallas kernels (a TPU);
    anywhere else they would run interpreted, so the default path of
    :meth:`Transformer.attend` keeps to plain XLA there."""
    return jax.default_backend() == "tpu"


def repeat_kv(x: Array, groups: int) -> Array:
    """Expand GQA K/V heads to the query head count: [B, S, KV, D] ->
    [B, S, KV*groups, D], each K/V head repeated for its query group."""
    if groups == 1:
        return x
    return jnp.repeat(x, groups, axis=2)


def expand_gqa(q: Array, k: Array, v: Array) -> tuple[Array, Array]:
    """Repeat grouped-query K/V heads up to the query head count, inferring
    the group size from the shapes.  Attention implementations call this
    THEMSELVES (rather than receiving pre-expanded K/V) so that comm-bound
    paths — ring's ppermute rotation, Ulysses' all-to-all — move the small
    kv_heads-sized tensors and expand only at the math."""
    groups = q.shape[2] // k.shape[2]
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"query heads {q.shape[2]} must divide by "
                         f"kv heads {k.shape[2]}")
    return repeat_kv(k, groups), repeat_kv(v, groups)


def prepare_gqa_kv(q: Array, k: Array, v: Array,
                   n_tp: int) -> tuple[Array, Array]:
    """Validate GQA head grouping and, when the unexpanded kv_heads axis
    cannot be sharded by the ``tensor`` axis (kv_heads % n_tp != 0),
    pre-expand K/V to the query head count so shard_map head specs stay
    satisfiable (MQA + tensor parallelism); all other configs keep the
    small kv_heads-sized transfers.  The single home for this rule,
    shared by the ring, Ulysses and batch-and-head shard_map wrappers."""
    if q.shape[2] % k.shape[2]:
        raise ValueError(f"query heads {q.shape[2]} must divide by "
                         f"kv heads {k.shape[2]}")
    if n_tp > 1 and k.shape[2] % n_tp:
        k, v = expand_gqa(q, k, v)
    return k, v


def causal_attention(q: Array, k: Array, v: Array,
                     window: int = 0) -> Array:
    """Reference einsum attention.  q: [B, S, H, D], k/v: [B, S, H, D] or
    the GQA [B, S, KV, D] (expanded here) -> [B, S, H, D].  float32
    logits/softmax for stability.  ``window`` W > 0 also hides keys W or
    more positions back (query i sees key j where 0 <= i - j < W)."""
    k, v = expand_gqa(q, k, v)
    head_dim = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(head_dim)
    s_q, s_k = q.shape[1], k.shape[1]
    mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_))
    if 0 < window < s_k:
        mask = mask & ~jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), -window)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


def device_arm(q_shape: tuple[int, ...], kv_shape: tuple[int, ...],
               window: int = 0) -> str:
    """Which implementation attends whole sequences ON ONE DEVICE, from
    the shapes the device holds (q [B, S, H, D], k/v [B, S, KV, D]), the
    window that binds (0: none) and the backend; the one place that knows:

    ``kernel``    — the blockwise kernel of ops/pallas/fused_attention.py,
                    which writes no [B, H, S, S] array: on a TPU, where no
                    window binds, q and k cover the same positions, the
                    shape tiles (heads of 64 or 128 that fill rows of 128
                    lanes, positions in blocks of 128) and there is more
                    than one sequence or a long one (a single prompt
                    shorter than ``Transformer.BLOCKWISE_FROM`` is faster
                    through the einsum: PERF.md, PR 30);
    ``blockwise`` — ops/blockwise_attention.py in plain XLA (scores of a
                    block at a time, blocks wholly outside the mask
                    skipped): any other sequence of ``BLOCKWISE_FROM``
                    positions or more;
    ``dense``     — the einsum (:func:`causal_attention`), the rest."""
    blockwise_from = Transformer.BLOCKWISE_FROM
    if not window and _kernel_backend():
        # (pallas is imported where a kernel can run, and only there)
        from ..ops.pallas.fused_attention import fits

        if fits(q_shape, kv_shape) and (q_shape[0] > 1
                                        or q_shape[1] >= blockwise_from):
            return "kernel"
    return "blockwise" if q_shape[1] >= blockwise_from else "dense"


def round_arm(kind: str, q_shape: tuple[int, ...],
              part_shape: tuple[int, ...], part_dtype=jnp.float32,
              devices: int = 1) -> str:
    """Which implementation runs a layer of ``kind`` (a mixer whose record
    has a ``round_kernel``: ``softmax`` for a FULL layer's K or V,
    ``latent``, ``ssm``) against its part of a decode cache spread over
    ``devices`` devices, beside :func:`device_arm` and by its rule (shapes
    and the backend, nothing else); the ONE function that answers for a
    round, for every kind and every caller:

    ``kernel`` — the kind's module of ops/pallas: a decode round's single
                 token a lane (``q_shape[1] == 1``) on ONE TPU device,
                 against an unquantised part whose shape the module's
                 ``fits`` takes (what each kernel reads: the records of
                 models/mixers.py);
    the plain form (``dense``: the einsums against the part as it lies,
    read whole whatever the lanes hold; ``plain``: ``ops/ssd.py``'s
    elementwise pass over every lane's matrix) — a block of several tokens
    (an extension, a speculative verify), a part spread over several
    devices (GSPMD partitions the plain form; it cannot cut a kernel), an
    int8 part, any backend but a TPU, any other shape.

    A kind whose record says so (``refusal``: the latent kind) REFUSES the
    one case in which the plain form would stand in for a kernel that could
    have run: a round's token on one TPU device whose shapes the kernel
    does not take."""
    kernel = MIXERS[kind].round_kernel
    if (q_shape[1] != 1 or devices != 1 or not _kernel_backend()
            or not jnp.issubdtype(part_dtype, jnp.floating)):
        return kernel.plain
    # (pallas is imported where a kernel can run, and only there)
    module = importlib.import_module(
        f"..ops.pallas.{kernel.module}", __package__)
    taken = kernel.shapes(tuple(q_shape), tuple(part_shape))
    if taken is not None and module.fits(*taken):
        return "kernel"
    if kernel.refusal:
        raise ValueError(f"{kernel.refusal.format(kernel=module)}; got "
                         f"queries {q_shape} against rows {part_shape}")
    return kernel.plain


def attend_by(arm: str, q: Array, k: Array, v: Array,
              window: int = 0) -> Array:
    """Run ``arm`` of :func:`device_arm` on a device's own q, k, v; the
    kernel under its own ``attn_kernel`` component."""
    if arm == "kernel":
        from ..ops.pallas.fused_attention import fused_causal_attention

        with jax.named_scope("attn_kernel"):
            return fused_causal_attention(q, k, v)
    if arm == "blockwise":
        from ..ops.blockwise_attention import blockwise_attention

        starts = jnp.zeros((q.shape[0],), jnp.int32)
        return blockwise_attention(q, k, v, starts, window=window)
    return causal_attention(q, k, v, window=window)


_INSTANCE_COUNTER = itertools.count()


class Transformer:
    def __init__(self, config: TransformerConfig,
                 attention_fn: Callable | None = None,
                 mesh: Mesh | None = None):
        period = config.period
        for spec in config.specs:
            if config.n_heads % (spec.kv_heads or config.kv_heads):
                raise ValueError(
                    f"n_heads={config.n_heads} must divide by "
                    f"n_kv_heads={spec.kv_heads or config.kv_heads}")
        if config.scan_layers and any(s.ffn == "moe" for s in period):
            raise ValueError(
                "scan_layers needs homogeneous periods whose layers carry "
                "no aux loss; the capacity-dropping moe layer "
                "(moe_every > 0) does not scan, an experts layer does")
        if config.scan_layers and config.n_layers % len(period):
            raise ValueError(
                f"scan_layers scans whole periods: n_layers="
                f"{config.n_layers} must divide by the pattern's "
                f"{len(period)}")
        self.config = config
        if any(s.ffn == "moe" for s in config.specs):
            from .moe import MoEConfig, MoELayer
            self._moe = MoELayer(MoEConfig(
                d_model=config.d_model, d_ff=config.d_ff,
                num_experts=config.moe_experts, top_k=config.moe_top_k,
                capacity_factor=config.moe_capacity, dtype=config.dtype))
        else:
            self._moe = None
        # None means the default path: attend() picks the arm from the
        # shape (default_arm).  Pass make_ring_attention /
        # make_ulysses_attention (select_attention(name, mesh)) for
        # sequence parallelism, or any (q, k, v) -> out of one's own.
        self.attention_fn = attention_fn
        self.mesh = mesh  # when set, activations get sharding constraints
        # Never-reused identity for compiled-runner caches (generation.py):
        # id(self) can be recycled after GC, a counter token cannot.
        self.cache_token = next(_INSTANCE_COUNTER)

    # ------------------------------------------------------------- shapes
    def param_shapes(self) -> dict[str, tuple[int, ...]]:
        c = self.config
        shapes: dict[str, tuple[int, ...]] = {"embed/tok": (c.vocab, c.d_model)}
        if c.pos_emb == "learned":
            shapes["embed/pos"] = (c.max_seq, c.d_model)
        if c.scan_layers:
            # stacked layout: one array per block weight with a leading
            # axis over the layers that HAVE it, in layer order ([L, ...]
            # where every layer does), scanned a period at a time
            for suffix in self._stacked_suffixes():
                holders = self._holders(suffix, c.n_layers)
                shape = self.block_shapes(c.layer_spec(holders[0]))[suffix]
                shapes[f"blocks/{suffix}"] = (len(holders), *shape)
        else:
            for i in range(c.n_layers):
                for suffix, shape in self.block_shapes(
                        c.layer_spec(i)).items():
                    shapes[f"layer{i}/{suffix}"] = shape
        shapes["final_ln/scale"] = (c.d_model,)
        if c.norm == "layernorm":
            shapes["final_ln/bias"] = (c.d_model,)
        shapes["lm_head/w"] = (c.d_model, c.vocab)
        return shapes

    def block_shapes(self, spec: LayerSpec) -> dict[str, tuple[int, ...]]:
        """The weights of one layer of kind ``spec``, by suffix: its
        mixer's (the kind's record, models/mixers.py), then the biases and
        the feed-forward branch's."""
        c = self.config
        block = dict(spec.kind.shapes(c, spec))
        if c.norm == "layernorm":
            block["ln1/bias"] = (c.d_model,)
            block["ln2/bias"] = (c.d_model,)
        if c.bias:     # (a model with conv layers has none: __post_init__)
            kv_dim = (spec.kv_heads or c.kv_heads) * c.head_dim
            block.update({"attn/bq": (c.attn_dim,), "attn/bk": (kv_dim,),
                          "attn/bv": (kv_dim,), "attn/bo": (c.d_model,)})
        if spec.ffn == "mlp":
            block.update({"mlp/w1": (c.d_model, c.d_ff),
                          "mlp/w2": (c.d_ff, c.d_model)})
            if c.gated_mlp:
                block["mlp/w3"] = (c.d_model, c.d_ff)   # up_proj
            if c.bias:
                block.update({"mlp/b1": (c.d_ff,), "mlp/b2": (c.d_model,)})
            return block
        if spec.ffn == "moe":
            block.update({"moe/router/w": (c.d_model, c.moe_experts),
                          "moe/w1": (c.moe_experts, c.d_model, c.d_ff),
                          "moe/w2": (c.moe_experts, c.d_ff, c.d_model)})
            return block
        # the router keeps its width; the weights are the held experts'
        width, held = c.expert_width, c.held_experts[1]
        block.update({"moe/router/w": (c.d_model, c.moe_experts),
                      "moe/w1": (held, c.d_model, width),
                      "moe/w2": (held, width, c.d_model)})
        if c.gated_mlp:
            block["moe/w3"] = (held, c.d_model, width)
        if c.moe_expert_bias:
            block["moe/router/bias"] = (c.moe_experts,)
        if c.moe_shared_experts:
            shared = c.moe_shared_experts * width
            block.update({"moe/shared/w1": (c.d_model, shared),
                          "moe/shared/w2": (shared, c.d_model)})
            if c.gated_mlp:
                block["moe/shared/w3"] = (c.d_model, shared)
        return block

    def _stacked_suffixes(self) -> list[str]:
        seen: dict[str, None] = {}
        for spec in self.config.period:
            seen.update(dict.fromkeys(self.block_shapes(spec)))
        return list(seen)

    def _holders(self, suffix: str, layers: int) -> list[int]:
        """Which of the first ``layers`` layers have the weight
        ``suffix``: a stack's leading axis runs over them, in order."""
        c = self.config
        return [i for i in range(layers)
                if suffix in self.block_shapes(c.layer_spec(i))]

    def num_params(self) -> int:
        return sum(math.prod(s) for s in self.param_shapes().values())

    def flops_per_sample(self, remat_credited: bool = False) -> float | None:
        """Training (fwd+bwd) FLOPs for one max_seq-length sample:
        6*P per token for the parameter matmuls plus 12*L*d_model*S per
        token for the attention score/value matmuls (PaLM-appendix
        convention, full-S accounting).

        MoE configs count ACTIVE-expert FLOPs: each token's FFN runs
        ``moe_top_k`` of the ``moe_experts`` experts, so the parameter
        term uses P_active = P - n_moe_layers * (E - top_k) * 2*d*d_ff
        (the standard sparse-MoE MFU numerator; an upper bound when
        expert-capacity dropping skips some tokens' experts — callers
        reporting MoE MFU must say "active-expert accounting", as
        perfbench/families/ does).  Where the weights hold a share of the
        experts (``moe_held``) a token meets ``top_k * held / E`` of the
        held ones on average: ACTIVE and HELD.

        ``remat_credited=True`` counts the extra forward the hardware
        executes under ``config.remat``: hardware-utilization accounting
        for rematerialized runs.  Under the "full" policy that is the whole
        forward again (+2*P and +4*L*d*S per token); under "dots" the
        projection/MLP matmuls are saved and only the attention einsums
        re-run (+4*L*d*S only).  The credited count is an UPPER BOUND on
        what the hardware executes under "full", because this function
        cannot know what ``_remat_policy``'s names will find to keep: on a
        ``tensor`` axis larger than 1 the mixer's output projection (2*d*d
        of the 2*P a token and layer) is NOT run again, and wherever the
        blockwise kernel attends (an arm a backend chooses from the shape)
        its forward products (the +4*L*d*S) are NOT repeated either."""
        c = self.config
        seq = c.max_seq
        n_params = self.num_params()
        for i in range(c.n_layers):
            shapes = self.block_shapes(c.layer_spec(i))
            held = shapes.get("moe/w1", (0,))[0]
            inactive = max(0, held - c.moe_top_k * held / c.moe_experts)
            n_params -= inactive * sum(
                math.prod(shape[1:]) for suffix, shape in shapes.items()
                if suffix in ("moe/w1", "moe/w2", "moe/w3"))
        params_mult, attn_mult = 6.0, 12.0
        if remat_credited:
            attn_mult = 16.0
            if c.remat_policy == "full":
                params_mult = 8.0
        # the mixers' products, a token: scores and values over S keys of
        # d_model, or what a kind's record says (a recurrent layer's are
        # products with its states and do not grow with S)
        attn = sum(attn_mult * c.layer_spec(i).kind.products(c, seq)
                   for i in range(c.n_layers))
        return params_mult * n_params * seq + attn * seq

    def _remat_policy(self):
        """config.remat_policy -> jax.checkpoint policy.  "full" recomputes
        a layer's arithmetic and keeps what is not arithmetic to repeat
        (see ``TransformerConfig.remat_policy``): the blockwise kernel's
        output and row sums wherever that kernel attends
        (``ATTN_KERNEL_KEPT``: the remat forward does not run the kernel a
        second time), and on a mesh whose ``tensor`` axis is larger than 1
        the mixer branch's reduced output (:data:`MIXER_OUT`: the
        all-reduce is not paid twice).  A name nothing in the layer
        carries keeps nothing."""
        if self.config.remat_policy == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        kept = ATTN_KERNEL_KEPT + ((MIXER_OUT,) if self._tensor_ways > 1
                                   else ())
        return jax.checkpoint_policies.save_only_these_names(*kept)

    @property
    def _tensor_ways(self) -> int:
        """The size of the mesh's ``tensor`` axis; 1 without a mesh."""
        return 1 if self.mesh is None else self.mesh.shape.get("tensor", 1)

    def init_params(self, rng: jax.Array | int = 0) -> dict[str, Array]:
        c = self.config
        if isinstance(rng, int):
            rng = jax.random.key(rng)
        params: dict[str, Array] = {}
        for name, shape in self.param_shapes().items():
            rng, sub = jax.random.split(rng)
            if name.endswith(("/scale", "ssm/skip")):
                params[name] = jnp.ones(shape, c.dtype)
            elif (name.endswith(("/bias", "/b1", "/b2", "/bq", "/bk",
                                 "/bv", "/bo"))):
                params[name] = jnp.zeros(shape, c.dtype)
            elif name in ("embed/tok", "embed/pos"):
                params[name] = jax.random.normal(sub, shape, c.dtype) * 0.02
            elif name.endswith("/decay/a_log"):
                params[name] = jnp.zeros(shape, c.dtype)
            elif name.endswith("/decay/dt_bias"):
                # softplus(-4) = 0.018: a channel keeps 98% a position
                params[name] = jnp.full(shape, -4.0, c.dtype)
            else:
                # fan-in: leading dim for 2D weights, middle dim for the
                # per-expert [E, in, out] MoE weights
                fan_in = shape[-2] if len(shape) == 3 else shape[0]
                scale = 1.0 / math.sqrt(fan_in)
                # residual-output projections get depth-scaled init
                if name.endswith(("attn/wo", "conv/out_proj", "ssm/out_proj",
                                  "mlp/w2", "moe/w2", "moe/shared/w2")):
                    scale /= math.sqrt(2.0 * c.n_layers)
                params[name] = jax.random.normal(sub, shape, c.dtype) * scale
        return params

    # ------------------------------------------------------------ forward
    def _constrain(self, x: Array, *spec) -> Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec(*spec)))

    def apply(self, params: Mapping[str, Array], tokens: Array) -> Array:
        """tokens [B, S] int32 -> logits [B, S, vocab] float32."""
        h, _, _ = self._forward(params, tokens, collect_kv=False)
        return self.final_logits(params, h)

    def apply_collect_kv(self, params: Mapping[str, Array],
                         tokens: Array) -> tuple[Array, list]:
        """Forward that also returns each layer's post-rope (k, v) — the
        prefill half of KV-cached generation (models/generation.py)."""
        h, kvs, _ = self._forward(params, tokens, collect_kv=True)
        return self.final_logits(params, h), kvs

    # --- shared layer pieces (used by _forward AND generation.decode_step,
    # so the layer math exists exactly once) -----------------------------
    @scoped("norm")
    def _norm(self, params: Mapping[str, Array], key: str, x: Array) -> Array:
        """rms_norm or layer_norm per config — ``key`` is the ln prefix
        (e.g. "layer0/ln1")."""
        c = self.config
        if c.norm == "layernorm":
            return layer_norm(x, params[f"{key}/scale"],
                              params[f"{key}/bias"], c.norm_eps)
        return rms_norm(x, params[f"{key}/scale"], c.norm_eps)

    def _branch_input(self, params: Mapping[str, Array], key: str,
                      h: Array) -> Array:
        """What a residual branch reads of the stream ``h``: its norm
        (``key`` as :meth:`_norm` takes it), or ``h`` itself where the
        config's norm stands on the branch's output."""
        if self.config.norm_placement == "post":
            return h
        return self._norm(params, key, h)

    def _residual(self, params: Mapping[str, Array], key: str, h: Array,
                  out: Array) -> Array:
        """``h`` + a branch's output ``out`` (float32) at the config's
        ``residual_scale``, normed first where the norm stands there.
        The mixer's branch (``key`` its norm's, ``.../ln1`` in every
        family) is named :data:`MIXER_OUT` as it joins the stream."""
        if self.config.norm_placement == "post":
            out = self._norm(params, key, out)
        out = self._branch(out).astype(self.config.dtype)
        if key.endswith("/ln1"):
            # after the cast: what "full" remat keeps on a ``tensor`` axis
            # (an identity outside a jax.checkpoint whose policy asks for
            # the name)
            out = checkpoint_name(out, MIXER_OUT)
        return h + out

    def _column_dots(self, x: Array, weights, biases=None) -> list[Array]:
        """``x @ w`` (+ its bias) for each of the column-parallel
        ``weights`` ``[d, out]`` of ONE input ``x`` [B, S, d]: summed and
        biased in float32, returned in the model's dtype.

        On a mesh whose ``tensor`` axis cuts every weight's columns they
        are one contraction.  Autodiff transposes a dot into a dot, and
        GSPMD reduces a dot's partial sums over ``tensor`` where the dot
        stands: three projections of one input pay three all-reduces of
        x's gradient, which the compiler does not join; one contraction
        pays one.  Each weight is viewed ``[d, ways, out / ways]`` (the
        ``tensor`` axis stays where :func:`transformer_rule` put it) and
        the views are joined on the last axis, a device's own columns
        side by side: a local copy, no weight is resharded.  The product
        is cut back into its parts AFTER the bias and the cast, so that
        both stay the dot's epilogue and no float32 copy of it is
        written.  The joined weight exists only here; the store keeps
        its names and shapes.

        Without a mesh, on a ``tensor`` axis of 1, where a width does not
        divide (the rule then leaves that weight whole) and for an int8
        ``QTensor`` (serving quant), a :func:`wdot` each."""
        dtype = self.config.dtype
        ways = self._tensor_ways
        if ways == 1 or any(isinstance(w, QTensor) or w.shape[-1] % ways
                            for w in weights):
            outs = [wdot(x, w, preferred_element_type=jnp.float32)
                    for w in weights]
            if biases is not None:
                outs = [out + b.astype(jnp.float32)
                        for out, b in zip(outs, biases)]
            return [out.astype(dtype) for out in outs]
        d = x.shape[-1]
        widths = [w.shape[-1] // ways for w in weights]

        def joined(parts, *lead):
            return jnp.concatenate(
                [part.reshape(*lead, ways, width)
                 for part, width in zip(parts, widths)], axis=-1)

        y = jnp.dot(x, joined(weights, d).reshape(d, -1),
                    preferred_element_type=jnp.float32)
        y = y.reshape(*x.shape[:-1], ways, -1)
        if biases is not None:
            y = y + joined(biases).astype(jnp.float32)
        y = self._constrain(y.astype(dtype),
                            ("data", "fsdp"), "seq", "tensor", None)
        outs = jnp.split(y, list(itertools.accumulate(widths))[:-1], axis=-1)
        return [out.reshape(*x.shape[:-1], ways * width)
                for out, width in zip(outs, widths)]

    @scoped("attn_qkv")
    def qkv(self, params: Mapping[str, Array], prefix: str, h: Array,
            positions: Array, spec: LayerSpec | None = None,
            ) -> tuple[Array, Array, Array]:
        """ln1 (where the norm stands on a branch's input) -> q/k/v
        projections (+ biases) -> head split -> rope (or
        pass-through under learned positions and on a layer whose
        ``spec`` has no rotary).  h: [B, S, d].
        K/V come back with ``kv_heads`` heads (UNexpanded under GQA — the
        cache-friendly form); expand to the query head count with
        :func:`repeat_kv` before a plain attention kernel."""
        c = self.config
        batch, seq = h.shape[:2]
        x = self._branch_input(params, f"{prefix}/ln1", h)
        q, k, v = self._column_dots(
            x, [params[f"{prefix}/attn/w{name}"] for name in "qkv"],
            [params[f"{prefix}/attn/b{name}"] for name in "qkv"]
            if c.bias else None)
        kv_heads = (spec.kv_heads if spec is not None else 0) or c.kv_heads
        if spec is not None and spec.qk_norm == "all":
            # (before the heads are split: one norm over all of them)
            q = rms_norm(q, params[f"{prefix}/attn/q_norm/scale"],
                         c.norm_eps)
            k = rms_norm(k, params[f"{prefix}/attn/k_norm/scale"],
                         c.norm_eps)
        q = q.reshape(batch, seq, c.n_heads, c.head_dim)
        k = k.reshape(batch, seq, kv_heads, c.head_dim)
        v = v.reshape(batch, seq, kv_heads, c.head_dim)
        if spec is not None and spec.qk_norm and spec.qk_norm != "all":
            q = rms_norm(q, params[f"{prefix}/attn/q_norm/scale"],
                         c.norm_eps)
            k = rms_norm(k, params[f"{prefix}/attn/k_norm/scale"],
                         c.norm_eps)
        if c.query_gain != 1.0 and (spec is None or spec.mixer == "softmax"):
            # ``attn_scale``: here and nowhere else
            q = (q * c.query_gain).astype(c.dtype)
        if c.pos_emb == "learned" or (spec is not None and not spec.rope):
            # learned positions live in the residual stream (embed/pos,
            # added at embedding time) — K/V need no positional transform;
            # a layer without rotary has no position signal at all
            return q, k, v
        return (rope(q, positions, c.rope_theta, c.rope_scaling),
                rope(k, positions, c.rope_theta, c.rope_scaling), v)

    @scoped("attn_out")
    def attn_residual(self, params: Mapping[str, Array], prefix: str,
                      h: Array, attn: Array,
                      spec: LayerSpec | None = None) -> Array:
        """h + wo(attn) (+ bias).  attn: [B, S, H, D].  A layer whose
        ``spec`` has an output norm applies it per head first; one with a
        gate multiplies by sigmoid(wg x), x the layer's normed input (the
        norm ``qkv`` computes, which the compiler shares)."""
        c = self.config
        batch, seq = h.shape[:2]
        if spec is not None and spec.out_norm:
            attn = rms_norm(attn, params[f"{prefix}/attn/o_norm/scale"],
                            c.norm_eps)
        attn = attn.reshape(batch, seq, c.attn_dim)
        if spec is not None and spec.gate:
            x = self._branch_input(params, f"{prefix}/ln1", h)
            gate = wdot(x, params[f"{prefix}/attn/wg"],
                        preferred_element_type=jnp.float32)
            attn = attn * jax.nn.sigmoid(gate).astype(c.dtype)
        out = wdot(attn, params[f"{prefix}/attn/wo"],
                   preferred_element_type=jnp.float32)
        if c.bias:
            out = out + params[f"{prefix}/attn/bo"].astype(jnp.float32)
        return self._residual(params, f"{prefix}/ln1", h, out)

    def _branch(self, out: Array) -> Array:
        """A residual branch's output at the config's ``residual_scale``."""
        scale = self.config.residual_scale
        return out if scale == 1.0 else out * scale

    def conv_residual(self, params: Mapping[str, Array], prefix: str,
                      h: Array, state: tuple | None = None,
                      counts: Array | None = None) -> tuple[Array, tuple]:
        """A ``conv`` layer's whole mixer branch, under ``attn/conv``:
        h + W_out(C * conv(B * x)) with (B, C, x) = split(W_in ln1(h)).
        h [B, T, d] at T consecutive positions; ``state`` is a tuple of
        ONE array [B, K - 1, d] (the signature every state kind's branch
        has: ``Mixer.residual``), the gated inputs B * x of the K - 1
        positions before them (None: the sequence starts here), and
        ``counts`` [B] says how many of the T are real (None: all).
        Returns (new h, the state after the last real position): one
        function for a whole sequence, a block against a cached state and
        a decode round's single token."""
        from ..ops.short_conv import gated_short_conv

        c = self.config
        with jax.named_scope("attn"), jax.named_scope("conv"):
            x = self._branch_input(params, f"{prefix}/ln1", h)
            bcx = wdot(x, params[f"{prefix}/conv/in_proj"],
                       preferred_element_type=jnp.float32).astype(c.dtype)
            mixed, register = gated_short_conv(
                *jnp.split(bcx, 3, axis=-1), params[f"{prefix}/conv/kernel"],
                None if state is None else state[0], counts)
            out = wdot(mixed, params[f"{prefix}/conv/out_proj"],
                       preferred_element_type=jnp.float32)
            return (self._residual(params, f"{prefix}/ln1", h, out),
                    (register,))

    # positions a kda or gdn layer works through at a time (kda: a
    # [C, C, D] term a head; both: a triangular solve of C rows;
    # ops/delta_attention.py)
    DELTA_CHUNK = 64

    def kda_residual(self, params: Mapping[str, Array], prefix: str,
                     h: Array, state: tuple | None = None,
                     counts: Array | None = None) -> tuple[Array, tuple]:
        """A ``kda`` layer's whole mixer branch, under ``attn/linear``
        (``conv``, ``gates``, ``delta`` inside): with x = ln1(h),
        q, k = l2norm(silu(conv(x W_q))), l2norm(silu(conv(x W_k))),
        v = silu(conv(x W_v)); a log-decay a head and key channel
        -exp(a_log) * softplus(W_b (W_a x) + dt_bias); a write strength a
        head sigmoid(W_beta x); the gated delta rule over them
        (ops/delta_attention.py), its result over sqrt(head_dim), normed a
        head, times sigmoid(W_gb (W_ga x)), through W_o.  h [B, T, d] at T
        consecutive positions; ``state`` is (the three convolutions' shift
        register [B, K - 1, 3 * attn_dim], the matrix [B, H, D, D] float32)
        of the positions before them (None: the sequence starts here) and
        ``counts`` [B] says how many of the T are real (None: all).
        Returns (new h, both states after the last real position): one
        function for a whole sequence, a block against cached states and a
        decode round's single token."""
        from ..ops.delta_attention import gated_delta_rule

        c = self.config
        batch, seq = h.shape[:2]
        shift, matrix = state if state is not None else (None, None)
        dot = partial(wdot, preferred_element_type=jnp.float32)
        attn = f"{prefix}/attn"
        with jax.named_scope("attn"), jax.named_scope("linear"):
            x = self._branch_input(params, f"{prefix}/ln1", h)
            q, k, v, shift = self._delta_qkv(params, attn, x, shift, counts)
            with jax.named_scope("gates"):
                def low_rank(name):
                    inner = dot(x, params[f"{attn}/{name}/wa"]).astype(c.dtype)
                    return dot(inner, params[f"{attn}/{name}/wb"])

                rate = jnp.exp(params[f"{attn}/decay/a_log"].astype(
                    jnp.float32))[:, None]
                fall = -rate * jax.nn.softplus(
                    low_rank("decay") + params[f"{attn}/decay/dt_bias"].astype(
                        jnp.float32)).reshape(batch, seq, c.n_heads,
                                              c.head_dim)
                beta = jax.nn.sigmoid(dot(x, params[f"{attn}/beta/w"]))
                gate = jax.nn.sigmoid(low_rank("gate")).astype(c.dtype)
            with jax.named_scope("delta"):
                out, matrix = gated_delta_rule(q, k, v, fall, beta, matrix,
                                               counts, self.DELTA_CHUNK)
                out = (out * c.head_dim ** -0.5).astype(c.dtype)
            out = rms_norm(out, params[f"{attn}/o_norm/scale"], c.norm_eps)
            out = dot(out.reshape(batch, seq, c.attn_dim) * gate,
                      params[f"{attn}/wo"])
            return (self._residual(params, f"{prefix}/ln1", h, out),
                    (shift, matrix))

    @scoped("conv")
    def _delta_qkv(self, params: Mapping[str, Array], attn: str, x: Array,
                   shift: Array | None, counts: Array | None):
        """A delta-rule layer's q, k, v of its branch input x [B, T, d]:
        the three projections side by side through one depthwise causal
        convolution (``shift`` its register, None at a sequence's start),
        silu, split by head, q and k at unit length (1e-6 under the root).
        Returns (q, k [B, T, H, Dk], v [B, T, H, Dv], the register after
        the last real position).  The widths are the weights' own."""
        from ..ops.short_conv import short_conv

        c = self.config
        dot = partial(wdot, preferred_element_type=jnp.float32)
        qkv = jnp.concatenate(
            [dot(x, params[f"{attn}/w{n}"]).astype(c.dtype)
             for n in "qkv"], axis=-1)
        kernels = [params[f"{attn}/conv_{n}"] for n in "qkv"]
        mixed, shift = short_conv(qkv, jnp.concatenate(kernels, axis=-1),
                                  shift, counts)
        ends = list(itertools.accumulate(
            kernel.shape[-1] for kernel in kernels))[:-1]
        q, k, v = (part.reshape(*x.shape[:2], c.n_heads, -1)
                   for part in jnp.split(jax.nn.silu(mixed), ends, axis=-1))
        q, k = (part * jax.lax.rsqrt(
            jnp.sum(part * part, axis=-1, keepdims=True) + 1e-6)
            for part in (q, k))
        return q, k, v, shift

    def gdn_residual(self, params: Mapping[str, Array], prefix: str,
                     h: Array, state: tuple | None = None,
                     counts: Array | None = None) -> tuple[Array, tuple]:
        """A ``gdn`` layer's whole mixer branch, under ``attn/linear``
        (``conv``, ``gates``, ``delta`` inside, as ``kda`` has them): with
        x the branch's input, q, k, v as :meth:`_delta_qkv` makes them
        (key heads of ``delta_dims[0]``, value heads of ``delta_dims[1]``);
        a log-decay a HEAD -exp(a_log) * softplus(W_a x + dt_bias), float32;
        a write strength a head sigmoid(W_beta x), doubled under
        ``delta_neg_eigval``; the gated delta rule's scalar-decay arm over
        them (ops/delta_attention.py), its result (q carries the 1 /
        sqrt(Dk)) normed a head with a gain [Dv], times silu(W_g x), through
        W_o.  ``state`` is (the convolutions' shift register [B, K - 1,
        H * (2 Dk + Dv)], the matrix [B, H, Dk, Dv] float32) of the positions
        before (None: the sequence starts here), ``counts`` [B] how many of
        the T are real.  Returns (new h, both states after the last real
        position): one function for a whole sequence, a block against
        cached states and a decode round's single token."""
        from ..ops.delta_attention import gated_delta_rule

        c = self.config
        batch, seq = h.shape[:2]
        shift, matrix = state if state is not None else (None, None)
        dot = partial(wdot, preferred_element_type=jnp.float32)
        attn = f"{prefix}/attn"
        with jax.named_scope("attn"), jax.named_scope("linear"):
            x = self._branch_input(params, f"{prefix}/ln1", h)
            q, k, v, shift = self._delta_qkv(params, attn, x, shift, counts)
            with jax.named_scope("gates"):
                rate = jnp.exp(params[f"{attn}/decay/a_log"].astype(
                    jnp.float32))
                fall = -rate * jax.nn.softplus(
                    dot(x, params[f"{attn}/decay/w"])
                    + params[f"{attn}/decay/dt_bias"].astype(jnp.float32))
                beta = jax.nn.sigmoid(dot(x, params[f"{attn}/beta/w"]))
                if c.delta_neg_eigval:
                    beta = 2.0 * beta
                gate = jax.nn.silu(dot(x, params[f"{attn}/wz"])).astype(
                    c.dtype)
            with jax.named_scope("delta"):
                out, matrix = gated_delta_rule(
                    q * c.delta_dims[0] ** -0.5, k, v, fall, beta, matrix,
                    counts, self.DELTA_CHUNK)
                out = out.astype(c.dtype)
            out = rms_norm(out, params[f"{attn}/o_norm/scale"], c.norm_eps)
            out = dot(out.reshape(batch, seq, -1) * gate,
                      params[f"{attn}/wo"])
            return (self._residual(params, f"{prefix}/ln1", h, out),
                    (shift, matrix))

    # positions an ssm layer works through at a time (a [C, C] term a
    # head; ops/ssd.py).  The published ``mamba_chunk_size`` is a kernel's
    # choice too and happens to be the same
    SSM_CHUNK = 256

    def ssm_residual(self, params: Mapping[str, Array], prefix: str,
                     h: Array, state: tuple | None = None,
                     counts: Array | None = None,
                     arm: str = "plain") -> tuple[Array, tuple]:
        """An ``ssm`` layer's whole mixer branch, under ``attn/linear``
        (``conv`` and ``ssd`` inside; the projections and the gated norm
        outside both): with u the branch's input, [z | xBC | dt] = u W_in;
        xBC = silu(conv(xBC) + bias) through ONE depthwise causal
        convolution, split into a value a head x [H, P] and a key and a
        query a group B, C [G, N]; a step a head softplus(dt + dt_bias) and
        a rate -exp(a_log), float32; the state-space dual form over them
        (ops/ssd.py) plus the skip D x; then the gate BEFORE the norm,
        RMSNorm(y * silu(z)) a group's channels at a time with a gain over
        all of them, through W_out.  ``state`` is (the convolution's shift
        register [B, K - 1, H P + 2 G N], the matrix [B, H, P, N] float32)
        of the positions before (None: the sequence starts here),
        ``counts`` [B] how many of the T are real (in a decode round 1 for
        a lane that holds a request and 0 for an idle one, whose states stay
        as they are), ``arm`` what :func:`round_arm` answers for the matrix
        (``kernel``: the single token through ops/pallas/ssd_decode.py).
        Returns (new h, both states after the last real position): one
        function for a whole sequence, a block against cached states and a
        decode round's single token."""
        from ..ops.short_conv import short_conv
        from ..ops.ssd import ssd

        c = self.config
        batch, seq = h.shape[:2]
        shift, matrix = state if state is not None else (None, None)
        dot = partial(wdot, preferred_element_type=jnp.float32)
        ssm = f"{prefix}/ssm"
        inner, conv = c.ssm_dims
        with jax.named_scope("attn"), jax.named_scope("linear"):
            u = self._branch_input(params, f"{prefix}/ln1", h)
            z, xbc, dt = jnp.split(dot(u, params[f"{ssm}/in_proj"]),
                                   [inner, inner + conv], axis=-1)
            with jax.named_scope("conv"):
                mixed, shift = short_conv(xbc.astype(c.dtype),
                                          params[f"{ssm}/conv/kernel"],
                                          shift, counts)
                mixed = jax.nn.silu(mixed + params[f"{ssm}/conv/bias"].astype(
                    jnp.float32)).astype(c.dtype)
                x, b, q = jnp.split(
                    mixed, [inner, inner + c.ssm_groups * c.ssm_state],
                    axis=-1)
            with jax.named_scope("ssd"):
                x = x.reshape(batch, seq, c.ssm_heads, c.ssm_head_dim)
                b, q = (part.reshape(batch, seq, c.ssm_groups, c.ssm_state)
                        for part in (b, q))
                step = jax.nn.softplus(dt + params[
                    f"{ssm}/decay/dt_bias"].astype(jnp.float32))
                rate = -jnp.exp(params[f"{ssm}/decay/a_log"].astype(
                    jnp.float32))
                out, matrix = ssd(x, step, rate, b, q, matrix, counts,
                                  self.SSM_CHUNK, kernel=arm == "kernel")
                out = out + x * params[f"{ssm}/skip"].astype(
                    jnp.float32)[:, None]
            # the gate, THEN the norm, a group's channels at a time, float32
            gated = (out.reshape(batch, seq, c.ssm_groups, -1)
                     * jax.nn.silu(z).reshape(batch, seq, c.ssm_groups, -1))
            normed = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True) + c.norm_eps)
            out = dot((normed.reshape(batch, seq, inner) * params[
                f"{ssm}/norm/scale"].astype(jnp.float32)).astype(c.dtype),
                params[f"{ssm}/out_proj"])
            return (self._residual(params, f"{prefix}/ln1", h, out),
                    (shift, matrix))

    def latent_rows(self, params: Mapping[str, Array], prefix: str,
                    h: Array, positions: Array) -> tuple[Array, Array]:
        """A ``latent`` layer's queries and what its cache keeps of the
        same positions: (q [B, T, H, head_dim + qk_shared], rows [B, T,
        latent_row]: the RMS-normed latent and the key part all heads
        share, side by side, then zeros to whole registers).  The query
        is one product, or under ``q_latent`` a pair with a norm between
        (scope ``q``).  Under ``latent_rope`` the shared parts of the
        query and of the row are rotated to ``positions`` [B, T], the
        row's BEFORE anything keeps it; else no rotary anywhere.  YaRN's
        gain on the scores (``rope_scaling.softmax_gain``) is taken here,
        on the query's float32 product before its cast, so every form of
        the attention behind keeps its one scale, 1 / sqrt(the keys'
        width)."""
        c = self.config
        batch, seq = h.shape[:2]
        dot = partial(wdot, preferred_element_type=jnp.float32)
        x = self._branch_input(params, f"{prefix}/ln1", h)
        scaling = c.rope_scaling

        def turned(part: Array) -> Array:
            return rope(part, positions, c.rope_theta, scaling)

        with jax.named_scope("q"):
            if c.q_latent:
                low = rms_norm(
                    dot(x, params[f"{prefix}/attn/wq_a"]).astype(c.dtype),
                    params[f"{prefix}/attn/q_norm/scale"], c.norm_eps)
                q = dot(low, params[f"{prefix}/attn/wq_b"])
            else:
                q = dot(x, params[f"{prefix}/attn/wq"])
            if scaling is not None and scaling.softmax_gain != 1.0:
                q = q * scaling.softmax_gain
            q = q.astype(c.dtype).reshape(
                batch, seq, c.n_heads, c.head_dim + c.qk_shared)
            if c.latent_rope:
                q = jnp.concatenate([q[..., :c.head_dim],
                                     turned(q[..., c.head_dim:])], axis=-1)
        with jax.named_scope("rows"):
            kv = dot(x, params[f"{prefix}/attn/wkv_a"]).astype(c.dtype)
            latent = rms_norm(kv[..., :c.kv_latent],
                              params[f"{prefix}/attn/kv_norm/scale"],
                              c.norm_eps)
            shared = kv[..., c.kv_latent:]
            if c.latent_rope:
                shared = turned(shared[:, :, None, :])[:, :, 0]
            rows = jnp.concatenate([latent, shared], axis=-1)
            return q, jnp.pad(rows, ((0, 0), (0, 0),
                                     (0, c.latent_row - rows.shape[-1])))

    def latent_up(self, params: Mapping[str, Array],
                  prefix: str) -> tuple[Array, Array]:
        """``wkv_b`` by head: (every head's key part [kv_latent, H,
        head_dim], every head's value [kv_latent, H, head_dim])."""
        c = self.config
        up = params[f"{prefix}/attn/wkv_b"].reshape(
            c.kv_latent, c.n_heads, 2 * c.head_dim)
        return up[..., :c.head_dim], up[..., c.head_dim:]

    def latent_expand(self, params: Mapping[str, Array], prefix: str,
                      rows: Array, wide_values: bool = True
                      ) -> tuple[Array, Array]:
        """K and V of ``rows`` [B, M, latent_row] for an
        attention that knows nothing of latents, under ``expand``: K [B, M,
        H, head_dim + qk_shared] (a head's own part, then the shared one)
        and V the same width, zeros past ``head_dim`` (the attentions here
        take one width for keys and values, and 1 / sqrt of it for the
        scale: the keys'; ``wide_values=False``: V [B, M, H, head_dim], for
        one that takes V's width as it is)."""
        c = self.config
        with jax.named_scope("expand"):
            up_k, up_v = self.latent_up(params, prefix)
            latent = rows[..., :c.kv_latent]
            shared = rows[..., c.kv_latent:c.kv_latent + c.qk_shared]
            k = jnp.einsum("bml,lhd->bmhd", latent, up_k,
                           preferred_element_type=jnp.float32).astype(c.dtype)
            v = jnp.einsum("bml,lhd->bmhd", latent, up_v,
                           preferred_element_type=jnp.float32).astype(c.dtype)
            k = jnp.concatenate([k, jnp.broadcast_to(
                shared[:, :, None, :], k.shape[:3] + (c.qk_shared,))], axis=-1)
            if not wide_values:
                return k, v
            return k, jnp.pad(v, ((0, 0),) * 3 + ((0, c.qk_shared),))

    def latent_out(self, params: Mapping[str, Array], prefix: str,
                   h: Array, attn: Array) -> Array:
        """h + wo(attn) for a latent layer's attn [B, T, H, head_dim]."""
        out = wdot(attn.reshape(*attn.shape[:2], self.config.attn_dim),
                   params[f"{prefix}/attn/wo"],
                   preferred_element_type=jnp.float32)
        return self._residual(params, f"{prefix}/ln1", h, out)

    def latent_residual(self, params: Mapping[str, Array], prefix: str,
                        h: Array) -> tuple[Array, Array]:
        """A ``latent`` layer's whole mixer branch over a whole sequence,
        under ``attn/latent``: K and V expanded from the rows, causal
        softmax of q k^T / sqrt(head_dim + qk_shared) by the device's arm
        (:func:`device_arm`); positions count from 0.  Returns (new h, the rows [B, S, latent_row]
        a cache keeps)."""
        c = self.config
        with jax.named_scope("attn"), jax.named_scope("latent"):
            positions = jnp.arange(h.shape[1], dtype=jnp.int32)[None, :]
            q, rows = self.latent_rows(params, prefix, h, positions)
            k, v = self.latent_expand(params, prefix, rows)
            attn = attend_by(device_arm(q.shape, k.shape), q, k, v)
            return self.latent_out(params, prefix, h,
                                   attn[..., :c.head_dim]), rows

    def _mlp(self, params: Mapping[str, Array], key: str, x: Array,
             bias: bool = False) -> Array:
        """w2(gelu(w1 x)) (+ biases under ``bias``), or the gated form
        w2(act(w1 x) * (w3 x)) under ``mlp_act="swiglu"`` (silu) and
        ``"reglu"`` (relu), in float32; the weights are ``key``'s."""
        c = self.config
        dot = partial(wdot, preferred_element_type=jnp.float32)
        ff = dot(x, params[f"{key}/w1"])
        if bias:
            ff = ff + params[f"{key}/b1"].astype(jnp.float32)
        if c.gated_mlp:
            up = dot(x, params[f"{key}/w3"]).astype(c.dtype)
            gate = jax.nn.silu if c.mlp_act == "swiglu" else jax.nn.relu
            ff = gate(ff.astype(c.dtype)) * up
        else:
            ff = jax.nn.gelu(ff.astype(c.dtype))
        out = dot(ff, params[f"{key}/w2"])
        if bias:
            out = out + params[f"{key}/b2"].astype(jnp.float32)
        return out

    @scoped("mlp")
    def mlp_residual(self, params: Mapping[str, Array], prefix: str,
                     h: Array) -> Array:
        """The dense MLP's branch (:meth:`_mlp` on ``mlp/*``) with its
        norm (``ln2``) where the config places it."""
        x = self._branch_input(params, f"{prefix}/ln2", h)
        return self._residual(
            params, f"{prefix}/ln2", h,
            self._mlp(params, f"{prefix}/mlp", x, self.config.bias))

    def layer_view(self, params: Mapping[str, Array],
                   layer: int) -> tuple[Mapping[str, Array], str]:
        """(param view, key prefix) for one layer in either layout: the
        store itself with prefix ``layer<i>`` when unrolled, or a sliced
        ``blk/*`` view of the stacked ``blocks/*`` arrays under
        ``scan_layers`` — so per-layer consumers (generation's decode
        loop) work on both layouts."""
        c = self.config
        if not c.scan_layers:
            return params, f"layer{layer}"
        mine = self.block_shapes(c.layer_spec(layer))
        # a stack's leading axis runs over the layers that hold the suffix
        return ({f"blk/{name[len('blocks/'):]}": value[
                    len(self._holders(name[len("blocks/"):], layer))]
                 for name, value in params.items()
                 if name.startswith("blocks/")
                 and name[len("blocks/"):] in mine}, "blk")

    def router_logits(self, params: Mapping[str, Array], prefix: str,
                      x: Array) -> Array:
        """An ``experts`` layer's router on its normed input x [B, S, d]:
        float32 logits [B, S, E]."""
        with jax.named_scope("moe"), jax.named_scope("router"):
            return jnp.einsum(
                "bsd,de->bse", x.astype(jnp.float32),
                params[f"{prefix}/moe/router/w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)

    def pre_attention_router(self, params: Mapping[str, Array], prefix: str,
                             spec: LayerSpec, h: Array) -> Array | None:
        """The logits of an ``experts`` layer's router where it stands
        BEFORE attention (``moe_router_input="attn"``: it reads the
        attention's input, normed where the norm stands there; the same
        norm ``qkv`` computes, which the compiler shares); None for every
        other layer, and where the router reads the feed-forward branch's
        own input (:meth:`ffn_residual` computes those logits itself)."""
        if spec.ffn != "experts" or self.config.moe_router_input != "attn":
            return None
        return self.router_logits(
            params, prefix, self._branch_input(params, f"{prefix}/ln1", h))

    def ffn_residual(self, params: Mapping[str, Array], prefix: str,
                     spec: LayerSpec, h: Array, decode: bool = False,
                     router_logits: Array | None = None,
                     route_stats: list | None = None,
                     chosen: list | None = None,
                     counts: Array | None = None) -> tuple[Array, Array]:
        """The layer's FFN branch by its kind: the dense MLP, the
        capacity-dropping ``moe`` layer or dropless ``experts``.
        Returns (new_h, aux_loss) — aux is 0 but for ``moe``.  ``params``
        and ``prefix`` are a :meth:`layer_view`.  ``decode`` runs ``moe``
        drop-free (capacity = token count): capacity dropping is a
        batch-global training mechanism and cannot be reproduced causally
        during KV-cached decoding; ``experts`` never drops, so prefill,
        extension and decode run one path.  ``router_logits`` are those of
        :meth:`pre_attention_router` where an ``experts`` layer's router
        stands there (None: it reads this branch's own input);
        ``route_stats``, where given, gains this layer's tokens per
        expert ([E] int32; where the weights hold a share of the experts,
        ``moe_held``, the held experts' and then the assignments routed
        elsewhere, [count + 1], and under a group limit the rank places
        after them, ``moe.dropless_experts``) for the caller's counters,
        and ``chosen``
        the experts every token of an ``experts`` layer took ([B, S, k]).
        A shared expert (``moe_shared_experts``) runs on the same input
        under ``moe/shared`` and is added ungated.  ``counts`` [B] says
        how many of a row's S tokens are somebody's (None: all; a decode
        round's idle lane has none, an admission's row its real tokens):
        an ``experts`` layer sorts the others' assignments into no group
        and counts them nowhere (``moe.dropless_experts``'s ``live``); the
        other kinds compute every token as ever."""
        zero = jnp.zeros((), jnp.float32)
        if spec.ffn == "mlp":
            return self.mlp_residual(params, prefix, h), zero
        c = self.config
        if spec.ffn == "moe":
            # (a training fixture: its norm stands on the input, always)
            x = self._norm(params, f"{prefix}/ln2", h)
            cap = h.shape[0] * h.shape[1] if decode else None
            moe_out, aux = self._moe.apply(params, x, prefix=f"{prefix}/",
                                           capacity_override=cap)
            return h + moe_out.astype(c.dtype), aux
        from .moe import dropless_experts

        x = self._branch_input(params, f"{prefix}/ln2", h)
        batch, seq = h.shape[:2]
        if router_logits is None:
            router_logits = self.router_logits(params, prefix, x)
        with jax.named_scope("moe"):
            out, loads = dropless_experts(
                x.reshape(batch * seq, c.d_model),
                router_logits.reshape(batch * seq, c.moe_experts),
                params[f"{prefix}/moe/w1"], params[f"{prefix}/moe/w2"],
                params.get(f"{prefix}/moe/w3"), top_k=c.moe_top_k,
                act=c.mlp_act, score=c.moe_score,
                bias=params.get(f"{prefix}/moe/router/bias"),
                scale=c.moe_route_scale, chosen=chosen,
                held=c.moe_held or None, groups=c.moe_groups,
                groups_kept=c.moe_groups_kept,
                # (the rings' expression: a row's first ``counts`` tokens)
                live=None if counts is None else (
                    jnp.arange(seq, dtype=jnp.int32)[None, :]
                    < counts[:, None]).reshape(batch * seq))
            out = out.reshape(batch, seq, c.d_model)
            if c.moe_shared_experts:
                with jax.named_scope("shared"):
                    out = out + self._mlp(params, f"{prefix}/moe/shared", x)
        if chosen is not None:
            chosen[-1] = chosen[-1].reshape(batch, seq, c.moe_top_k)
        if route_stats is not None:
            route_stats.append(loads)
        return self._residual(params, f"{prefix}/ln2", h, out), zero

    # sequences from this length on run blockwise attention (scores of a
    # block at a time, blocks wholly outside the mask skipped) on the
    # default path where the kernel does not engage; shorter ones the
    # dense einsum
    BLOCKWISE_FROM = 2048

    def on_mesh(self, mesh: Mesh, attention: str = "dense") -> None:
        """This model placed on ``mesh`` (activations get sharding
        constraints, the default path takes a device's shard) with the
        ``--attention`` choice ``attention`` (:func:`select_attention`)."""
        self.mesh = mesh
        self.attention_fn = select_attention(attention, mesh)

    def default_arm(self, q_shape: tuple[int, ...],
                    kv_shape: tuple[int, ...], window: int) -> str:
        """Which arm the default path takes, from what :meth:`attend` can
        observe and nothing else.  Without a mesh, :func:`device_arm` of
        the shapes: ``kernel``, ``blockwise`` or ``dense``.  With one, what
        the mesh adds: ``sharded_kernel`` (the kernel under ``shard_map``
        over the mesh's batch and head axes; ``kernel`` on a mesh of one
        device) where the ``seq`` and ``pipe`` axes are 1, the mesh
        divides batch and heads and :func:`device_arm` takes the kernel
        for a device's shard; else ``dense``, the einsum, which GSPMD
        partitions (never ``blockwise``)."""
        mesh = self.mesh
        if mesh is None:
            return device_arm(q_shape, kv_shape, window)
        if mesh.shape.get("seq", 1) == mesh.shape.get("pipe", 1) == 1:
            rows = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
            tp = mesh.shape.get("tensor", 1)
            divides = (q_shape[0] % rows == 0 and q_shape[2] % tp == 0
                       and kv_shape[2] % tp == 0)
            q_shard, kv_shard = (
                (shape[0] // rows, shape[1], shape[2] // tp, shape[3])
                for shape in (q_shape, kv_shape))
            if divides and device_arm(q_shard, kv_shard, window) == "kernel":
                return "sharded_kernel" if mesh.size > 1 else "kernel"
        return "dense"

    def attend(self, q: Array, k: Array, v: Array,
               spec: LayerSpec) -> Array:
        """Causal attention of a whole sequence for a layer of kind
        ``spec``, under ``attn/full`` or ``attn/window``.  A caller's
        ``attention_fn`` runs as given (it knows no window, so a window
        that binds is refused); the default (``attention_fn`` None)
        chooses by what it sees (:meth:`default_arm`): the blockwise
        kernel, under its own ``attn_kernel`` component, wherever its
        shape fits on a TPU, else blockwise in plain XLA for a long
        sequence, else the einsum."""
        seq = q.shape[1]
        window = spec.window if 0 < spec.window < seq else 0
        if spec.mixer == "sparse" and seq >= self.config.sparse.dense_len:
            raise ValueError("a sparse layer's sequence past dense_len "
                             "goes through mix(), not attend()")
        kind = ("window" if spec.window
                else "sparse/attend" if spec.mixer == "sparse" else "full")
        with jax.named_scope("attn"), jax.named_scope(kind):
            if self.attention_fn is not None:
                if window:
                    raise ValueError(
                        f"a window of {spec.window} binds at sequence "
                        f"length {seq}: window layers run the default "
                        "attention (dense or blockwise), not a caller's "
                        "attention_fn")
                return self.attention_fn(q, k, v)
            arm = self.default_arm(q.shape, k.shape, window)
            if arm == "sharded_kernel":
                from ..ops.pallas.fused_attention import (
                    fused_causal_attention)

                with jax.named_scope("attn_kernel"):
                    return shard_over_batch_and_heads(
                        self.mesh, fused_causal_attention)(q, k, v)
            return attend_by(arm, q, k, v, window)

    # positions a linear layer works through at a time
    LINEAR_CHUNK = 128

    def mix(self, q: Array, k: Array, v: Array, spec: LayerSpec,
            counts: Array | None = None, selections: list | None = None):
        """A whole sequence through the layer's mixer: (attn [B, S, H, D],
        what a cache keeps of the layer: its (k, v), or a linear layer's
        state after the last real position, ``counts`` [B] of them, as a
        tuple of one: a state layer keeps a tuple of states).  A
        sparse layer whose sequence reaches ``dense_len`` selects key
        blocks (under ``attn/sparse``: ``select``, ``attend``) and, where
        ``selections`` is given, adds its selection [B, KV, S, NB] to it;
        a shorter one pays for no selection.  A linear layer runs in chunks
        under ``attn/linear`` (``intra``, ``state``)."""
        c = self.config
        if spec.mixer == "linear":
            from ..ops.linear_attention import linear_attention

            with jax.named_scope("attn"), jax.named_scope("linear"):
                out, state = linear_attention(q, k, v, counts=counts,
                                              chunk=self.LINEAR_CHUNK)
                return (out / math.sqrt(c.head_dim)).astype(c.dtype), (state,)
        if spec.mixer == "sparse" and q.shape[1] >= c.sparse.dense_len:
            from ..ops.sparse_attention import (compress_keys,
                                                sparse_blockwise_attention)

            with jax.named_scope("attn"), jax.named_scope("sparse"), \
                    jax.named_scope("select"):
                k_heads, v_heads = (x.transpose(0, 2, 1, 3) for x in (k, v))
                ck = compress_keys(k_heads, c.sparse)
            out = sparse_blockwise_attention(
                q, k_heads, v_heads, ck, jnp.zeros((q.shape[0],), jnp.int32),
                c.sparse, with_mask=selections is not None)
            if selections is not None:
                out, chosen = out
                selections.append(chosen)
            return out, (k, v)
        return self.attend(q, k, v, spec), (k, v)

    def expert_selections(self, params: Mapping[str, Array],
                          tokens: Array, kept: list | None = None) -> list:
        """Which experts every token of every ``experts`` layer took in
        the forward pass of ``tokens`` [B, S]: [B, S, k] a layer, in layer
        order.  ``kept``, a list, gains what a cache keeps of every layer
        after that same pass (:meth:`_forward`'s second result: a state
        layer's tuple of states, a latent layer's rows, else (k, v))."""
        chosen: list = []
        _, layers, _ = self._forward(params, tokens,
                                     collect_kv=kept is not None,
                                     chosen=chosen)
        if kept is not None:
            kept.extend(layers)
        return chosen

    def sparse_selections(self, params: Mapping[str, Array],
                          tokens: Array) -> list:
        """Which key blocks every query of every sparse layer attended in
        the forward pass of ``tokens`` [B, S]: a [B, KV, S, NB] mask a
        sparse layer, in layer order (empty where S < ``dense_len``)."""
        chosen: list = []
        self._forward(params, tokens, collect_kv=False, selections=chosen)
        return chosen

    @scoped("head")
    def final_logits(self, params: Mapping[str, Array], h: Array) -> Array:
        h = self._norm(params, "final_ln", h)
        if self.config.logit_scale != 1.0:
            h = (h * self.config.logit_scale).astype(h.dtype)
        return wdot(h, params["lm_head/w"],
                    preferred_element_type=jnp.float32)

    @scoped("embed")
    def embed(self, params: Mapping[str, Array], tokens: Array,
              positions: Array) -> Array:
        """Token (+ learned positional) embedding — the single definition
        shared by the training forward and cached decode, so the two can
        never disagree about where position information enters.

        mode="clip" on the positional gather: batched speculative
        decoding's finished rows intentionally overshoot max_seq (their
        outputs land in discarded slack lanes) and jnp.take's default
        would fill NaN there, poisoning the row's whole forward.  The
        REAL out-of-range case (a user decoding past max_seq) is rejected
        loudly at the entry points (generate / DecodeServer.submit /
        speculative_generate_batched), not silently clamped here."""
        h = jnp.take(params["embed/tok"], tokens, axis=0)
        if self.config.embed_scale != 1.0:
            h = (h * self.config.embed_scale).astype(h.dtype)
        if self.config.pos_emb == "learned":
            h = h + jnp.take(params["embed/pos"], positions, axis=0,
                             mode="clip").astype(h.dtype)
        return h

    def _forward(self, params: Mapping[str, Array], tokens: Array,
                 collect_kv: bool, route_stats: list | None = None,
                 counts: Array | None = None,
                 selections: list | None = None,
                 chosen: list | None = None,
                 ) -> tuple[Array, list, Array]:
        """(h, what a cache keeps of every layer under ``collect_kv``: a
        (k, v), a state layer's tuple of states (see :meth:`mix`,
        :meth:`conv_residual`, :meth:`kda_residual`,
        :meth:`gdn_residual` and :meth:`ssm_residual`) or a latent layer's
        rows (:meth:`latent_residual`); aux loss).  ``counts``
        [B]: how many of a row's tokens are real, for the layers whose
        state must not hold a pad and for the ``experts`` layers, which
        route the real tokens alone (:meth:`ffn_residual`)."""
        c = self.config
        batch, seq = tokens.shape
        if c.pos_emb == "learned" and seq > c.max_seq:
            # static shapes: this fires at trace time, before any compute.
            # Without it, embed's clip would silently reuse the last
            # position row for every overflow position — wrong logits AND
            # gradients (HF torch raises IndexError on the same input)
            raise ValueError(
                f"sequence length {seq} exceeds the learned-position "
                f"table max_seq={c.max_seq}")
        positions = jnp.arange(seq, dtype=jnp.int32)[None, :].repeat(batch, 0)
        h = self.embed(params, tokens, positions)
        h = self._constrain(h, ("data", "fsdp"), "seq", None)
        kvs: list = []
        aux_total = jnp.zeros((), jnp.float32)

        def layer_body(layer_params, p, spec, h):
            router = self.pre_attention_router(layer_params, p, spec, h)
            mixer = spec.kind
            if mixer.residual is not None:
                # the whole branch one method; a state kind's keeps the
                # pads out of its states
                branch = getattr(self, mixer.residual)
                h, kept = (branch(layer_params, p, h, counts=counts)
                           if mixer.keeps == "state"
                           else branch(layer_params, p, h))
            else:
                q, k, v = self.qkv(layer_params, p, h, positions, spec)
                # K/V go to the attention fn UNexpanded (kv_heads-sized);
                # each implementation expands at the math (expand_gqa), so
                # ring/Ulysses communicate the small tensors
                attn, kept = self.mix(q, k, v, spec, counts, selections)
                h = self.attn_residual(layer_params, p, h, attn, spec)
            h = self._constrain(h, ("data", "fsdp"), "seq", None)
            h, aux = self.ffn_residual(layer_params, p, spec, h,
                                       router_logits=router,
                                       route_stats=route_stats,
                                       chosen=chosen, counts=counts)
            h = self._constrain(h, ("data", "fsdp"), "seq", None)
            return h, aux, kept

        if c.scan_layers:
            # one scan body traced once, holding one PERIOD of the layer
            # pattern; block weights stream from their stacked arrays —
            # compile cost is depth-independent.  A stack's leading axis
            # runs over the layers that hold the suffix, so per period it
            # folds to [periods, holders in a period, ...]
            period = c.period
            periods = c.n_layers // len(period)
            holders = {name[len("blocks/"):]: self._holders(
                name[len("blocks/"):], len(period))
                for name in params if name.startswith("blocks/")}

            def fold(x, held):
                # (one holder a period: the stack is already [periods, ...])
                return x if len(held) == 1 else x.reshape(
                    periods, len(held), *x.shape[1:])

            # (tree.map: an int8 QTensor stack folds leaf by leaf)
            blocks = {suffix: jax.tree.map(lambda x, held=held: fold(x, held),
                                           params[f"blocks/{suffix}"])
                      for suffix, held in holders.items()}

            def scan_body(h, blk):
                kvs_p, aux = [], jnp.zeros((), jnp.float32)
                for j, spec in enumerate(period):
                    view = {f"blk/{suffix}": value
                            if len(holders[suffix]) == 1 else jax.tree.map(
                                lambda x, at=holders[suffix].index(j): x[at],
                                value)
                            for suffix, value in blk.items()
                            if j in holders[suffix]}
                    h, layer_aux, kv = layer_body(view, "blk", spec, h)
                    aux = layer_aux if len(period) == 1 else aux + layer_aux
                    kvs_p.append(kv)
                if not collect_kv:
                    return h, aux
                if len(period) == 1:
                    return h, kvs_p[0]
                return h, (jnp.stack([k for k, _ in kvs_p]),
                           jnp.stack([v for _, v in kvs_p]))

            if c.remat and not collect_kv:
                # scan's internals already rule out the CSE hazard that
                # jax.checkpoint's default prevent_cse=True guards against;
                # the default would insert optimization barriers per step
                scan_body = jax.checkpoint(scan_body, prevent_cse=False,
                                           policy=self._remat_policy())
            # a scan body's values cannot leave it
            route_stats = chosen = None
            h, ys = jax.lax.scan(scan_body, h, blocks)
            if collect_kv:
                # [periods, (P,) B, S, H, D] -> per layer
                k_stack, v_stack = (
                    y.reshape(c.n_layers, *y.shape[-4:]) for y in ys)
                kvs = [(k_stack[i], v_stack[i]) for i in range(c.n_layers)]
            else:
                aux_total = jnp.sum(ys)
            return h, kvs, aux_total

        # remat recomputes layer activations in the backward pass (O(1)
        # layers of residuals); never combined with collect_kv, which
        # exists to SAVE per-layer tensors (generation prefill)
        if c.remat and not collect_kv:
            body = jax.checkpoint(
                lambda lp, i, h: layer_body(lp, f"layer{i}",
                                            c.layer_spec(i), h)[:2],
                static_argnums=(1,), policy=self._remat_policy())
        else:
            body = None
        for i in range(c.n_layers):
            if body is not None:
                h, aux = body(params, i, h)
            else:
                h, aux, kv = layer_body(params, f"layer{i}",
                                        c.layer_spec(i), h)
                if collect_kv:
                    kvs.append(kv)
            aux_total = aux_total + aux
        return h, kvs, aux_total

    def loss(self, params: Mapping[str, Array], batch) -> Array:
        """Next-token cross-entropy (+ MoE load-balance aux when
        configured).  batch: [B, S] int32 tokens (or a (tokens,) tuple)."""
        tokens = batch[0] if isinstance(batch, (tuple, list)) else batch
        # run the full sequence (keeps the seq length shard-divisible for
        # sequence parallelism) and drop the last position's logits
        h, _, aux = self._forward(params, tokens, collect_kv=False)
        if self.config.loss_chunk:
            nll = self._chunked_next_token_nll(params, h, tokens)
        else:
            nll = next_token_nll(self.final_logits(params, h), tokens)
        return nll + self.config.moe_aux_coef * aux

    @scoped("loss")
    def _chunked_next_token_nll(self, params: Mapping[str, Array],
                                h: Array, tokens: Array) -> Array:
        """Mean next-token NLL with the LM head computed in seq chunks of
        ``config.loss_chunk`` positions under jax.checkpoint: peak logits
        memory is O(chunk * vocab) instead of O(S * vocab), with the chunk
        recomputed in the backward pass.  Numerically identical to the
        unchunked loss (tested)."""
        c = self.config
        batch, seq = tokens.shape
        chunk = c.loss_chunk
        if seq % chunk:
            raise ValueError(
                f"loss_chunk={chunk} must divide seq len {seq}")
        n_chunks = seq // chunk
        # shift targets; the final position has no target (masked out)
        targets = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((batch, 1), tokens.dtype)], axis=1)
        valid = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
        h_chunks = jnp.moveaxis(
            h.reshape(batch, n_chunks, chunk, h.shape[-1]), 1, 0)
        t_chunks = jnp.moveaxis(targets.reshape(batch, n_chunks, chunk), 1, 0)
        v_chunks = valid.reshape(n_chunks, chunk)

        @jax.checkpoint
        def chunk_nll_sum(h_c, t_c, v_c):
            logp = jax.nn.log_softmax(self.final_logits(params, h_c), axis=-1)
            nll = -jnp.take_along_axis(
                logp, t_c[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return jnp.sum(nll * v_c[None, :])

        def body(carry, xs):
            return carry + chunk_nll_sum(*xs), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (h_chunks, t_chunks, v_chunks))
        return total / (batch * (seq - 1))


def stack_layers(params: Mapping[str, Array], n_layers: int) -> dict:
    """Convert an unrolled store (``layer<i>/<suffix>``) to the stacked
    ``scan_layers`` layout (``blocks/<suffix>`` with leading [L]) — e.g.
    to load a checkpoint trained unrolled into a scanned model.  Dense
    layers only (stacking requires homogeneous blocks)."""
    out: dict = {}
    by_suffix: dict[str, list] = {}
    for i in range(n_layers):
        prefix = f"layer{i}/"
        for name, value in params.items():
            if name.startswith(prefix):
                by_suffix.setdefault(name[len(prefix):], []).append(value)
    for suffix, values in by_suffix.items():
        if len(values) != n_layers:
            raise ValueError(
                f"suffix {suffix!r} present in {len(values)}/{n_layers} "
                f"layers — stacking requires homogeneous blocks")
        out[f"blocks/{suffix}"] = jnp.stack(values)
    for name, value in params.items():
        if not name.startswith("layer"):
            out[name] = value
    return out


def unstack_layers(params: Mapping[str, Array]) -> dict:
    """Inverse of :func:`stack_layers`: stacked ``blocks/*`` arrays back
    to per-layer ``layer<i>/*`` entries."""
    out: dict = {}
    for name, value in params.items():
        if name.startswith("blocks/"):
            suffix = name[len("blocks/"):]
            for i in range(value.shape[0]):
                out[f"layer{i}/{suffix}"] = value[i]
        else:
            out[name] = value
    return out


def transformer_rule(mesh: Mesh):
    """Sharding rule for transformer stores: Megatron TP + fsdp (+ EP).

    column-parallel (tensor on output dim): wq wk wv w1 lm_head, a gdn
        layer's output gate wz, and by head a latent layer's wkv_b (and
        its low-rank query's wq_b) and a kda layer's gates' second halves
    row-parallel  (tensor on input dim):    wo w2
    (a kda or gdn layer's conv kernels, a_log, dt_bias, beta, a kda
    layer's gates' first halves and a gdn layer's decay projection, a
    latent layer's wkv_a and wq_a, an ssm layer's kernel, bias, a_log,
    dt_bias and skip: small, replicated; its in_proj, whose columns are
    three parts side by side, and out_proj take the fallback)
    (a shared expert's ``moe/shared/w*`` as the dense MLP's)
    vocab-sharded embedding; norm scales replicated (fsdp if divisible);
    MoE expert weights sharded over the ``expert`` axis (router replicated).
    """
    n_fsdp = mesh.shape["fsdp"]
    n_tp = mesh.shape["tensor"]
    n_exp = mesh.shape.get("expert", 1)

    def rule(name: str, shape: tuple[int, ...]) -> PartitionSpec:
        if "/moe/router/" in name:
            return PartitionSpec()
        if "/moe/w" in name:
            return moe_expert_weight_spec(name, shape, n_exp, n_tp, n_fsdp)
        def fsdp_on(axis: int, taken: int | None) -> list:
            spec: list = [None] * len(shape)
            if taken is not None:
                spec[taken] = "tensor"
            if n_fsdp > 1 and shape[axis] % n_fsdp == 0 and axis != taken:
                spec[axis] = "fsdp"
            return spec

        # in/out weight dims are the trailing two; stacked scan-layer
        # weights (blocks/*, [L, in, out]) keep their leading layer dim
        # unsharded — it is the scan axis, and sharding it would gather
        # one shard's slice every scan step
        if name.endswith(("attn/wq", "attn/wk", "attn/wv", "mlp/w1",
                          "mlp/w3", "moe/shared/w1", "moe/shared/w3",
                          "lm_head/w", "attn/wkv_b", "attn/wq_b",
                          "attn/decay/wb", "attn/gate/wb", "attn/wz")):
            taken = len(shape) - 1 if n_tp > 1 and shape[-1] % n_tp == 0 else None
            return PartitionSpec(*fsdp_on(len(shape) - 2, taken))
        if name.endswith(("attn/wo", "mlp/w2", "moe/shared/w2")):
            taken = (len(shape) - 2
                     if n_tp > 1 and shape[-2] % n_tp == 0 else None)
            return PartitionSpec(*fsdp_on(len(shape) - 1, taken))
        if name == "embed/tok":
            # TP goes d_model-wise, never vocab(row)-wise: a TENSOR-sharded
            # vocab axis makes GSPMD fall back to "involuntary full
            # rematerialization" (replicate + repartition) on every lookup,
            # because the gather output wants a different sharding.  fsdp on
            # the vocab axis is fine — ZeRO storage sharding costs one
            # params all-gather per step (verified: 0 remat warnings vs 4
            # for tensor-on-vocab on a 2x2x2 mesh).
            taken = (len(shape) - 1
                     if n_tp > 1 and shape[-1] % n_tp == 0 else None)
            return PartitionSpec(*fsdp_on(0, taken))
        if name.endswith(("/scale", "/bias", "/bq", "/bk", "/bv", "/bo",
                          "/b1", "/b2", "/a_log", "/dt_bias", "attn/conv_q",
                          "attn/conv_k", "attn/conv_v", "ssm/conv/kernel",
                          "ssm/skip", "attn/wkv_a",
                          "attn/wq_a", "attn/decay/wa", "attn/gate/wa", "attn/beta/w",
                          "attn/decay/w")):
            # norm scales and all biases: tiny 1-D vectors, replicated like
            # their paired scales (an fsdp-sharded bias would force a
            # per-use all-gather against its tensor-sharded activation)
            return PartitionSpec()
        if name == "embed/pos":
            # small [max_seq, d_model] table gathered per position —
            # replicate rather than reshard every lookup
            return PartitionSpec()
        # fallback: fsdp on largest divisible dim
        spec: list = [None] * len(shape)
        for axis in sorted(range(len(shape)), key=lambda a: -shape[a]):
            if n_fsdp > 1 and shape[axis] % n_fsdp == 0:
                spec[axis] = "fsdp"
                break
        return PartitionSpec(*spec)

    return rule


def small_lm(vocab: int = 1024, seq: int = 256, dtype=jnp.float32,
             remat: bool = False, scan_layers: bool = False,
             n_layers: int = 2) -> Transformer:
    """Test-scale LM (``small_lm4`` in the registry is the 4-layer variant
    — deep enough for pipe x virtual-stage factorizations)."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=128, n_heads=4, n_layers=n_layers, d_ff=512,
        max_seq=seq, dtype=dtype, remat=remat, scan_layers=scan_layers))


def tiny_lm(vocab: int = 1024, seq: int = 256, dtype=jnp.float32,
            remat: bool = False, scan_layers: bool = False) -> Transformer:
    """1-layer draft-scale LM (same default vocab as small_lm, so the pair
    works as a speculative-decoding target/draft out of the box)."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=64, n_heads=2, n_layers=1, d_ff=256,
        max_seq=seq, dtype=dtype, remat=remat, scan_layers=scan_layers))


def lm_350m(vocab: int = 32000, seq: int = 1024, dtype=jnp.bfloat16,
            remat: bool = True, scan_layers: bool = False,
            kv_heads: int = 0, n_heads: int = 16,
            remat_policy: str = "full") -> Transformer:
    """~370M-param GPT-style flagship (chip_smoke.py's model): 24 layers,
    d_model 1024, seq 1024, bf16 weights/activations with f32 MXU
    accumulation, per-layer remat by default (activation memory, not HBM
    capacity, should bound the batch), chunked cross-entropy (peak f32
    logits ~1 GB -> ~32 MB at batch 8).  ``scan_layers`` stores blocks
    stacked and scans the layer loop — depth-independent compile time.
    ``kv_heads`` in {1, 2, 4, 8} switches to GQA (0, the default, keeps
    all 16; the `lm_350m_gqa` registry entry uses 4): kv_heads/16 the
    KV-cache HBM and ring/Ulysses ICI bytes, and the attention kernel
    keeps K/V unexpanded end to end."""
    # n_heads=8 gives head_dim 128 — a full MXU tile per attention
    # matmul, where head_dim 64 fills half of one (the effect on step
    # time is not measured on the chip) — same parameter count either way
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=1024, n_heads=n_heads, n_layers=24, d_ff=4096,
        n_kv_heads=kv_heads, remat_policy=remat_policy,
        max_seq=seq, dtype=dtype, remat=remat, scan_layers=scan_layers,
        # largest chunk <= 128 dividing seq, so every seq stays valid
        loss_chunk=math.gcd(128, seq)))


def llama_350m(vocab: int = 32000, seq: int = 1024, dtype=jnp.bfloat16,
               remat: bool = True, scan_layers: bool = False,
               kv_heads: int = 4,
               remat_policy: str = "full") -> Transformer:
    """LLaMA-architecture sibling of :func:`lm_350m` (~350M params):
    SwiGLU gated MLP (d_ff scaled to 8/3·d keeping the parameter count
    near the GELU flagship), GQA kv_heads=4, RoPE/RMSNorm — exactly the
    shape :func:`models.hf.from_hf_llama` produces, so measurements on this
    entry transfer to converted checkpoints."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=1024, n_heads=16, n_layers=24,
        d_ff=2816,  # ~8/3 * 1024, rounded to a 128-multiple for the MXU
        n_kv_heads=kv_heads, mlp_act="swiglu", remat_policy=remat_policy,
        max_seq=seq, dtype=dtype, remat=remat, scan_layers=scan_layers,
        loss_chunk=math.gcd(128, seq)))


def moe_lm(vocab: int = 1024, seq: int = 256, dtype=jnp.float32,
           remat: bool = False, top_k: int = 1) -> Transformer:
    """Test-scale MoE LM: every 2nd layer is an expert-routed FFN
    (``top_k=1`` Switch, ``top_k=2`` Mixtral-style)."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=128, n_heads=4, n_layers=4, d_ff=512,
        max_seq=seq, dtype=dtype, moe_every=2, moe_experts=4, remat=remat,
        moe_top_k=top_k))


def moe_350m(vocab: int = 32000, seq: int = 1024, dtype=jnp.bfloat16,
             remat: bool = True, top_k: int = 1,
             experts: int = 8) -> Transformer:
    """Flagship-scale MoE: the :func:`lm_350m` trunk (24L / d1024 /
    seq 1024) with every 2nd FFN expert-routed — ~350M ACTIVE params
    per token (Switch top-1) over ~1.07B total.  The sparse-scaling
    shape: serve-time compute of the dense flagship, ~3x its capacity.
    Pair with a mesh ``expert`` axis to shard the expert stacks
    (``--mesh=expert:4,data:2``); MFU is not reported for MoE configs
    (6*P overcounts inactive experts — flops_per_sample returns None),
    so its rate is reported in samples/s."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=1024, n_heads=16, n_layers=24, d_ff=4096,
        max_seq=seq, dtype=dtype, remat=remat, moe_every=2,
        moe_experts=experts, moe_top_k=top_k,
        loss_chunk=math.gcd(128, seq)))


def switch_lm(vocab: int = 1024, seq: int = 256, dtype=jnp.float32,
              remat: bool = False, top_k: int = 1) -> Transformer:
    """Test-scale ALL-MoE LM (moe_every=1, the Switch/Mixtral layout):
    homogeneous expert blocks, so it composes with pipeline parallelism
    (parallel/pipeline.py requires uniform per-layer param sets)."""
    return Transformer(TransformerConfig(
        vocab=vocab, d_model=128, n_heads=4, n_layers=4, d_ff=512,
        max_seq=seq, dtype=dtype, moe_every=1, moe_experts=4, remat=remat,
        moe_top_k=top_k))
