"""Mixture-of-Experts layers: two, for two uses.

:func:`dropless_experts` is what a served or forwarded model's ``experts``
layers run (``LayerSpec.ffn == "experts"``): every (token, choice)
assignment is computed whatever the imbalance.  Assignments are sorted by
expert and the experts run as ONE grouped matmul per weight
(``jax.lax.ragged_dot``: rows of expert e meet only expert e's matrix), so
nothing of size tokens x experts x capacity is ever built and prefill,
extension and decode share the path.  Gates are the softmax over the
selected logits; the experts take the model's ``mlp_act`` form (gated:
``w2(act(w1 x) * (w3 x))``).  The weights may hold a SHARE of the router's
experts (``held``): the layer then computes its own experts' part of the
sum and no more.

:class:`MoELayer` is the capacity-dropping Switch/top-k layer of the
home-made presets (``moe_lm``, ``switch_lm``, ``moe_350m``;
``LayerSpec.ffn == "moe"``, ``moe_every``).  Those presets stay what they
were: CPU fixtures of expert parallelism over the mesh's ``expert`` axis,
of the pipeline's MoE stage and of the load-balancing loss, with their
tests.  None of them is a benchmark configuration, and none runs the
dropless path.

MoELayer: top-k routing with capacity (k=1 gives the Switch transformer, k=2 the
Mixtral/GShard shape): the router picks each token's top-k experts, gates
are the top-k probabilities renormalized to sum one, and (token, choice)
assignments beyond an expert's capacity are dropped (pass through the
residual).  Dispatch/combine are expressed as einsums so that with the
expert dimension of w1/w2 sharded over the mesh's ``expert`` axis, GSPMD
lowers dispatch to an all-to-all over ICI — no manual collective code.

Load-balancing auxiliary loss per Switch Transformer: E * sum_e f_e * p_e
(fraction of assignments routed * mean router prob).  No reference
analogue (SURVEY.md §2: expert parallelism absent from the reference).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 128
    d_ff: int = 512
    num_experts: int = 8
    capacity_factor: float = 1.25
    # experts per token: 1 = Switch, 2 = Mixtral/GShard top-2
    top_k: int = 1
    dtype: object = jnp.float32


class MoELayer:
    def __init__(self, config: MoEConfig):
        if not 1 <= config.top_k <= config.num_experts:
            raise ValueError(
                f"top_k={config.top_k} must be in [1, num_experts="
                f"{config.num_experts}]")
        self.config = config

    def param_shapes(self) -> dict[str, tuple[int, ...]]:
        c = self.config
        return {
            "moe/router/w": (c.d_model, c.num_experts),
            "moe/w1": (c.num_experts, c.d_model, c.d_ff),
            "moe/w2": (c.num_experts, c.d_ff, c.d_model),
        }

    def init_params(self, rng: jax.Array | int = 0,
                    prefix: str = "") -> dict[str, Array]:
        c = self.config
        if isinstance(rng, int):
            rng = jax.random.key(rng)
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            f"{prefix}moe/router/w": jax.random.normal(
                k1, (c.d_model, c.num_experts), c.dtype) * 0.02,
            f"{prefix}moe/w1": jax.random.normal(
                k2, (c.num_experts, c.d_model, c.d_ff), c.dtype)
                / math.sqrt(c.d_model),
            f"{prefix}moe/w2": jax.random.normal(
                k3, (c.num_experts, c.d_ff, c.d_model), c.dtype)
                / math.sqrt(c.d_ff),
        }

    def capacity(self, num_assignments: int) -> int:
        """Per-expert queue length for ``num_assignments`` (token, choice)
        routing assignments — N tokens produce N * top_k assignments."""
        c = self.config
        return max(1, int(math.ceil(
            num_assignments / c.num_experts * c.capacity_factor)))

    def apply(self, params: Mapping[str, Array], x: Array,
              prefix: str = "",
              capacity_override: int | None = None,
              expert_slice: "tuple[Array, int] | None" = None
              ) -> tuple[Array, Array]:
        """x: [B, S, D] -> (out [B, S, D], aux_loss scalar).

        Dropped tokens (over capacity) contribute zero output — callers add
        the residual connection.  ``capacity_override`` replaces the
        factor-derived capacity; pass the token count for drop-free
        inference (capacity dropping is a batch-global training-time
        mechanism: which token drops depends on every other token in the
        batch, so it cannot be reproduced causally at decode time).

        ``expert_slice=(start, count)``: manual expert parallelism for
        callers INSIDE shard_map (parallel/pipeline.py), where GSPMD can't
        partition the dispatch einsums.  Routing/capacity/aux are computed
        over ALL num_experts from the (expert-axis-replicated) tokens —
        identical on every rank — but ``params[...moe/w1|w2]`` hold only
        this rank's ``count`` experts starting at ``start``, and the
        returned out is that PARTIAL contribution: the caller psums it
        over the expert axis.  ``start`` may be traced (lax.axis_index);
        ``count`` must be static."""
        c = self.config
        k = c.top_k
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        n = b * s
        cap = capacity_override if capacity_override is not None \
            else self.capacity(n * k)

        logits = jnp.dot(tokens.astype(jnp.float32),
                         params[f"{prefix}moe/router/w"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)            # [N, E]
        top_probs, top_idx = jax.lax.top_k(probs, k)       # [N, k]
        if k == 1:
            # Switch gates by the raw router prob — renormalizing would
            # make the gate a constant 1 and cut the router's gradient
            gates = top_probs
        else:
            # Mixtral/GShard: top-k probs renormalized to sum one (the
            # router still gets gradients through the ratios)
            gates = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)

        # flatten (token, choice) assignments, token-major so earlier
        # tokens win expert queue slots regardless of choice rank
        a_idx = top_idx.reshape(n * k)                     # [A]
        a_gate = gates.reshape(n * k)
        # position of each assignment within its expert's queue
        onehot = jax.nn.one_hot(a_idx, c.num_experts, dtype=jnp.int32)
        position = jnp.cumsum(onehot, axis=0) * onehot     # [A, E], 1-based
        pos_in_expert = jnp.sum(position, axis=-1) - 1     # [A]
        keep = pos_in_expert < cap

        # dispatch tensor [N, K, E, C]: token n's choice j -> slot (e, c);
        # contracting the (n) or (k, e, c) sides directly avoids ever
        # materializing a [N*k, D] repeated-token copy
        dispatch = ((jax.nn.one_hot(a_idx, c.num_experts, dtype=x.dtype)
                     [:, :, None]
                     * jax.nn.one_hot(jnp.where(keep, pos_in_expert, cap),
                                      cap + 1, dtype=x.dtype)[:, None, :cap])
                    .reshape(n, k, c.num_experts, cap))
        w1, w2 = params[f"{prefix}moe/w1"], params[f"{prefix}moe/w2"]
        if expert_slice is not None:
            start, count = expert_slice
            if w1.shape[0] != count:
                raise ValueError(
                    f"expert_slice count {count} != local expert weights "
                    f"{w1.shape[0]}")
            dispatch = jax.lax.dynamic_slice_in_dim(dispatch, start, count,
                                                    axis=2)
        # expert inputs [E, C, D] — with w1/w2 sharded over 'expert', GSPMD
        # turns this einsum contraction into the dispatch all-to-all
        expert_in = jnp.einsum("nkec,nd->ecd", dispatch, tokens)
        h = jnp.einsum("ecd,edf->ecf", expert_in, w1)
        h = jax.nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w2)
        combined = jnp.einsum("nkec,ecd->nkd", dispatch, expert_out)
        weighted = combined * (a_gate * keep).astype(x.dtype).reshape(
            n, k)[..., None]
        out = weighted.sum(axis=1)

        # Switch load-balancing aux: E * sum_e (fraction of assignments
        # to e) * (mean router prob of e)
        frac = jnp.mean(jax.nn.one_hot(a_idx, c.num_experts,
                                       dtype=jnp.float32), axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = c.num_experts * jnp.sum(frac * mean_prob)
        return out.reshape(b, s, d), aux


def select_experts(router_logits: Array, top_k: int, score: str = "softmax",
                   bias: Array | None = None, scale: float = 1.0,
                   groups: int = 1, groups_kept: int = 1,
                   ) -> tuple[Array, Array]:
    """(gates [N, k] float32, chosen experts [N, k]) from router logits
    [N, E], in float32.  ``softmax``: the top_k logits, gated by the
    softmax over them.  ``sigmoid``: every expert scores sigmoid(logit);
    the top_k of score + ``bias`` ([E], a stored correction that enters
    the SELECTION only) are chosen and gated by their own scores over
    their sum (+ 1e-6), times ``scale``.  ``groups`` > 1 LIMITS that
    selection (under ``group_limit``): the experts lie in ``groups`` groups
    of E / groups neighbours, a group scores the sum of its two best score
    + bias, and outside the ``groups_kept`` best groups an expert's score +
    bias counts as 0 (the deepseek routers' node-limited selection: a
    token's experts lie on the devices of ``groups_kept`` groups)."""
    logits = router_logits.astype(jnp.float32)
    if score == "softmax":
        top_logits, top_idx = jax.lax.top_k(logits, top_k)
        return jax.nn.softmax(top_logits, axis=-1), top_idx
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if groups > 1:
        with jax.named_scope("group_limit"):
            by_group = choice.reshape(choice.shape[0], groups, -1)
            best_two, _ = jax.lax.top_k(by_group, 2)
            _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), groups_kept)
            open_ = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
            choice = jnp.where(open_[:, :, None], by_group,
                               0.0).reshape(choice.shape)
    _, top_idx = jax.lax.top_k(choice, top_k)
    chosen = jnp.take_along_axis(scores, top_idx, axis=-1)
    return (chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
            * scale, top_idx)


# a sort of more keys than this takes the TPU compiler 11-12 s to build, in
# every program that holds one (2.4 s at 16,384, 12.2 s at 20,480, the same
# at 65,536; compiled for a described v5e, PR 47): past it
# :func:`_sorted_by_group` counts instead
_SORT_LIMIT = 16384


def _sorted_by_group(keys: Array, groups: int) -> tuple[Array, Array]:
    """The stable sort of ``keys`` [A] (group ids in [0, groups)) as (order,
    place): ``keys[order]`` ascends with ties in their first order, and
    ``place`` is its inverse (assignment a goes to row place[a]).  Up to
    :data:`_SORT_LIMIT` keys by two sorts; past it by counting (a key's row
    is its group's first row + how many of its group came before it), the
    same permutation."""
    a = keys.shape[0]
    if a <= _SORT_LIMIT:
        order = jnp.argsort(keys, stable=True)
        return order, jnp.argsort(order)
    member = (keys[:, None] == jnp.arange(groups)[None, :]).astype(jnp.int32)
    before = jnp.cumsum(member, axis=0) - member           # [A, G]
    sizes = before[-1] + member[-1]
    first = jnp.cumsum(sizes) - sizes
    place = jnp.sum(member * (before + first[None, :]), axis=1)
    order = jnp.zeros((a,), jnp.int32).at[place].set(
        jnp.arange(a, dtype=jnp.int32))
    return order, place


def dropless_experts(x: Array, router_logits: Array, w1: Array, w2: Array,
                     w3: Array | None, *, top_k: int, act: str = "gelu",
                     score: str = "softmax", bias: Array | None = None,
                     scale: float = 1.0, chosen: list | None = None,
                     held: tuple[int, int] | None = None,
                     groups: int = 1, groups_kept: int = 1,
                     live: Array | None = None,
                     ) -> tuple[Array, Array]:
    """Dropless top-k experts over a flat batch of tokens.

    x [N, D]; router_logits [N, E] float32; w1 (and the gate pair's up
    projection w3, None for an ungated ``act``) [E, D, F]; w2 [E, F, D].
    Returns (out [N, D] float32, loads [E] int32: assignments per expert).

    Token n's output is sum over its top_k experts e of gate_e *
    expert_e(x_n), experts and gates as :func:`select_experts` gives them
    (``score``, ``bias``, ``scale``; by default the softmax over the
    SELECTED logits); ``chosen``, where given, gains the experts [N, k].
    The N * top_k assignments are sorted by expert (stable, so ties keep
    token order) and each weight runs once as a grouped matmul over the
    sorted rows; the results are un-sorted by gather and summed per token.

    ``held`` = (first, count): the weights are [count, ...], the experts
    first .. first + count - 1 of the router's E (a chip's share of an
    expert-parallel layer).  Routing, the bias and the gates are over all
    E as ever; only the assignments to held experts are sorted into
    groups and computed, and ``out`` is THAT part of the sum (what the
    other ranks' experts would add is left out, and nothing stands in for
    it).  The grouped matmul gets N * min(top_k, count) static rows, the
    most a token's choices can send here, so nothing is ever dropped; the
    rows past the held assignments are zeros and belong to no group.
    ``loads`` is then [count + 1]: the held experts' assignments, and last
    the assignments routed to experts held elsewhere.  Under a group limit
    (``groups`` > 1, :func:`select_experts`) one more entry follows,
    [count + 2]: the RANK PLACES, over the tokens the sum of how many of
    the stage's E / count ranks (rank r holds the experts r * count ..) a
    token's choices lie on, which is what the limit bounds and what an
    exchange between the ranks would send.

    ``live`` [N] bool: which tokens are somebody's (None: every one, and
    the function traces what it always has).  A token that is nobody's (a
    decode round's idle lane, an admission's pad position) is routed like
    any other, and its assignments then sort behind every group as those to
    experts held elsewhere do: the row count stays N * top_k (nothing is
    dropped, nothing recompiles by occupancy), the grouped matmul gets the
    live assignments' rows and zeros behind them, an expert only such
    tokens chose has no rows and is not read, and the token's output is
    zeros.  ``loads``, every entry, counts live tokens only.
    """
    n, d = x.shape
    experts = w1.shape[0]
    with jax.named_scope("router"):
        gates, top_idx = select_experts(router_logits, top_k, score, bias,
                                        scale, groups, groups_kept)  # [N, k]
        if chosen is not None:
            chosen.append(top_idx)
        flat = top_idx.reshape(n * top_k)
        if held is None and live is None:
            order, place = _sorted_by_group(flat, experts)     # [A]
            loads = sizes = jnp.zeros((experts,), jnp.int32).at[flat].add(1)
            rows = x[order // top_k]                           # [A, D]
            mine = None
        else:
            first, count = held or (0, experts)
            if experts != count:
                raise ValueError(f"held={held}: the weights hold {experts} "
                                 "experts")
            # an assignment to an expert held elsewhere sorts behind every
            # held one, into no group; so does a token's that is nobody's
            local, each = flat, 1
            if held is not None:
                local = flat - first
                local = jnp.where((local >= 0) & (local < count), local,
                                  count)
            key = local
            if live is not None:
                each = jnp.repeat(live, top_k)                 # [A]
                key = jnp.where(each, local, count)
                each = each.astype(jnp.int32)
            order, place = _sorted_by_group(key, count + 1)    # [A]
            loads = jnp.zeros((count + 1,), jnp.int32).at[local].add(each)
            sizes = loads[:count]
            if held is None:
                loads = sizes
            bound = n * min(top_k, count)
            mine = (jnp.arange(bound) < jnp.sum(sizes))[:, None]
            rows = jnp.where(mine, x[order[:bound] // top_k], 0)
            if held is not None and groups > 1:
                ranks = router_logits.shape[-1] // count
                on = jnp.any((top_idx // count)[:, :, None]
                             == jnp.arange(ranks), axis=1)         # [N, R]
                if live is not None:
                    on &= live[:, None]
                loads = jnp.concatenate(
                    [loads, jnp.sum(on, dtype=jnp.int32)[None]])
    with jax.named_scope("experts"):
        dot = partial(jax.lax.ragged_dot, group_sizes=sizes,
                      preferred_element_type=jnp.float32)
        hidden = dot(rows, w1).astype(x.dtype)
        if act == "gelu":
            hidden = jax.nn.gelu(hidden)
        else:
            gate = jax.nn.silu if act == "swiglu" else jax.nn.relu
            hidden = gate(hidden) * dot(rows, w3).astype(x.dtype)
        out = dot(hidden, w2)                                  # [A, D] f32
    with jax.named_scope("router"):
        if mine is not None:
            # (what a grouped matmul leaves in a row of no group is its own)
            out = jnp.pad(jnp.where(mine, out, 0.0),
                          ((0, n * top_k - bound), (0, 0)))
        # back to (token, choice) order, weighted and summed per token
        out = out[place].reshape(n, top_k, d)
        return jnp.sum(out * gates[..., None], axis=1), loads


def moe_expert_weight_spec(name: str, shape: tuple[int, ...], n_exp: int,
                           n_tp: int, n_fsdp: int) -> PartitionSpec:
    """Sharding for a [E, in, out] expert weight: ``expert`` on the expert
    dim, Megatron within-expert TP on the d_ff dim (w1 output / w2 input —
    one all-reduce per MoE branch, inserted by GSPMD), fsdp storage
    sharding on the free d_model dim.  Shared by moe_sharding_rule and
    models.transformer.transformer_rule."""
    spec: list = [None] * len(shape)
    if n_exp > 1 and shape[0] % n_exp == 0:
        spec[0] = "expert"
    is_w1 = name.endswith(("w1", "w3"))
    ff_axis = len(shape) - 1 if is_w1 else 1
    d_axis = 1 if is_w1 else len(shape) - 1
    if n_tp > 1 and shape[ff_axis] % n_tp == 0:
        spec[ff_axis] = "tensor"
    if n_fsdp > 1 and shape[d_axis] % n_fsdp == 0:
        spec[d_axis] = "fsdp"
    return PartitionSpec(*spec)


def moe_sharding_rule(mesh: Mesh):
    """Shard expert weights over ``expert`` (+ within-expert ``tensor`` on
    d_ff, ``fsdp`` on d_model); router replicated."""
    n_exp = mesh.shape["expert"]
    n_tp = mesh.shape["tensor"]
    n_fsdp = mesh.shape["fsdp"]

    def rule(name: str, shape: tuple[int, ...]) -> PartitionSpec:
        if "/moe/w" in name or name.startswith("moe/w"):
            return moe_expert_weight_spec(name, shape, n_exp, n_tp, n_fsdp)
        return PartitionSpec()  # router + anything else: replicated

    return rule
