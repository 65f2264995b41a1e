"""Pipeline parallelism over the mesh's ``pipe`` axis.

GPipe-style microbatch pipelining in shard_map: stage parameters live on
their pipe rank (leading axis sharded over ``pipe``), activations flow rank
-> rank via `ppermute` once per tick, and microbatches stream through so
all stages work concurrently after the fill phase.  The schedule runs
M + P - 1 ticks for M microbatches over P stages (bubble fraction
(P-1)/(M+P-1)).

Differentiable end-to-end (ppermute transposes to the reverse rotation), so
`jax.grad` of a pipelined loss gives exact gradients — no reference
analogue (the reference has no model layer at all; SURVEY.md §1).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def num_pipeline_stages(mesh: Mesh) -> int:
    return mesh.shape["pipe"]


def _microbatch_size(mesh: Mesh, batch_axes: tuple[str, ...],
                     global_batch: int, num_microbatches: int) -> int:
    """Per-device microbatch rows; the one divisibility check both the
    GPipe and 1F1B schedules share."""
    dp = 1
    for axis in batch_axes:
        dp *= mesh.shape.get(axis, 1)
    local_batch, rem = divmod(global_batch, dp)
    if rem or local_batch % num_microbatches:
        raise ValueError(
            f"per-device batch {global_batch}/{dp} must divide by "
            f"num_microbatches={num_microbatches}")
    return local_batch // num_microbatches


def stack_stage_params(per_stage_params: list[dict], mesh: Mesh) -> dict:
    """Stack per-stage param stores along a leading [P] axis and shard it
    over ``pipe``: stage i's weights live on pipe rank i."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
    sharding = NamedSharding(mesh, P("pipe"))

    def place(x):
        spec = P("pipe", *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, stacked)


class PipelinedTransformerLM:
    """A Transformer LM trained with pipeline parallelism over ``pipe``.

    Layer blocks are stacked ``[P, L/P, ...]`` and sharded over the pipe
    axis (stage s holds layers s*L/P .. (s+1)*L/P-1); activations stream
    through :func:`pipeline_apply`'s GPipe schedule.  The embedding and LM
    head run OUTSIDE the pipeline, replicated over ``pipe`` — that lifts
    the shape-preserving restriction to the full embed -> blocks -> head
    model while keeping the pipelined middle shape-preserving, which is
    what the schedule requires.

    Drop-in for the plain Transformer in ShardedTrainer/run_training:
    exposes ``config``, ``init_params``, ``num_params``, ``loss``.
    Gradients are exact (ppermute differentiates to the reverse rotation),
    so a pipelined run matches the non-pipelined model step for step —
    verified in tests/test_pipeline.py.
    """

    BLOCK_PREFIX = "blocks/"
    _STAGE_KEY = "blk"  # reuse Transformer block methods with this prefix

    SCHEDULES = ("gpipe", "1f1b")

    def __init__(self, inner, mesh: Mesh, num_microbatches: int = 0,
                 schedule: str = "gpipe", virtual_stages: int = 1):
        from ..models.transformer import Transformer, causal_attention

        if not isinstance(inner, Transformer):
            raise ValueError("pipeline parallelism wraps a Transformer LM")
        native_arch = (inner.config.pos_emb == "rope"
                       and inner.config.norm == "rms"
                       and not inner.config.bias)
        # the one arch restriction left: the MoE stage normalizes with
        # rms inline, so non-native configs cannot pipeline all-MoE
        # blocks (dense GPT-2-family configs run under BOTH schedules —
        # the 1F1B injection/backward goes through the model's embed)
        if not native_arch and inner.config.moe_every == 1:
            raise ValueError(
                "pipeline + MoE requires the native architecture (the "
                "MoE stage normalizes with rms inline)")

        if inner.config.moe_every > 1:
            # Stage stacking requires HOMOGENEOUS blocks: every layer's
            # params stack along one leading [L/P] axis (init_params), so
            # dense/MoE interleaves (different per-layer param sets) cannot
            # be pipelined.  The supported MoE pipeline shape is
            # moe_every=1 — every block MoE, the Switch/Mixtral layout.
            raise ValueError(
                "pipeline + interleaved MoE (moe_every > 1) is not "
                "supported: stage stacking needs homogeneous blocks; "
                "use moe_every=1 (all-MoE blocks)")
        if inner.config.scan_layers:
            raise ValueError(
                "pipeline wraps an unrolled Transformer (it restacks "
                "layer<i>/* itself); build the model without scan_layers")
        if schedule not in self.SCHEDULES:
            raise ValueError(f"schedule {schedule!r}; options {self.SCHEDULES}")
        n_pipe = mesh.shape["pipe"]
        if virtual_stages < 1:
            raise ValueError(f"virtual_stages must be >= 1, got "
                             f"{virtual_stages}")
        if virtual_stages > 1 and schedule != "1f1b":
            raise ValueError(
                "virtual_stages > 1 (interleaved pipelining) requires "
                "schedule='1f1b' — GPipe has no interleaved form here")
        if inner.config.n_layers % (n_pipe * virtual_stages):
            raise ValueError(
                f"n_layers={inner.config.n_layers} must divide by "
                f"pipe x virtual_stages ({n_pipe} x {virtual_stages})")
        # Stage-internal attention runs per device inside shard_map: the
        # wrapped model's own attention_fn, else the einsum (a sequence
        # split over a seq axis does not compose with the pipeline)
        self._stage_attention = inner.attention_fn or causal_attention
        self.inner = inner
        self.config = inner.config
        self.mesh = mesh
        self.n_pipe = n_pipe
        self.schedule = schedule
        self.virtual_stages = virtual_stages
        # per-SCHEDULED-stage layer count: rank r holds virtual_stages
        # chunks, chunk c being global stage c*P + r (Megatron round-robin)
        self.layers_per_stage = inner.config.n_layers // (
            n_pipe * virtual_stages)
        self.num_microbatches = num_microbatches or n_pipe

    # ---------------------------------------------------------------- params
    def _is_block_param(self, name: str) -> bool:
        return name.startswith("layer")

    def _block_suffix(self, name: str) -> str:
        return name.split("/", 1)[1]  # "layer3/attn/wq" -> "attn/wq"

    def _block_leading_shape(self) -> tuple[int, ...]:
        """Leading axes of a stacked ``blocks/*`` param: [P, Lc] plain,
        [P, V, Lc] interleaved (rank r, chunk c = global stage c*P + r)."""
        if self.virtual_stages == 1:
            return (self.n_pipe, self.layers_per_stage)
        return (self.n_pipe, self.virtual_stages, self.layers_per_stage)

    def init_params(self, rng=0) -> dict:
        return self.restack_params(self.inner.init_params(rng))

    def restack_params(self, flat: Mapping) -> dict:
        """Flat transformer store (``layer<i>/*``) restacked for the
        pipeline: per-layer params become ``blocks/<suffix>`` with
        leading [P, L/P] axes ([P, V, L/(P*V)] interleaved: layer l
        lives at [stage % P, stage // P, l % Lc] where stage = l // Lc —
        the Megatron round-robin chunk layout).  The inverse of
        :meth:`flat_params` — converts an EXISTING checkpoint (a dense
        pretrain, an HF conversion) for pipelined training."""
        out: dict = {}
        by_suffix: dict[str, list] = {}
        for i in range(self.config.n_layers):
            for name, value in flat.items():
                if name.startswith(f"layer{i}/"):
                    by_suffix.setdefault(self._block_suffix(name),
                                         []).append(value)
        lead = self._block_leading_shape()
        for suffix, values in by_suffix.items():
            stacked = jnp.stack(values)  # [L, ...] in layer order
            if self.virtual_stages > 1:
                # layer order is stage-major [(c,P),(r),(j)] -> [V,P,Lc];
                # swap to the rank-major [P,V,Lc] the pipe axis shards
                stacked = jnp.swapaxes(stacked.reshape(
                    self.virtual_stages, self.n_pipe,
                    self.layers_per_stage, *stacked.shape[1:]), 0, 1)
            else:
                stacked = stacked.reshape(*lead, *stacked.shape[1:])
            out[self.BLOCK_PREFIX + suffix] = stacked
        for name, value in flat.items():
            if not self._is_block_param(name):
                out[name] = value
        return out

    def flat_params(self, params: Mapping) -> dict:
        """Inverse of :meth:`init_params`' restack: a pipelined store
        (``blocks/*`` with [P(,V),Lc] leading axes) back to the plain
        ``layer<i>/*`` layout, so a pipeline-trained checkpoint loads into
        the unwrapped Transformer (generation/serving, or re-training at a
        different pipe/virtual_stages factorization)."""
        out: dict = {}
        lc = self.layers_per_stage
        for name, value in params.items():
            if not name.startswith(self.BLOCK_PREFIX):
                out[name] = value
                continue
            suffix = name[len(self.BLOCK_PREFIX):]
            value = jnp.asarray(value)
            if self.virtual_stages > 1:   # [P,V,Lc,...] -> stage-major
                value = jnp.swapaxes(value, 0, 1)
            stages = value.reshape(-1, lc, *value.shape[
                (3 if self.virtual_stages > 1 else 2):])
            for s in range(stages.shape[0]):
                for j in range(lc):
                    out[f"layer{s * lc + j}/{suffix}"] = stages[s, j]
        return out

    def num_params(self) -> int:
        return self.inner.num_params()

    def param_shapes(self) -> dict:
        shapes: dict = {}
        for name, shape in self.inner.param_shapes().items():
            if self._is_block_param(name):
                if name.startswith("layer0/"):
                    shapes[self.BLOCK_PREFIX + self._block_suffix(name)] = (
                        *self._block_leading_shape(), *shape)
            else:
                shapes[name] = shape
        return shapes

    # --------------------------------------------------------------- forward
    def _stage_fn(self, stage_params: dict, h: jax.Array) -> jax.Array:
        """Apply one scheduled stage's transformer blocks.  stage_params
        values have a leading layer axis (its static length is the block
        count — L/P plain, L/(P*V) interleaved); the loop is unrolled by
        trace.  Honors config.remat: each block recomputes its activations
        in the backward pass (jax.checkpoint), same trade as the plain
        model."""
        model = self.inner
        key = self._STAGE_KEY
        seq = h.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)

        def one_block(blk, h):
            q, k, v = model.qkv(blk, key, h, positions)
            attn = self._stage_attention(q, k, v)  # impls expand GQA K/V
            h = model.attn_residual(blk, key, h, attn)
            return model.mlp_residual(blk, key, h)

        apply_block = (jax.checkpoint(one_block) if self.config.remat
                       else one_block)
        n_layers = next(iter(stage_params.values())).shape[0]
        for j in range(n_layers):
            blk = {f"{key}/{suffix[len(self.BLOCK_PREFIX):]}": value[j]
                   for suffix, value in stage_params.items()}
            h = apply_block(blk, h)
        return h

    def _stage_fn_aux(self, stage_params: dict, h: jax.Array,
                      sharded_experts: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
        """MoE variant of :meth:`_stage_fn`: every block's FFN is the
        Switch/Mixtral MoE (config.moe_every == 1) and the stage returns
        (h, summed aux loss).  Expert capacity is computed per MICROBATCH
        (the tokens a stage sees per tick) — the standard microbatched-MoE
        semantics: which tokens drop depends on routing statistics within
        the microbatch, not the global batch.

        ``sharded_experts`` (set when running inside pipeline_apply's
        shard_map on a mesh with an ``expert`` axis > 1): each rank holds
        only its slice of every block's expert weights (pipe x expert
        2-D-sharded stacks — see loss()'s param_spec_fn); routing runs on
        the expert-replicated tokens, each rank computes its local
        experts' partial output, and a psum over ``expert`` combines —
        real expert parallelism composed orthogonally with the pipe axis."""
        from ..models.transformer import rms_norm

        model = self.inner
        key = self._STAGE_KEY
        seq = h.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)

        def one_block(blk, h):
            q, k, v = model.qkv(blk, key, h, positions)
            attn = self._stage_attention(q, k, v)
            h = model.attn_residual(blk, key, h, attn)
            x = rms_norm(h, blk[f"{key}/ln2/scale"],
                         model.config.norm_eps)
            if sharded_experts:
                count = blk[f"{key}/moe/w1"].shape[0]
                start = jax.lax.axis_index("expert") * count
                moe_out, aux = model._moe.apply(
                    blk, x, prefix=f"{key}/", expert_slice=(start, count))
                moe_out = jax.lax.psum(moe_out, "expert")
            else:
                moe_out, aux = model._moe.apply(blk, x, prefix=f"{key}/")
            return h + moe_out.astype(model.config.dtype), aux

        apply_block = (jax.checkpoint(one_block) if self.config.remat
                       else one_block)
        n_layers = next(iter(stage_params.values())).shape[0]
        aux_total = jnp.zeros((), jnp.float32)
        for j in range(n_layers):
            blk = {f"{key}/{suffix[len(self.BLOCK_PREFIX):]}": value[j]
                   for suffix, value in stage_params.items()}
            h, aux = apply_block(blk, h)
            aux_total = aux_total + aux
        return h, aux_total

    def loss(self, params: Mapping, batch) -> jax.Array:
        tokens = batch[0] if isinstance(batch, (tuple, list)) else batch
        if (self.config.pos_emb == "learned"
                and tokens.shape[1] > self.config.max_seq):
            # same trace-time guard as Transformer._forward: embed's
            # mode="clip" would otherwise silently reuse the last
            # positional row for every overlong position
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds the "
                f"learned-position table max_seq={self.config.max_seq}")
        # the model's own embed: adds the learned positional table for
        # GPT-2-family configs (a raw token-table take would silently
        # drop it); rope configs take positions inside each stage's qkv
        h = self.inner.embed(
            params, tokens,
            jnp.arange(tokens.shape[1], dtype=jnp.int32))
        stage_params = {name: value for name, value in params.items()
                        if name.startswith(self.BLOCK_PREFIX)}
        if self.config.moe_every == 1:
            ep = self.mesh.shape.get("expert", 1)
            sharded = (ep > 1 and self.n_pipe > 1
                       and self.config.moe_experts % ep == 0)
            spec_fn = None
            if sharded:
                def spec_fn(name, p):
                    # same definition the state-placement rule uses
                    # (_block_param_spec): no reshard at shard_map entry
                    return _block_param_spec(name, p.ndim, p.shape[2:3], ep)

            def stage(blk_params, h):
                return self._stage_fn_aux(blk_params, h,
                                          sharded_experts=sharded)

            h, aux = pipeline_apply(stage, stage_params, h,
                                    self.mesh, self.num_microbatches,
                                    with_aux=True, param_spec_fn=spec_fn)
            return (self._head_loss(params, h, tokens)
                    + self.config.moe_aux_coef * aux)
        if self.virtual_stages == 1:
            h = pipeline_apply(self._stage_fn, stage_params, h, self.mesh,
                               self.num_microbatches)
        else:
            # interleaved layout, forward-only (eval): one GPipe pass per
            # chunk — pass c applies global stages c*P .. c*P+P-1, so V
            # sequential passes traverse the layers in order
            for c in range(self.virtual_stages):
                chunk = {name: value[:, c]
                         for name, value in stage_params.items()}
                h = pipeline_apply(self._stage_fn, chunk, h, self.mesh,
                                   self.num_microbatches)
        return self._head_loss(params, h, tokens)

    def _head_loss(self, rest_params: Mapping, h: jax.Array,
                   tokens: jax.Array) -> jax.Array:
        """Per-microbatch LM-head loss (final norm -> logits -> NLL), the
        last pipeline stage's tail in the 1F1B schedule."""
        if self.config.loss_chunk:
            return self.inner._chunked_next_token_nll(rest_params, h, tokens)
        from ..models.transformer import next_token_nll
        return next_token_nll(self.inner.final_logits(rest_params, h),
                              tokens)

    def value_and_grad(self, params: Mapping, batch):
        """(loss, grads) under the configured schedule.  For "1f1b" this is
        the hand-written interleaved schedule below; "gpipe" (or a 1-wide
        pipe axis) differentiates the GPipe forward with jax.grad."""
        if self.schedule == "1f1b" and self.n_pipe > 1:
            return self._value_and_grad_1f1b(params, batch)
        return jax.value_and_grad(self.loss)(params, batch)

    def _value_and_grad_1f1b(self, params: Mapping, batch):
        """One-forward-one-backward pipeline schedule (PipeDream-flush /
        Megatron 1F1B, optionally INTERLEAVED over virtual stages),
        hand-written as an SPMD program.

        Why: GPipe-by-autodiff (jax.grad over :func:`pipeline_apply`) runs
        all M forwards, then all M backwards — every stage holds residuals
        for all M microbatches at the backward's start.  1F1B starts
        microbatch m's backward as soon as its forward leaves the last
        stage, bounding in-flight units per rank at K = 2*(P*V-1)+1
        regardless of M — activation memory O(P*V) instead of O(M).

        Rematerialized: each scheduled stage saves only its INPUT per
        in-flight unit (a [mb, S, D] block in a K-slot ring buffer) and
        recomputes the stage forward inside `jax.vjp` at backward time —
        the standard memory/compute trade for pipelined large models, and
        the same trade `config.remat` makes for the plain model.

        Schedule (P ranks, V chunks/rank, S = P*V global stages; stage
        s = c*P + r is rank r's chunk c — Megatron round-robin; microbatch
        m = G*P + i in groups of P):

          forward  of (m, s) at tick  t_f = G*P*V + c*P + i + r
          backward of (m, s) at tick  t_b = G*P*V + i + 2*(P*V-1) - c*P - r

        Both chains advance one ppermute per tick (+1 rotation forward,
        -1 backward; chunk boundaries ride the same wrap-around edge), the
        last global stage runs fwd(m) and bwd(m) in the same tick (its
        head cotangent is produced in-tick), and V=1 reduces exactly to
        the plain 1F1B formulas (t_f = m + r, t_b = m + 2(P-1) - r).
        T = t_b(M-1, stage 0) + 1 ticks total; interleaving (V>1) shrinks
        the pipeline-fill/drain bubble from ~2P stage-sized ticks to
        ~2PV chunk-sized ticks at 1/V the work each — the Megatron
        interleaved-schedule trade (more, smaller bubbles + V x the
        ppermute count).  Every rank executes every tick's fwd+vjp on
        (possibly garbage) data with validity masks zeroing the
        contributions — the SPMD-uniform formulation shard_map requires.

        Exactness: gradients equal jax.grad of the non-pipelined model
        (tests/test_pipeline.py::test_pipelined_lm_1f1b_* and
        *_interleaved_*).
        """
        from jax import lax

        tokens = batch[0] if isinstance(batch, (tuple, list)) else batch
        mesh, n_pipe, M = self.mesh, self.n_pipe, self.num_microbatches
        V = self.virtual_stages
        PV = n_pipe * V
        batch_axes = ("data", "fsdp")
        mb = _microbatch_size(mesh, batch_axes, tokens.shape[0], M)
        seq = tokens.shape[1]
        d_model = self.config.d_model
        K = 2 * (PV - 1) + 1      # in-flight ring-buffer slots per rank

        def t_fwd(m: int, c: int, r: int) -> int:
            grp, i = divmod(m, n_pipe)
            return grp * PV + c * n_pipe + i + r

        def t_bwd(m: int, c: int, r: int) -> int:
            grp, i = divmod(m, n_pipe)
            return grp * PV + i + 2 * (PV - 1) - c * n_pipe - r

        T = t_bwd(M - 1, 0, 0) + 1
        # static tick -> microbatch maps for the single-rank events: the
        # LAST stage (rank P-1, chunk V-1: head loss + cotangent seed) and
        # stage 0's backward (rank 0, chunk 0: embedding-lookup grad)
        head_m = {t_fwd(m, V - 1, n_pipe - 1): m for m in range(M)}
        embed_m = {t_bwd(m, 0, 0): m for m in range(M)}

        inner_embed = self.inner.embed
        learned_pos = self.config.pos_emb == "learned"
        positions_iota = jnp.arange(seq, dtype=jnp.int32)
        if learned_pos and seq > self.config.max_seq:
            raise ValueError(
                f"sequence length {seq} exceeds the learned-position "
                f"table max_seq={self.config.max_seq}")
        blocks = {k: v for k, v in params.items()
                  if k.startswith(self.BLOCK_PREFIX)}
        rest = {k: v for k, v in params.items()
                if not k.startswith(self.BLOCK_PREFIX)}
        # MoE (all-MoE blocks): the stage returns (h, aux) and the
        # schedule threads the aux-loss accumulator through the backward
        # wave — each valid unit's aux is read off the vjp's PRIMAL (the
        # recompute forward), and the aux cotangent seeds moe_aux_coef so
        # router/expert gradients ride the same stage_vjp as the
        # activation chain.  Expert-axis sharding stays GPipe-only: the
        # hand-written schedule seeds jax.vjp cotangents mid-shard_map,
        # which breaks the unreduced-cotangent convention the expert
        # psum's transpose relies on (measured: expert-weight grads come
        # out exactly ep x too large) — grad-of-the-whole-shard_map
        # (GPipe) pairs the transposes correctly, verified by
        # tests/test_pipeline.py::test_pipelined_moe_expert_sharded_matches.
        moe = self.config.moe_every == 1
        aux_coef = self.config.moe_aux_coef
        ep = mesh.shape.get("expert", 1)
        if moe and ep > 1:
            raise ValueError(
                "pipeline + MoE + expert-axis sharding requires "
                "schedule='gpipe' (the 1F1B schedule's manual vjp cannot "
                "thread the expert psum transpose); drop the expert axis "
                "or use gpipe")
        if moe:
            stage_fn = partial(self._stage_fn_aux, sharded_experts=False)
        else:
            stage_fn = self._stage_fn
        block_specs = {k: P("pipe", *([None] * (v.ndim - 1)))
                       for k, v in blocks.items()}
        rest_specs = {k: P() for k in rest}
        tok_spec = P(batch_axes, None)
        head_loss = self._head_loss
        acts_dtype = self.config.dtype
        Lc = self.layers_per_stage

        @partial(shard_map, mesh=mesh,
                 in_specs=(block_specs, rest_specs, tok_spec),
                 out_specs=(P(), block_specs, rest_specs),
                 check_vma=False)
        def run(blocks_in, rest_in, tok_local):
            my = lax.axis_index("pipe")

            def to_chunks(p):  # local [1,(V,)Lc,...] -> uniform [V,Lc,...]
                rest_shape = p.shape[2:] if V == 1 else p.shape[3:]
                return p[0].reshape(V, Lc, *rest_shape)

            my_chunks = jax.tree.map(to_chunks, blocks_in)
            tok_mb = tok_local.reshape(M, mb, seq)
            fwd_perm = [(i, (i + 1) % n_pipe) for i in range(n_pipe)]
            bwd_perm = [(i, (i - 1) % n_pipe) for i in range(n_pipe)]

            def chunk_view(c):
                """Chunk c's stage params ([Lc, ...] leaves); c may be a
                traced index (dynamic chunk selection per rank)."""
                if V == 1:
                    return jax.tree.map(lambda p: p[0], my_chunks)
                return jax.tree.map(
                    lambda p: lax.dynamic_index_in_dim(p, c, axis=0,
                                                       keepdims=False),
                    my_chunks)

            state = jnp.zeros((mb, seq, d_model), acts_dtype)
            cot_recv = jnp.zeros((mb, seq, d_model), jnp.float32)
            buf = jnp.zeros((K, mb, seq, d_model), acts_dtype)
            g_chunks = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), my_chunks)
            g_rest = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), rest_in)
            loss_acc = jnp.zeros((), jnp.float32)
            aux_acc = jnp.zeros((), jnp.float32)
            is_last_rank = my == n_pipe - 1

            def masked_add(acc, contrib, mask):
                return jax.tree.map(
                    lambda a, g: a + jnp.where(mask, g, 0.0).astype(
                        jnp.float32), acc, contrib)

            for t in range(T):
                # ---- forward unit: u = t - my decomposes to (G, c, i);
                # invalid units compute garbage that masks out downstream
                # (their buffer slots never alias a live unit's: lifetime
                # 2(PV-1-s) < K and u advances one per tick)
                u = t - my
                c_f = jnp.mod(u, PV) // n_pipe
                # stage-0 injection is rank 0 only, where u = t is STATIC:
                # embed microbatch m statically when rank 0's unit this
                # tick is a chunk-0 unit
                rem0, i0 = divmod(t % PV, n_pipe)
                m0 = (t // PV) * n_pipe + i0
                if rem0 == 0 and m0 < M:
                    # the model's embed adds the learned positional table
                    # for GPT-2-family configs; its backward is the
                    # hand-written scatter at the embed_m tick below
                    inj = inner_embed(rest_in, tok_mb[m0],
                                      positions_iota).astype(acts_dtype)
                    state_in = jnp.where(my == 0, inj, state)
                else:
                    state_in = state
                f_slot = jnp.mod(u, K)
                buf = lax.dynamic_update_index_in_dim(buf, state_in,
                                                      f_slot, axis=0)
                state_out = stage_fn(chunk_view(jnp.clip(c_f, 0, V - 1)),
                                     state_in)
                if moe:  # aux is collected on the backward wave instead
                    state_out, _ = state_out

                # ---- head: loss + cotangent seed on the LAST stage's
                # (static) ticks; by the t_b identity the same rank's bwd
                # unit this tick IS (m, last stage), so cot feeds straight
                # through
                if t in head_m:
                    def head(rp, h, _tok=tok_mb[head_m[t]]):
                        return head_loss(rp, h, _tok)
                    lval, head_vjp = jax.vjp(head, rest_in,
                                             state_out.astype(jnp.float32))
                    g_rest_m, cot_head = head_vjp(jnp.ones((), lval.dtype))
                    loss_acc = loss_acc + jnp.where(is_last_rank, lval, 0.0)
                    g_rest = masked_add(g_rest, g_rest_m, is_last_rank)
                    cot = jnp.where(is_last_rank, cot_head, cot_recv)
                else:
                    cot = cot_recv

                # ---- backward unit: y = t + my - 2(PV-1) decomposes via
                # i = y mod P, q = (y - i)/P = G*V - c, G = ceil(q/V)
                dx_send = jnp.zeros((mb, seq, d_model), jnp.float32)
                if t >= PV - 1:
                    y = t + my - 2 * (PV - 1)
                    i_b = jnp.mod(y, n_pipe)
                    q = (y - i_b) // n_pipe
                    G_b = -((-q) // V)          # ceil(q / V)
                    c_b = G_b * V - q           # in [0, V) by construction
                    m_b = G_b * n_pipe + i_b
                    bvalid = (G_b >= 0) & (m_b < M)
                    u_b = G_b * PV + c_b * n_pipe + i_b
                    saved_in = lax.dynamic_index_in_dim(
                        buf, jnp.mod(u_b, K), axis=0, keepdims=False)
                    chunk_b = chunk_view(c_b)
                    primal, stage_vjp = jax.vjp(stage_fn, chunk_b,
                                                saved_in)
                    if moe:
                        # the vjp's primal IS the recompute forward, so
                        # the unit's aux comes for free; seeding the aux
                        # cotangent with its loss weight sends router/
                        # expert gradients down the same backward
                        aux_acc = aux_acc + jnp.where(bvalid, primal[1],
                                                      0.0)
                        g_blk_m, dx = stage_vjp(
                            (cot.astype(acts_dtype),
                             jnp.asarray(aux_coef, jnp.float32)))
                    else:
                        g_blk_m, dx = stage_vjp(cot.astype(acts_dtype))
                    if V == 1:
                        g_chunks = masked_add(
                            g_chunks,
                            jax.tree.map(lambda g: g[None], g_blk_m),
                            bvalid)
                    else:
                        g_chunks = jax.tree.map(
                            lambda a, g: a.at[c_b].add(
                                jnp.where(bvalid, g, 0.0).astype(
                                    jnp.float32)), g_chunks, g_blk_m)
                    dx_send = jnp.where(bvalid, dx.astype(jnp.float32), 0.0)
                    if t in embed_m:  # rank 0 / chunk 0: embedding bwd
                        emb_mask = jnp.where((my == 0) & bvalid, 1.0, 0.0)
                        g_rest["embed/tok"] = (
                            g_rest["embed/tok"].at[tok_mb[embed_m[t]]].add(
                                dx_send * emb_mask))
                        if learned_pos:
                            # h = tok_table[tokens] + pos_table[0..S-1]:
                            # the positional rows see every microbatch at
                            # the same positions, so their cotangent is
                            # the batch-sum of dx
                            g_rest["embed/pos"] = (
                                g_rest["embed/pos"].at[:seq].add(
                                    jnp.sum(dx_send * emb_mask, axis=0)))

                # ---- rotate activations forward, cotangents backward
                if t < T - 1:
                    state = lax.ppermute(state_out, "pipe", fwd_perm)
                    cot_recv = lax.ppermute(dx_send, "pipe", bwd_perm)

            # reductions: microbatch mean, then mean over the data shards;
            # loss/head/embed live on single ranks -> share over pipe.
            # MoE: the aux term joins with its coefficient — the reported
            # loss matches the GPipe path's head + coef * aux
            total_acc = (loss_acc + aux_coef * aux_acc if moe
                         else loss_acc)
            loss = lax.pmean(lax.psum(total_acc, "pipe") / M, batch_axes)
            g_blocks = jax.tree.map(
                lambda g, p: lax.pmean(
                    g.reshape(p[0].shape) / M, batch_axes).astype(
                        p.dtype)[None], g_chunks, blocks_in)
            g_rest = jax.tree.map(
                lambda g, p: lax.pmean(lax.psum(g, "pipe") / M,
                                       batch_axes).astype(p.dtype),
                g_rest, rest_in)
            return loss, g_blocks, g_rest

        loss, g_blocks, g_rest = run(blocks, rest, tokens)
        grads = dict(g_blocks)
        grads.update(g_rest)
        return loss, {name: grads[name] for name in params}


def pipeline_rule(mesh: Mesh):
    """Sharding rule for a PipelinedTransformerLM store: ``blocks/*`` get
    ``pipe`` on the stage axis (stage s's weights live on pipe rank s);
    everything else is replicated over pipe and falls through to the plain
    transformer rule (embed/head/norms).  Block trailing dims stay unsharded
    so the shard_map stage sees whole per-layer weights — combine pipe with
    data parallelism, not TP/fsdp-in-block (see pipeline_apply)."""
    from ..models.transformer import transformer_rule

    base = transformer_rule(mesh)

    n_exp = mesh.shape.get("expert", 1)

    def rule(name: str, shape: tuple) -> P:
        if name.startswith(PipelinedTransformerLM.BLOCK_PREFIX):
            return _block_param_spec(name, len(shape), shape[2:3], n_exp)
        return base(name, shape)

    return rule


def _block_param_spec(name: str, ndim: int, expert_dim: tuple,
                      n_exp: int) -> P:
    """THE spec for a stacked ``blocks/*`` param — the single definition
    shared by :func:`pipeline_rule` (state placement) and the MoE loss's
    shard_map in_specs, so stored state and shard_map entry can never
    drift apart (drifting costs a silent reshard every step).  MoE expert
    stacks [P, Lc, E, ...] go pipe x expert 2-D when the expert axis can
    divide E; everything else is pipe on the stage axis only."""
    if (n_exp > 1 and (name.endswith("moe/w1") or name.endswith("moe/w2"))
            and expert_dim and expert_dim[0] % n_exp == 0):
        return P("pipe", None, "expert", *([None] * (ndim - 3)))
    return P("pipe", *([None] * (ndim - 1)))


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array,
                   mesh: Mesh, num_microbatches: int,
                   batch_axes: tuple[str, ...] = ("data", "fsdp"),
                   with_aux: bool = False,
                   param_spec_fn: Callable | None = None) -> jax.Array:
    """Run ``x`` through P pipelined stages.

    stage_fn(params_i, h) -> h applies ONE stage.  stage_params is the
    stacked store from :func:`stack_stage_params` ([P, ...] leading axis).
    x: [B, ...] with B divisible by num_microbatches (and by the data axes).
    Shape-preserving stages (d_in == d_out), the usual transformer-block
    case.

    ``with_aux``: stage_fn returns (h, aux scalar) — MoE load-balance
    loss.  Ticks where a rank processes fill/drain garbage are masked out;
    the returned aux is the mean over microbatches of the per-microbatch
    stage sums (the standard microbatched-MoE aux semantics).  Returns
    (out, aux).
    """
    n_pipe = mesh.shape["pipe"]
    if n_pipe == 1:
        params0 = jax.tree.map(lambda p: p[0], stage_params)
        if not with_aux:
            return stage_fn(params0, x)
        # Preserve the per-MICROBATCH contract on a 1-wide pipe axis too:
        # expert capacity / routing aux are microbatch statistics, so the
        # batch still goes through in num_microbatches slices (otherwise
        # collapsing pipe to 1 would silently switch MoE dropping to
        # whole-batch capacity and change the training trajectory).
        if x.shape[0] % num_microbatches:
            raise ValueError(f"batch {x.shape[0]} must divide by "
                             f"num_microbatches={num_microbatches}")
        mb = x.shape[0] // num_microbatches
        outs = []
        aux_acc = jnp.zeros((), jnp.float32)
        for i in range(num_microbatches):
            h, aux = stage_fn(params0, x[i * mb:(i + 1) * mb])
            outs.append(h)
            aux_acc = aux_acc + aux
        return jnp.concatenate(outs), aux_acc / num_microbatches

    mb = _microbatch_size(mesh, batch_axes, x.shape[0], num_microbatches)

    if param_spec_fn is None:
        param_specs = jax.tree.map(
            lambda p: P("pipe", *([None] * (p.ndim - 1))), stage_params)
    else:
        # per-name specs (stage_params is a flat name->array store):
        # lets MoE stacks shard pipe x expert 2-D (see the pipelined LM)
        param_specs = {name: param_spec_fn(name, p)
                       for name, p in stage_params.items()}
    x_spec = P(batch_axes, *([None] * (x.ndim - 1)))
    out_specs = (x_spec, P()) if with_aux else x_spec

    @partial(shard_map, mesh=mesh,
             in_specs=(param_specs, x_spec), out_specs=out_specs,
             check_vma=False)
    def run(params, x_local):
        my = jax.lax.axis_index("pipe")
        my_params = jax.tree.map(lambda p: p[0], params)  # [1,...] -> [...]
        x_mb = x_local.reshape(num_microbatches, mb, *x_local.shape[1:])
        state = jnp.zeros_like(x_mb[0])
        out = jnp.zeros_like(x_mb)
        aux_acc = jnp.zeros((), jnp.float32)
        fwd = [(i, (i + 1) % n_pipe) for i in range(n_pipe)]
        for t in range(num_microbatches + n_pipe - 1):
            # stage 0 injects microbatch t during the fill phase
            if t < num_microbatches:
                state = jnp.where(my == 0, x_mb[t], state)
            if with_aux:
                state, aux = stage_fn(my_params, state)
                # rank r processes microbatch t-r this tick; anything else
                # is fill/drain garbage whose routing stats must not leak
                # into the aux loss
                valid = jnp.logical_and(t - my >= 0,
                                        t - my < num_microbatches)
                aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            else:
                state = stage_fn(my_params, state)
            # last stage emits microbatch t-(P-1) during the drain phase
            out_idx = t - (n_pipe - 1)
            if 0 <= out_idx < num_microbatches:
                emit = jnp.where(my == n_pipe - 1, state, jnp.zeros_like(state))
                out = out.at[out_idx].set(emit)
            if t < num_microbatches + n_pipe - 2:
                state = jax.lax.ppermute(state, "pipe", fwd)
        # outputs live on the last rank; share them with every rank so the
        # loss (and its gradient) is computed replicated over pipe
        out = jax.lax.psum(out, "pipe")
        out = out.reshape(x_local.shape)
        if with_aux:
            aux = jax.lax.psum(aux_acc, "pipe") / num_microbatches
            # replicate over the batch axes too (P() out_spec): each data
            # shard routed different tokens, so average their aux
            for ax in batch_axes:
                if mesh.shape.get(ax, 1) > 1:
                    aux = jax.lax.pmean(aux, ax)
            return out, aux
        return out

    return run(stage_params, x)
