"""Continuous-batching decode server: the serving runtime over the ragged
KV-cache machinery.

Static-shape TPU serving has a classic tension: the device wants one fixed
[B, ...] decode program compiled once, but requests arrive and finish at
arbitrary times.  The resolution (the pattern behind production LLM
servers) is **slot-based continuous batching**:

- the KV cache is allocated once with B slots;
- every device step decodes ALL B slots in one ragged ``decode_block``
  (per-row lengths — rows sit at different positions), one compiled
  program, no retraces;
- a request occupies a slot from submit to EOS/limit; a finished slot is
  immediately refillable by the next request via a prefill whose K/V are
  spliced into that slot's cache rows while the other slots' state is
  untouched — admission never pauses in-flight decodes.

Prefill pads prompts up to a power-of-two bucket so only a handful of
prefill programs ever compile.  Pad positions write garbage K/V beyond
the row's real length — harmless by construction: the ragged attention
mask hides positions >= length, and subsequent decode steps overwrite
exactly those cache rows.

The reference has no serving path at all (no model, no inference —
reference src/worker.cpp:316-329 fabricates 0.01-gradients); this is
TPU-native added capability alongside generation.py's one-shot decoders.
Composes with the int8 serving stack: ``cache_dtype="int8"`` quantizes
the slot cache (generation.QuantKVCache), and a models/quant.py
weight-quantized ``params`` store works unchanged.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
import weakref
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import flight
from ..obs import legs as obs_legs
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from .generation import (KVCache, QuantKVCache, _cached_runner,
                         _kv_quantize, _model_key, _spec_round_runner,
                         check_position_budget, check_rolls_back,
                         decode_block, full_round_block, heads_major,
                         heads_per_row, positions_major,
                         init_cache, pack_heads, ring_layers_of, sample_token,
                         sample_token_rowwise, split_row, state_shape)
from .prefix_tree import PrefixTree, RowRef
from .transformer import Transformer

Array = jax.Array


@dataclasses.dataclass
class _Slot:
    request_id: int
    tokens: list[int]          # generated tokens so far
    max_new: int
    done: bool = False
    # per-request finish tokens checked alongside the server eos_id
    stop: frozenset = frozenset()


@dataclasses.dataclass
class _Flight:
    """A plain decode round that has been dispatched and whose tokens are
    still on the device."""
    out: tuple                 # (tokens [B], counted): the round's outputs
    lanes: dict[int, _Slot]    # slot -> the request it decoded a token for
    positions: int             # positions its lanes held, new token included
    fetched: int               # positions a full layer's arm fetched for them


# rows longer than this are padded to its next multiple, not to the next
# power of two: a 12,288-token row stays 12,288 wide (a power of two would
# make it 16,384, and with a suffix it would no longer fit a 16,384 slot)
_FINE_BUCKET = 2048


def _bucket(n: int, lo: int = 16) -> int:
    if n > _FINE_BUCKET:
        return -(-n // _FINE_BUCKET) * _FINE_BUCKET
    b = lo
    while b < n:
        b *= 2
    return b


def _no_layers(positions: int) -> Array:
    """The K (or V) of a row none of whose layers keeps K/V by position:
    no layer, ``positions`` wide (a row's first leaf says how wide it is)."""
    return jnp.zeros((0, positions, 1, 1), jnp.int8)


def _row_tail(model: Transformer, row) -> tuple[tuple, tuple]:
    """What a native row holds past its K and V: (its latent layers' rows,
    a layer each; its state layers' snapshots, a tuple of states a layer).
    In the row they lie flat: the latent layers stacked in one leaf, then
    every state of every state layer a leaf of its own (their shapes may
    differ: :func:`state_shape`)."""
    tail = list(row[2:])
    latent = (tuple(tail.pop(0)) if model.config.layers_keeping("latent")
              else ())
    states = []
    for layer in state_shape(model):
        states.append(tuple(tail[:len(layer)]))
        del tail[:len(layer)]
    return latent, tuple(states)


def _row_nbytes(row) -> int:
    """Device bytes pinned by one cached K/V row (native: (k, v); int8:
    (k8, v8, k_scale, v_scale)) — what the radix tree's byte-accounted
    LRU charges against PSDT_PREFIX_CACHE_BYTES."""
    return sum(int(leaf.nbytes) for leaf in row)


def _place_params(params, mesh, rule):
    """Place a (possibly int8-quantized) store on the mesh.  Dense leaves
    take the rule's spec directly; a QTensor's int8 matrix takes the spec
    of its own shape and the per-output-channel scale inherits the same
    mesh axes minus the contracted (-2) dim — so a tensor-column-sharded
    weight keeps its scale tensor-sharded alongside it and the wdot
    product needs no resharding."""
    from jax.sharding import NamedSharding, PartitionSpec

    from .quant import QTensor

    out = {}
    for name, value in params.items():
        if isinstance(value, QTensor):
            spec = rule(name, tuple(value.q.shape))
            # PartitionSpec may legally omit trailing replicated dims —
            # pad to full rank so the -2/-1 slicing below always refers
            # to the contracted/output axes
            axes = list(spec) + [None] * (value.q.ndim - len(spec))
            scale_axes = axes[:-2] + [axes[-1]]
            out[name] = QTensor(
                jax.device_put(value.q, NamedSharding(mesh, spec)),
                jax.device_put(value.scale,
                               NamedSharding(mesh,
                                             PartitionSpec(*scale_axes))))
        else:
            spec = rule(name, tuple(value.shape))
            out[name] = jax.device_put(value, NamedSharding(mesh, spec))
    return out


def _shard_cache(cache, mesh):
    """Place the slot cache on the mesh: batch over ``data``, the rows of
    kv heads over ``tensor`` (where divisible), everything else
    replicated.  K/V leaves are [B, M, KV / pack, pack * D], a layer each;
    int8 scale leaves [B, M, KV]; length is scalar."""
    from jax.sharding import NamedSharding, PartitionSpec

    def place(leaf):
        ndim = getattr(leaf, "ndim", 0)
        if ndim < 3:
            spec = PartitionSpec()
        else:
            data = ("data" if mesh.shape.get("data", 1) > 1
                    and leaf.shape[0] % mesh.shape["data"] == 0 else None)
            tensor = ("tensor" if mesh.shape.get("tensor", 1) > 1
                      and leaf.shape[2] % mesh.shape["tensor"] == 0
                      else None)
            spec = PartitionSpec(*([data, None, tensor]
                                   + [None] * (ndim - 3)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    cache = jax.tree_util.tree_map(place, cache)
    # (a round over several devices is GSPMD's to partition: no kernel)
    return (dataclasses.replace(cache, devices=mesh.size)
            if isinstance(cache, KVCache) else cache)


def _builds_few(model: Transformer) -> bool:
    """Whether the server keeps this model's admission programs FEW
    (``Mixer.few_programs`` of any of its layers' kinds): a model
    with recurrent layers (kda, gdn, ssm), whose every program is all its
    layers unrolled around a chunked recurrence (10 to 20 s of the
    compiler's time
    each on a cold start; four resident contexts and their turns were 70
    programs and 515 s, PERF.md section 6, PR 47), and a model with LATENT
    layers, whose block of fewer than ``generation._BLOCKWISE_QUERIES``
    tokens takes the absorbed form in plain XLA: [heads, block, lane] float32
    scores against the part as it lies, whatever the context's length (0.5
    GB a layer for 64 tokens at 128 heads and 16,384 positions), where a
    block of 256 runs blockwise over the context's own key blocks.  One
    extension program a prefix bucket
    (:func:`_suffix_floor`), built ahead (``DecodeServer._build_ahead``),
    and one prefill program for every prompt of a chunk or more
    (:func:`_prefills_whole`)."""
    return any(spec.kind.few_programs for spec in model.config.specs)


def _suffix_floor(model: Transformer) -> int:
    """The smallest suffix bucket of an extension: 16; 256 for a model that
    :func:`_builds_few`.  Every (prefix bucket, suffix bucket) pair is a
    program of all the layers and a splice for its row's width (8 to 12 s
    of the compiler's time on a cold start), and such a model's admission is
    bound by what it READS whatever the block: its weights (a matrix in
    bfloat16 is read no faster than 240 rows multiply it on a v5e, 197
    TFLOP/s over 819 GB/s), the row and the snapshot it restores; its delta
    rule takes a block in chunks of ``DELTA_CHUNK`` either way (an ssm
    layer's dual form in ONE chunk of ``SSM_CHUNK``), and its
    latent layers attend a block of 256 by key block where a shorter one
    reads the whole lane.  So the
    turns of a conversation (16 to 256 tokens) share ONE program a prefix
    bucket, where powers of two from 16 would build five."""
    return 256 if _builds_few(model) else 16


def _prefill_runner(model: Transformer, bucket: int, cache_dtype: str):
    """Jitted per (model, prompt bucket): forward the padded prompt, return
    the last REAL position's logits, the prompt's ROW and the tokens per
    expert of every experts layer ([L * E], else None).  A row is (k, v):
    every layer that keeps K/V by position, heads side by side as the
    cache's parts hold them, [L, S', KV / pack, pack * D] (quantized
    already when the slot cache is int8, so splicing is dtype-pure; no
    layer at all where none keeps K/V: :func:`_no_layers`).  A model with
    latent layers adds their rows, [L_latent, S', latent_row];
    one with state layers every state of every such layer after the last
    real position, a leaf each (:func:`_row_tail`): a SNAPSHOT, good at
    that depth and no other."""
    key = (_model_key(model), "serve_prefill", bucket, cache_dtype)

    def build():
        @jax.jit
        def run(params, padded, real_len):
            # the head runs on the last REAL position alone: the logits
            # of a whole long row would be gigabytes
            routed: list = []
            h, kept, _ = model._forward(params, padded, collect_kv=True,
                                        route_stats=routed,
                                        counts=real_len[None])
            loads = jnp.concatenate(routed) if routed else None
            last = model.final_logits(
                params, jax.lax.dynamic_slice_in_dim(
                    h, real_len - 1, 1, axis=1))[0, 0]      # [vocab]
            c = model.config
            pack = heads_per_row(c.kv_heads, c.head_dim)
            states, latents = c.state_layers, c.layers_keeping("latent")
            kvs = [kv for i, kv in enumerate(kept)
                   if i not in states + latents]
            if not kvs:
                k = v = _no_layers(bucket)
                pack = 1
            else:
                k = jnp.stack([k for k, _ in kvs])[:, 0]    # [L, S', H, D]
                v = jnp.stack([v for _, v in kvs])[:, 0]
            if states or latents:
                # the latent layers' rows; then the snapshot: every state
                # of every state layer after the last real position
                tail = [jnp.stack([kept[i][0] for i in latents])
                        ] if latents else []
                tail += [state[0] for i in states for state in kept[i]]
                return last, (pack_heads(k, pack), pack_heads(v, pack),
                              *tail), loads
            if cache_dtype == "int8":
                k, ks = _kv_quantize(k)
                v, vs = _kv_quantize(v)
                return last, (pack_heads(k, pack), pack_heads(v, pack),
                              ks, vs), loads
            return last, (pack_heads(k, pack), pack_heads(v, pack)), loads

        return run

    return _cached_runner(key, build)


def _splice_runner(model: Transformer, bucket: int, cache_dtype: str):
    """Jitted per (model, bucket): write one prefilled row's K/V into slot
    ``slot`` of the batch cache (dynamic slot index — one program serves
    every slot).  A row holds every layer by position; a layer the cache
    keeps as a ring takes the last ring's worth before ``length``."""
    key = (_model_key(model), "serve_splice", bucket, cache_dtype)

    def build():
        # donate the cache: the host drops its old reference immediately,
        # so XLA updates the (large) K/V parts in place
        @partial(jax.jit, donate_argnums=(0,))
        @jax.named_scope("splice")
        def run(cache, row, slot, length):
            def put(part, new):
                """one layer of the row into its part, at the slot"""
                return jax.lax.dynamic_update_slice(
                    part, new[None].astype(part.dtype),
                    (slot,) + (0,) * (part.ndim - 1))

            if cache_dtype != "int8":
                latent, states = _row_tail(model, row)
                k, v, wk, wv = split_row(cache, *row[:2], length)
                ck = ()
                if cache.sparse_layers:
                    from ..ops.sparse_attention import compress_keys

                    # a sparse layer's K/V go in by head; its compressed
                    # keys are made from its keys (those past ``length``
                    # are not complete, and the rounds that complete them
                    # write them)
                    kv_heads = model.config.kv_heads
                    k, v = (tuple(
                        heads_major(layer, kv_heads) if cache.by_head(i)
                        else layer for i, layer in enumerate(layers))
                        for layers in (k, v))
                    ck = tuple(compress_keys(
                        k[cache.place(i)[1]], model.config.sparse, axis=1)
                        for i in cache.sparse_layers)
                row = (k, v, wk, wv, ck, states, latent)
            return dataclasses.replace(cache, **{
                name: jax.tree.map(put, getattr(cache, name), tuple(layers))
                for name, layers in zip(cache.PARTS, row)})

        return run

    return _cached_runner(key, build)


def _row_cache(model: Transformer, row, total: int, cache_dtype: str):
    """A one-slot cache of ``total`` positions seeded with a row, every
    layer stored by position (a window is then a mask)."""
    def part(layer, fill=0):
        wide = jnp.full((1, total) + layer.shape[1:], fill, layer.dtype)
        return jax.lax.dynamic_update_slice(wide, layer[None],
                                            (0,) * wide.ndim)

    length = jnp.zeros((), jnp.int32)
    if cache_dtype == "int8":
        k8, v8, ks, vs = row
        return QuantKVCache(
            k=tuple(map(part, k8)), v=tuple(map(part, v8)),
            k_scale=tuple(part(layer, 1) for layer in ks),
            v_scale=tuple(part(layer, 1) for layer in vs),
            length=length, max_len=total)
    c = model.config
    k, v = row[:2]
    latent, state = _row_tail(model, row)
    sparse, states = c.layers_of("sparse"), c.state_layers
    latents = c.layers_keeping("latent")
    kept = [i for i in range(c.n_layers) if i not in states + latents]

    def stored(layers) -> tuple:
        """a sparse layer's part by head, every other as the row has it"""
        return tuple(
            heads_major(wide, c.kv_heads) if i in sparse else wide
            for i, wide in zip(kept, (part(layer.astype(c.dtype))
                                      for layer in layers)))

    return KVCache(
        k=stored(k), v=stored(v),
        # (a block forwarded against the row makes every compressed key
        # anew from the keys: decode_block)
        ck=tuple(jnp.zeros((1, c.kv_heads, total // c.sparse.stride,
                            c.head_dim), c.dtype) for _ in sparse),
        state=tuple(tuple(leaf[None] for leaf in layer) for layer in state),
        latent=tuple(map(part, latent)),
        sparse_layers=sparse, state_layers=states, latent_layers=latents,
        length=length, max_len=total)


def _cache_row(cache) -> tuple:
    """The row of a one-slot cache that stores every layer by position
    (with its latent layers' rows and its state layers' states, where it
    has any: :func:`_row_tail`)."""
    if isinstance(cache, QuantKVCache):
        return tuple(jnp.stack([part[0] for part in getattr(cache, name)])
                     for name in cache.PARTS)

    def stacked(held):
        if not held:
            return _no_layers(cache.max_len)
        # (a sparse layer's part lies by head: back to the row's form)
        return jnp.stack([
            positions_major(part[0], heads_per_row(*part.shape[1::2]))
            if cache.by_head(i) else part[0] for i, part in enumerate(held)])

    tail = [jnp.stack([part[0] for part in cache.latent])
            ] if cache.latent else []
    tail += [state[0] for layer in cache.state for state in layer]
    return (stacked(cache.k), stacked(cache.v), *tail)


def _extend_runner(model: Transformer, pbucket: int, sbucket: int,
                   cache_dtype: str):
    """Jitted per (model, prefix bucket, suffix bucket): extend a cached
    prefix row by forwarding ONLY the suffix tokens against it — the
    shared-prefix half of the prompt cache.  The suffix runs through the
    same ragged ``decode_block`` a decode round uses (a [1, sbucket]
    block against a single-row cache seeded with the prefix K/V), so the
    suffix's K/V and logits are exactly what submitting the prefix and
    then decoding forward would have computed; pad positions past the
    real suffix write garbage beyond the frontier, masked and
    overwritten exactly like prefill pad positions.  Returns the last
    REAL suffix position's logits, the combined (prefix + suffix) K/V
    row, ready for the ordinary slot splice, and the experts layers'
    tokens per expert as :func:`_prefill_runner` returns them."""
    key = (_model_key(model), "serve_extend", pbucket, sbucket,
           cache_dtype)

    def build():
        @jax.jit
        def run(params, row, padded_suffix, prefix_len, suffix_len):
            routed: list = []
            logits, cache = decode_block(
                model, params, padded_suffix,
                _row_cache(model, row, pbucket + sbucket, cache_dtype),
                lengths=prefix_len[None], counts=suffix_len[None],
                route_stats=routed)
            loads = jnp.concatenate(routed) if routed else None
            return logits[0, suffix_len - 1], _cache_row(cache), loads

        return run

    return _cached_runner(key, build)


# a prompt is forwarded whole while the widest activation of its forward
# pass, [bucket, the feed-forward width a token meets], has at most this
# many elements (256 MB in bfloat16; the program's temporaries are several
# of them: 2.5 GB at 12,288 x 16,384, read on the chip, PERF.md PR 32);
# a wider one goes through _chunk_runner _PREFILL_CHUNK tokens at a time
_PREFILL_WHOLE = 1 << 27
_PREFILL_CHUNK = 4096


def _prefills_whole(model: Transformer, bucket: int) -> bool:
    """The rule of the two prefill paths, from shapes alone.  A model that
    :func:`_builds_few` takes every prompt of a chunk or more in chunks:
    they share ONE program (``DecodeServer._prefill_in_chunks`` fills a
    row a lane wide and keeps its bucket's worth), where a whole prefill
    is a program a bucket."""
    c = model.config
    if _builds_few(model) and bucket >= _PREFILL_CHUNK:
        return False
    widest = max([c.d_model] + [
        c.d_ff if spec.ffn == "mlp" else c.moe_top_k * (
            c.expert_width if spec.ffn == "experts" else c.d_ff)
        for spec in c.specs] + [
        # (a recurrent layer's channels through its convolution, side by
        # side: ``Mixer.widest``)
        spec.kind.widest(c) for spec in c.specs])
    return bucket * widest <= _PREFILL_WHOLE


def _chunk_runner(model: Transformer, total: int):
    """Jitted per (model, row width): forward the next ``_PREFILL_CHUNK``
    tokens of a long prompt against the one-slot cache that holds what
    came before them (donated: the row is written where it lies).  The
    same ragged ``decode_block`` as an extension; returns the logits of
    the chunk's last REAL token and the cache."""
    key = (_model_key(model), "serve_chunk", total)

    def build():
        @partial(jax.jit, donate_argnums=(2,))
        def run(params, tokens, cache, done, real):
            logits, cache = decode_block(
                model, params, tokens, cache, lengths=done[None],
                counts=real[None], only=real[None] - 1)
            return logits[0, 0], cache

        return run

    return _cached_runner(key, build)


def _empty_row_runner(model: Transformer, total: int):
    """Jitted per (model, row width): (an empty one-slot cache of ``total``
    positions, every layer by position, for _chunk_runner to fill; its row
    when it is full)."""
    key = (_model_key(model), "serve_empty_row", total)

    def build():
        c = model.config
        pack = heads_per_row(c.kv_heads, c.head_dim)
        latents = c.layers_keeping("latent")
        kv = jnp.zeros((c.n_layers - len(c.state_layers) - len(latents), 16,
                        c.kv_heads // pack, pack * c.head_dim), c.dtype)
        tail = [jnp.zeros((len(latents), 16, c.latent_row),
                          c.dtype)] if latents else []
        tail += [jnp.zeros(shape, dtype) for layer in state_shape(model)
                 for shape, dtype in layer]
        return (jax.jit(lambda: _row_cache(model, (kv, kv, *tail), total,
                                           "native")),
                jax.jit(_cache_row))

    return _cached_runner(key, build)


def _step_runner(model: Transformer, slots: int,
                 top_k: int, top_p: float, cache_dtype: str):
    """Jitted once per (model, B, truncation config): one ragged decode
    step over ALL slots + per-row-temperature sampling (temperatures are
    a traced [B] input, so per-request values never recompile).  Free/
    done slots decode garbage lanes that the host discards — the price
    of a single static program.

    A lane's token comes from ``prev``, the round before's tokens as that
    round left them on the device, unless the host names one in ``fresh``
    (-1: none): the round can be dispatched before the host has seen the
    tokens it decodes from.

    Where a layer of the model reads which lanes hold a request
    (:func:`_mask_layers`: a state layer whose kernel moves live lanes
    alone, an ``experts`` layer), ``fresh`` also says so, in the same
    upload: ``_IDLE`` names a lane no request decodes in (its token is
    whatever ``prev`` holds: nobody reads what it decodes), and the round's
    ``counts`` are formed from it on the device.  Every consumer of
    ``counts`` in the model then sees 0 for that lane: its experts route
    nothing for it (an expert only idle lanes chose has no rows and is not
    read), its rings, registers and states stand still (an idle lane's
    contents are nobody's: an admission's splice writes every part of the
    lane it is given).  Any other model traces the program it always
    has."""
    key = (_model_key(model), "serve_step", slots, top_k, top_p,
           cache_dtype)

    def build():
        masked = bool(_mask_layers(model))

        # donate the cache: without it every per-token step would copy the
        # whole K/V — doubling HBM traffic in the exact loop this server
        # exists to keep bandwidth-bound
        @partial(jax.jit, donate_argnums=(3,))
        def run(params, prev, fresh, cache, lengths, temps, rng):
            return _decode_round(
                model, top_k, top_p, params,
                jnp.where(fresh < 0, prev, fresh), cache, lengths, temps,
                rng, (fresh != _IDLE).astype(jnp.int32) if masked else None)

        return run

    return _cached_runner(key, build)


# in a round's ``fresh`` (beside -1, a lane chained to the round before)
# and in a fused block's tokens: a lane that holds no request
_IDLE = -2


def _mask_layers(model: Transformer) -> tuple[int, ...]:
    """The layers of ``model`` that read which lanes of a decode round hold
    a request (``decode_block``'s ``counts``: 1 live, 0 idle;
    ``LayerSpec.reads_live_lanes``): those whose kind has a round kernel
    that moves live lanes alone (ops/pallas/ssd_decode.py), whose states of
    an idle lane then stay where they are, the matrix unread, and those
    whose feed-forward branch is ``experts``, which route the live lanes'
    tokens alone, so that an expert only idle lanes chose is not read.  A
    round of a model with such a layer is told; the host always knows the
    mask, and it enters a traced program only where this says so."""
    c = model.config
    return tuple(i for i in range(c.n_layers)
                 if c.layer_spec(i).reads_live_lanes)


def _decode_round(model, top_k, top_p, params, tokens, cache, lengths,
                  temps, rng, counts=None):
    """ONE plain decode round — the single definition both the per-round
    program (_step_runner) and the fused scan (_multi_step_runner) jit,
    so step_many's token-exactness vs a step() loop holds by
    construction (same decode_block -> rng split -> rowwise sample
    sequence).  ``counts`` [B]: 1 for a lane that holds a request, 0 for
    an idle one (None: the model reads no such mask)."""
    routed: list = []
    selected: list = []
    logits, cache = decode_block(model, params, tokens[:, None], cache,
                                 lengths=lengths, counts=counts,
                                 route_stats=routed, sparse_stats=selected)
    with jax.named_scope("sample"):
        rng, sub = jax.random.split(rng)
        nxt = sample_token_rowwise(logits[:, 0], sub, temps, top_k, top_p)
    # tokens per expert of every experts layer ([L * E]) leave with the
    # round's tokens, in the fetch the round makes anyway; so do a model
    # with sparse layers' [positions attended, kernels scored], summed
    # over its layers and the slots
    counted = (jnp.concatenate(routed) if routed else None,
               sum(selected) if selected else None)
    return nxt, cache, rng, counted


def _multi_step_runner(model: Transformer, slots: int, top_k: int,
                       top_p: float, cache_dtype: str, n_rounds: int):
    """Jitted per (model, B, truncation, N): N plain decode rounds as ONE
    compiled lax.scan — rng split and per-round math identical to N
    calls of the single-step program, so outputs are token-exact vs a
    step() loop (tested).  The host lever for dispatch-bound serving:
    each step() round-trip costs a full host<->device dispatch (not
    measured on the chip), and between admissions those rounds need no
    host decisions.  Where the model's rounds take a mask
    (:func:`_mask_layers`) a lane whose token is ``_IDLE`` holds no request,
    in every one of the N rounds."""
    key = (_model_key(model), "serve_multistep", slots, top_k, top_p,
           cache_dtype, n_rounds)

    def build():
        masked = bool(_mask_layers(model))

        @partial(jax.jit, donate_argnums=(2,))
        def run(params, tokens, cache, lengths, temps, rng):
            counts = None
            if masked:
                counts = (tokens != _IDLE).astype(jnp.int32)
                tokens = jnp.maximum(tokens, 0)

            def body(carry, _):
                tokens, cache, lengths, rng = carry
                # (the fused rounds keep nothing counted)
                nxt, cache, rng, _ = _decode_round(
                    model, top_k, top_p, params, tokens, cache, lengths,
                    temps, rng, counts)
                return (nxt, cache, lengths + 1, rng), nxt

            (tokens, cache, lengths, rng), outs = jax.lax.scan(
                body, (tokens, cache, lengths, rng), None,
                length=n_rounds)
            return outs, tokens, cache, rng     # outs: [N, B]

        return run

    return _cached_runner(key, build)


class DecodeServer:
    """Slot-based continuous-batching decoder.

    >>> srv = DecodeServer(model, params, slots=8, max_len=2048)
    >>> rid = srv.submit([1, 2, 3], max_new_tokens=64)
    >>> while not srv.idle:
    ...     for request_id, token in srv.step():
    ...         ...                      # stream tokens as they decode
    >>> srv.result(rid)                  # full generation for a request

    Host-side state is per-slot bookkeeping only; all model math runs in
    three compiled programs (prefill-per-bucket, splice, step).  ``eos_id``
    frees a slot early; a freed slot is reused by the next ``submit``.
    """

    def __init__(self, model: Transformer, params: Mapping[str, Any],
                 slots: int = 8, max_len: int = 2048, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, eos_id: int | None = None,
                 cache_dtype: str = "native", seed: int = 0,
                 mesh=None, param_rule=None,
                 draft: Transformer | None = None, draft_params=None,
                 draft_len: int = 4, adaptive_draft: bool = True,
                 draft_cost_ratio: float = 0.5, prompt_cache: int = 0,
                 prefix_cache_bytes: int | None = None):
        """``mesh`` turns on multi-chip serving: params are placed under
        ``param_rule`` (default: models.transformer.transformer_rule —
        Megatron TP columns/rows + fsdp) and the slot cache is sharded
        batch-over-``data`` / kv-heads-over-``tensor`` where divisible;
        GSPMD then partitions the same three compiled programs, inserting
        the attention/MLP collectives.  Token-exact vs the single-device
        server for every weight/cache dtype combination (tested on the
        virtual mesh; int8 QTensor weights place their per-channel scale
        alongside the matrix's output sharding).

        ``draft`` turns on SPECULATIVE continuous batching: every step()
        runs one draft-propose/verify round over all slots, so each
        request advances 1..k+1 tokens per target forward at its own
        acceptance rate.  Greedy (default) stays token-exact vs the
        plain greedy server whatever the draft (tested);
        ``temperature>0`` applies the Leviathan/Chen rejection rule,
        preserving the target's sampling distribution (tested
        empirically); top_k/top_p do not combine.  The draft shares the
        cache dtype and mesh.

        ``adaptive_draft`` (default on) treats ``draft_len`` as the CAP
        and re-picks the per-round depth k every few rounds via
        generation.optimal_draft_depth: the EMA accept fraction inverts
        to per-proposal agreement p, and k* maximizes expected tokens
        per round cost (1 target forward + k drafts at
        ``draft_cost_ratio`` target-units each) — the controller that
        avoids the over-speculation regime of a fixed deep k (the gain
        of either is not measured on the chip).  Each k's round program is
        compiled once and cached; token-exactness is unaffected
        (speculative commits are exact at ANY depth).
        ``adaptive_draft=False`` pins k = draft_len.

        ``prompt_cache`` > 0 turns on the radix-tree prefix cache
        (models/prefix_tree.py): admitted prompts' prefill results
        (final-position logits + the prompt's K/V row, and the draft's
        row in speculative mode) are indexed token-by-token, so an
        identical resubmission skips the prefill entirely and only
        splices, while a prompt sharing ANY cached prefix — including
        the interior of a longer cached prompt — forwards only its
        suffix (vLLM-style prefix reuse, _extend_runner).  Token-exact:
        a cached row is exactly what the prefill would recompute
        (causal attention — see prefix_tree.py on handle sharing), and
        the first token is re-sampled per request, so per-request
        temperature still applies.  Cached rows pin device memory,
        bounded by byte-accounted LRU over tree nodes:
        ``prefix_cache_bytes`` (default env ``PSDT_PREFIX_CACHE_BYTES``,
        256 MiB) — a hit touches the whole ancestor path, so a hot
        shared prefix outlives its descendants' churn."""
        if prompt_cache < 0:
            raise ValueError(f"prompt_cache must be >= 0, "
                             f"got {prompt_cache}")
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache_dtype = cache_dtype
        self.mesh = mesh
        from .transformer import transformer_rule
        self._param_rule = (param_rule or transformer_rule(mesh)
                            if mesh is not None else None)
        if mesh is not None:
            params = _place_params(dict(params), mesh, self._param_rule)
        self.params = params
        self._n_swaps = 0  # live weight hot-swaps (swap_params)
        config = model.config
        # the layers whose state is a snapshot (good at one depth only):
        # they decide what the prefix tree may match and what cannot be
        # rolled back
        self._state_layers = len(config.state_layers)
        # those of them that keep a matrix a head (``serve.linear.*``)
        self._linear_layers = sum(config.layer_spec(i).kind.matrix
                                  for i in config.state_layers)
        # a round tells the model which lanes hold a request where a layer
        # reads it (an experts layer, or a state layer that then leaves an
        # idle lane's states as they are: those are counted)
        self._masked = bool(_mask_layers(model))
        self._live_lane_layers = sum(
            config.layer_spec(i).moves_live_lanes
            for i in config.state_layers)
        self._sparse_layers = len(config.layers_of("sparse"))
        self._latent_layers = len(config.layers_keeping("latent"))
        if draft is not None:
            check_rolls_back(model)
            check_rolls_back(draft)
        self._cache = init_cache(model, slots, max_len, cache_dtype)
        # the full softmax layers: K/V by position, the whole context
        self._full_layers = len(self._cache.k) - len(
            getattr(self._cache, "sparse_layers", ()))
        if mesh is not None:
            self._cache = _shard_cache(self._cache, mesh)
        # the positions a block where a round's full layers run the
        # kernel of ops/pallas/full_decode.py, 0 where they read whole
        self._full_block = full_round_block(model, self._cache, slots)
        self._moe_layers = sum(config.layer_spec(i).ffn == "experts"
                               for i in range(config.n_layers))
        if draft is not None and (ring_layers_of(model, max_len)
                                  or ring_layers_of(draft, max_len)):
            raise ValueError(
                "speculative serving rolls rejected positions back, and a "
                "window layer's ring cannot be rolled back: serve a model "
                "with window layers without a draft")
        # per lane, as the host knows them: the position the next round
        # dispatched writes (a live lane's grows by one at every dispatch)
        # and the newest token FETCHED; a plain round is dispatched one
        # round ahead of the fetch (see step()), so a live lane's newest
        # token is as a rule still on the device, in ``_last``
        self._lengths = np.zeros((slots,), np.int32)
        self._tokens = np.zeros((slots,), np.int32)
        self._last = jnp.zeros((slots,), jnp.int32)
        self._flight: _Flight | None = None
        self._slot: list[_Slot | None] = [None] * slots
        self._results: dict[int, list[int]] = {}
        self._next_id = 0
        # observability counters (the stats property)
        self._n_steps = 0
        self._n_emitted = 0
        self._n_requests = 0
        self._n_retired = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._plain_rounds = 0   # non-speculative rounds since last probe
        # obs-registry mirrors: serving health in the same process-wide
        # registry the RPC layer and train loops report to (obs/stats.py)
        self._obs_round = obs_stats.histogram("serve.round_s")
        self._obs_tokens = obs_stats.counter("serve.tokens")
        # the legs of an admission and of a round (always on; each is
        # also a span while obs/trace records): all of an admitting
        # submit() and, inside it, first dispatch to first token on the
        # host; a round's dispatch-to-tokens-on-the-host and the rest of
        # the call; and the caller's time between two rounds
        self._obs_admit = obs_stats.histogram("serve.admit_s")
        self._obs_admit_device = obs_stats.histogram("serve.admit_device_s")
        # an admission by its five legs (disjoint; with the slot
        # bookkeeping they cover serve.admit_s): the key's build and the
        # tree's lookup; the host's time to put the forward on the device;
        # the tree's insert, split and eviction pass; the sampling and the
        # BLOCKED fetch of the first token (the rest of the round in
        # flight, then the forward); the splice's dispatch
        self._obs_admit_legs = {
            name: obs_stats.histogram(f"serve.admit_{name}_s")
            for name in ("lookup", "forward", "tree", "first_token",
                         "splice")}
        self._obs_round_device = obs_stats.histogram("serve.round_device_s")
        self._obs_round_host = obs_stats.histogram("serve.round_host_s")
        self._obs_between = obs_stats.histogram("serve.between_rounds_s")
        # plain rounds dispatched, and those of them dispatched on their
        # predecessor's tokens before the host had fetched them
        self._obs_rounds = obs_stats.counter("serve.rounds")
        self._obs_chained = obs_stats.counter("serve.rounds_chained")
        # what the experts layers routed, a round at a time (see
        # _count_routing), and the bytes held by kind of layer
        self._moe_assignments = 0
        self._obs_moe = {name: obs_stats.counter(f"serve.moe.{name}")
                         for name in ("assignments", "assignments_routed",
                                      "layer_rounds",
                                      "experts_touched", "expert_places",
                                      "load_max_over_mean",
                                      "admit_experts_touched",
                                      "rank_places", "tokens_routed",
                                      "round_assignments",
                                      "round_assignment_places")}
        for kind, held in self._cache_bytes_by_kind().items():
            obs_stats.gauge(f"serve.cache.{kind}_bytes").set(held)
        # what a round's sparse, linear (kda, gdn and ssm too), latent and full
        # layers read (see _count_mixers)
        self._obs_mixers = {
            name: obs_stats.counter(name) for name in (
                "serve.sparse.positions_selected",
                "serve.sparse.positions_cached",
                "serve.sparse.kernels_scored",
                "serve.linear.state_updates",
                "serve.linear.state_places",
                "serve.latent.positions_read",
                "serve.latent.positions_cached",
                "serve.full.positions_live",
                "serve.full.positions_cached",
                "serve.full.positions_read")}
        # the mark (obs/legs.py: perf_counter first) at the last round's
        # return, while a slot is active
        self._round_returned: tuple | None = None
        # a leg of this thread that takes over obs_legs.SLOW_LEG_S keeps
        # its evidence (``slow_legs``, the newest 64; the log; the flight
        # ring; five counters): the caller's time between two rounds LESS
        # the admissions inside it (``_admissions``: their marks since the
        # last round), a round's host and device legs, an admission's five
        # (through a weak reference: the watch must not keep the server,
        # and the device memory it holds, alive past its last user)
        held = weakref.WeakMethod(self._held)
        self._watch = obs_legs.SlowLegs(lambda: held()())
        self.slow_legs = self._watch.records
        self._admissions: list[tuple] = []
        self._admission: dict | None = None   # the one in hand, for _held
        # radix-tree prefix cache (ISSUE 20): token-level index over
        # cached K/V rows — exact hits replay, any shared prefix seeds
        # a suffix-only extension, byte-accounted LRU eviction.
        # prompt_cache_size > 0 stays the enable switch (the PR 14 flag
        # surface); the budget is bytes now, not entries.
        self.prompt_cache_size = prompt_cache
        budget = (int(prefix_cache_bytes) if prefix_cache_bytes is not None
                  else int(os.environ.get("PSDT_PREFIX_CACHE_BYTES",
                                          "268435456")))
        self._prefix_tree = (PrefixTree(
            budget, snapshots=bool(self._state_layers))
            if prompt_cache else None)
        self._prompt_hits = 0
        # extension programs being built ahead, by (prefix bucket, suffix
        # bucket): _build_ahead
        self._ahead: dict[tuple[int, int], threading.Thread] = {}
        # shared-PREFIX reuse: a miss whose prompt shares a cached
        # prefix forwards only the suffix (_extend_runner).  Speculative
        # mode extends the DRAFT row from the same tree node alongside
        # the target row (ISSUE 20 satellite — the PR 14 plain-mode-only
        # restriction is gone); a k==0-era ancestor without a draft row
        # falls back to a full draft prefill for the draft side only.
        self._prefix_hits = 0
        # prompt-phase accounting for the prefix cache's reuse ratio:
        # tokens actually forwarded in a prompt phase vs prompt tokens
        # admitted (exact hit: 0, extension: the suffix, miss: all)
        self._prefill_tokens = 0
        self._prompt_tokens = 0
        # params version tag (fleet/ version-skew bookkeeping): 0 = boot
        # weights; swap_params(version=...) stamps the published version
        # every subsequently decoded token is attributed to
        self.params_version = 0
        self._rng = jax.random.key(seed)
        self._step = _step_runner(model, slots, top_k, top_p, cache_dtype)
        self._temperature = temperature
        self._top_k = top_k
        self._top_p = top_p
        # per-slot sampling temperature (traced input to the step program;
        # submit(..., temperature=) overrides the server default per slot)
        self._temps = np.full((slots,), temperature, np.float32)
        # --- speculative mode state
        self.draft = draft
        self.draft_len = draft_len          # cap (verify-slack sizing)
        self.adaptive_draft = adaptive_draft
        if draft is not None:
            if top_k or top_p:
                raise ValueError("speculative serving supports greedy "
                                 "(default) or plain --temperature "
                                 "sampling; top_k/top_p must be off")
            if draft.config.vocab != model.config.vocab:
                raise ValueError(
                    f"vocab mismatch: target {model.config.vocab} vs "
                    f"draft {draft.config.vocab}")
            if draft_len < 1:
                raise ValueError("draft_len must be >= 1")
            if draft_params is None:
                raise ValueError("draft requires draft_params")
            if mesh is not None:
                draft_params = _place_params(
                    dict(draft_params), mesh,
                    param_rule or transformer_rule(mesh))
            self.draft_params = draft_params
            self._d_cache = init_cache(draft, slots, max_len, cache_dtype)
            if mesh is not None:
                self._d_cache = _shard_cache(self._d_cache, mesh)
            self._d_lengths = np.zeros((slots,), np.int32)  # pc per slot
            self._prev = np.zeros((slots,), np.int32)       # y per slot
            # current depth + adaptation state; one compiled round program
            # per depth, built lazily (cached in _cached_runner)
            self._k = min(2, draft_len) if adaptive_draft else draft_len
            self.draft_cost_ratio = draft_cost_ratio
            self._accept_ema: float | None = None
            self._rounds_since_adapt = 0
            self._ema_proposals = 0  # proposals folded into the EMA so far
        self._chunks_ahead = self._build_chunks_ahead()

    _ADAPT_EVERY = 4        # rounds between depth decisions
    _ADAPT_DECAY = 0.8      # EMA decay on the per-round accept fraction
    _MIN_DISABLE_PROPOSALS = 16  # EMA evidence required before k=0 allowed
    _REPROBE_AFTER_PLAIN = 64    # plain rounds between k=0 re-probes

    def _spec_round(self, *args):
        runner = _spec_round_runner(self.model, self.draft, self._k,
                                    self.cache_dtype,
                                    float(self._temperature))
        return runner(*args)

    def _adapt_depth(self, accepted: int, proposed: int) -> None:
        """Update the agreement estimate with this round's active-slot
        stats and re-pick k every _ADAPT_EVERY rounds via the shared
        expected-throughput controller (generation.optimal_draft_depth).
        The EMA runs in per-proposal-agreement space (each round's accept
        FRACTION is inverted at the depth it was measured at) so samples
        taken at different depths stay comparable.  Shortening when
        agreement is weak avoids over-speculation (k tokens drafted, few
        kept: wasted draft forwards AND a wider verify); deepening when
        it is strong converts cheap drafts into >1 token/verify."""
        if not self.adaptive_draft or not proposed:
            return
        from .generation import _invert_accept_fraction, optimal_draft_depth
        p_round = _invert_accept_fraction(accepted / proposed, self._k)
        self._accept_ema = (p_round if self._accept_ema is None else
                            self._ADAPT_DECAY * self._accept_ema
                            + (1.0 - self._ADAPT_DECAY) * p_round)
        self._ema_proposals += proposed
        self._rounds_since_adapt += 1
        if self._rounds_since_adapt < self._ADAPT_EVERY:
            return
        self._rounds_since_adapt = 0
        # the EMA is already p, so invert at k=1 (identity).  Disabling
        # (k=0) needs _MIN_DISABLE_PROPOSALS of evidence in the EMA: one
        # unlucky early round must not shut speculation off (k=0 used
        # to be permanent AND cheap to reach).
        self._k = optimal_draft_depth(
            self._accept_ema, 1, self.draft_len, self.draft_cost_ratio,
            allow_disable=self._ema_proposals >= self._MIN_DISABLE_PROPOSALS)
        if self._k == 0:
            self._plain_rounds = 0   # count plain rounds toward a re-probe

    def _maybe_rearm_speculation(self) -> None:
        """k=0 is not forever: after
        _REPROBE_AFTER_PLAIN plain rounds, the next IDLE admission re-arms
        speculation at a probe depth of 1 with fresh adaptation state (the
        workload may have shifted toward the draft since the disable).
        Idle matters for correctness: requests admitted while k=0 skipped
        their draft prefill, so their draft-cache rows are holes — once
        idle, every active request after the rearm is admitted with a
        draft prefill again."""
        if (self.draft is None or not self.adaptive_draft or self._k > 0
                or not self.idle
                or self._plain_rounds < self._REPROBE_AFTER_PLAIN):
            return
        self._k = 1
        self._plain_rounds = 0
        self._accept_ema = None
        self._ema_proposals = 0
        self._rounds_since_adapt = 0

    # ------------------------------------------------------------- admin
    def swap_params(self, params: Mapping[str, Any], *,
                    version: int | None = None) -> None:
        """Hot-swap the model weights (live weight publication — a
        follower tracking a training run feeds fresh versions through
        here, cli/serve_main.py ``--follow``).  Call BETWEEN step()
        calls from the serving thread: the compiled programs take the
        params as a traced input, so no retrace happens and the very
        next round DISPATCHED reads the new weights.  In-flight requests
        keep their slots, KV rows, and sampling state — their
        already-emitted tokens stand and their continuations decode under
        the new weights, which is the point of tracking a live run (token
        streams are uninterrupted, not retroactively recomputed).

        The round step() left in flight was dispatched under the weights
        that leave, and its tokens have not been handed out.  A caller
        that tells its clients which version decoded each token (a swap
        with ``version``: fleet/decode.py stamps every chunk with
        ``params_version``) calls land() first and delivers what it
        returns under the old version; a versioned swap with a round
        still in flight raises, so ``params_version`` is at all times the
        version that decoded every token step() returns.  Without
        ``version`` the round stays in flight and the next step() returns
        its tokens: the last ones of the old weights.

        The prefix cache is dropped: its prefill logits/KV rows were
        computed under the old weights, and replaying them would splice
        stale state next to fresh-weight decode steps.

        Raises on name/shape drift against the current params (an
        upstream model change mid-publication): the swap point is where
        callers catch a bad publication and keep the last-good weights
        (cli/serve_main.py maybe_swap) — without this check the mismatch
        would surface as a crash inside a later decode round."""
        current = {name: np.shape(arr)
                   for name, arr in self.params.items()}
        fresh = {name: np.shape(arr) for name, arr in params.items()}
        if current != fresh:
            drift = {name for name in (set(current) ^ set(fresh))} | {
                name for name in set(current) & set(fresh)
                if current[name] != fresh[name]}
            raise ValueError(
                f"published weights do not match the served model "
                f"(name/shape drift: {sorted(drift)[:4]}...)")
        if version is not None and self._flight is not None:
            raise RuntimeError(
                "a round is in flight under the weights that leave: land() "
                "it and deliver its tokens before a versioned swap")
        if self.mesh is not None:
            params = _place_params(dict(params), self.mesh,
                                   self._param_rule)
        self.params = params
        if self._prefix_tree is not None:
            self._prefix_tree.clear()
        self._n_swaps += 1
        if version is not None:
            self.params_version = int(version)

    @property
    def idle(self) -> bool:
        return all(s is None for s in self._slot)

    @property
    def has_free_slot(self) -> bool:
        return self._free_slot() is not None

    @property
    def active(self) -> int:
        """Number of in-flight requests."""
        return sum(s is not None for s in self._slot)

    def _free_slot(self) -> int | None:
        for i, s in enumerate(self._slot):
            if s is None:
                return i
        return None

    def prefix_fingerprint(self) -> bytes:
        """Compact prefix fingerprint of the radix cache (packed chained
        CRC32 block hashes — prefix_tree.block_hashes) for the fleet
        heartbeat.  Safe to call from the heartbeat thread: it reads one
        immutable bytes snapshot the decode thread swaps in after each
        tree mutation.  Empty when the cache is off — the router's
        overlap term degrades to zero and PR 14 scoring stands."""
        tree = self._prefix_tree
        return tree.fingerprint if tree is not None else b""

    def _radix_extend(self, prompt: np.ndarray, real_len: int,
                      node, matched: int):
        """Shared-prefix extension from the deepest cached ancestor:
        forward only the suffix past the ``matched``-token tree prefix
        against the covering node's K/V row (_extend_runner).  Returns
        (last logits, combined row, draft row | None, the experts
        layers' loads | None) or None (no
        usable prefix / combined row would not fit the slot cache —
        the caller full-prefills).  The suffix math is a ragged
        decode_block — exactly what decoding those tokens one round at
        a time would compute — so the continuation is decode-path-
        consistent by construction.  A prompt that IS a cached path
        (an interior split node with no replayable logits) caps the
        prefix at real_len - 1 and extends a single token.

        Speculative mode extends the draft row from the same node's
        draft handle; an ancestor admitted while the depth controller
        had speculation off carries no draft row, so the draft side
        (only) falls back to a full prefill — the target row still
        rides the suffix-only path."""
        plen = min(matched, real_len - 1)
        if plen <= 0 or node.handle is None:
            return None
        if self._state_layers and plen != matched:
            return None  # a snapshot is good at its own depth only
        pre_row = node.handle.row
        pbucket = int(pre_row[0].shape[1])
        slen = real_len - plen
        sbucket = _bucket(slen, _suffix_floor(self.model))
        if pbucket + sbucket > self.max_len:
            # the floor's bucket does not fit the lane beside the prefix:
            # the suffix's own (a prefill of the whole prompt costs more)
            sbucket = _bucket(slen)
        if pbucket + sbucket > self.max_len:
            return None  # combined row would overflow the slot cache
        with self._forward_leg(slen):
            padded = np.zeros((1, sbucket), np.int32)
            padded[0, :slen] = prompt[plen:]
            suffix = jnp.asarray(padded)
            plen_j = jnp.asarray(plen, jnp.int32)
            slen_j = jnp.asarray(slen, jnp.int32)
            ahead = self._ahead.get((pbucket, sbucket))
            if ahead is not None:
                ahead.join()    # (at once, but for the first few seconds)
            last, row, loads = _extend_runner(self.model, pbucket, sbucket,
                                              self.cache_dtype)(
                self.params, pre_row, suffix, plen_j, slen_j)
            d_row = None
            if self.draft is not None and self._k > 0:
                dpre = node.dhandle.row if node.dhandle is not None else None
                dbucket = int(dpre[0].shape[1]) if dpre is not None else 0
                if dpre is not None and dbucket + sbucket <= self.max_len:
                    _, d_row, _ = _extend_runner(
                        self.draft, dbucket, sbucket, self.cache_dtype)(
                        self.draft_params, dpre, suffix, plen_j, slen_j)
                else:
                    dbucket = min(_bucket(real_len), self.max_len)
                    dpadded = np.zeros((1, dbucket), np.int32)
                    dpadded[0, :real_len] = prompt
                    _, d_row, _ = _prefill_runner(self.draft, dbucket,
                                                  self.cache_dtype)(
                        self.draft_params, jnp.asarray(dpadded),
                        jnp.asarray(real_len, jnp.int32))
        self._prefix_tree.use(node)  # the whole ancestor path is hot
        self._prefill_tokens += slen
        return last, row, d_row, loads

    def _build_ahead(self, row) -> None:
        """Build, on a thread of its own, the program that will extend the
        row a PREFILL just put into the tree (a system prompt, a document:
        what others' prompts begin with), for a model that
        :func:`_builds_few` (one suffix bucket serves its every turn,
        :func:`_suffix_floor`): the compiler works on
        it while the caller prefills the next prompt, and a cold start
        with four resident contexts builds its extensions beside its
        prefills, not after them.  The program is run once on the row and a
        block of zeros, as any program's first call builds it; the
        admission that needs it first waits for the thread
        (:meth:`_radix_extend`).  A row an extension made is one
        conversation's own and starts nothing."""
        floor = _suffix_floor(self.model)
        pbucket = int(row[0].shape[1])
        if (not _builds_few(self.model) or self.draft is not None
                or (pbucket, floor) in self._ahead
                or pbucket + floor > self.max_len):
            return
        run = _extend_runner(self.model, pbucket, floor, self.cache_dtype)
        one = jnp.asarray(1, jnp.int32)
        args = (self.params, row, jnp.zeros((1, floor), jnp.int32), one, one)
        thread = threading.Thread(
            target=lambda: jax.block_until_ready(run(*args)), daemon=True,
            name=f"psdt-build-ahead-{pbucket}+{floor}")
        self._ahead[pbucket, floor] = thread
        thread.start()

    def _build_chunks_ahead(self) -> threading.Thread | None:
        """Build, on a thread of its own and with the server, the ONE
        program a model that :func:`_builds_few` prefills every prompt of a
        chunk or more through (:meth:`_prefill_in_chunks`; with it the
        empty row and the row's reading): a server whose lanes can hold
        such a prompt will be sent one, and not only in a warm-up: a
        resident context that fell out of the prefix store comes back
        through it, with the next request that carries it.  Run once on a
        chunk of zeros, as any program's first call builds it; the prefill
        that needs it first waits for the thread."""
        if (not _builds_few(self.model) or self.cache_dtype != "native"
                or self.max_len < _PREFILL_CHUNK):
            return None
        empty, row_of = _empty_row_runner(self.model, self.max_len)
        run = _chunk_runner(self.model, self.max_len)
        zero, one = jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32)
        args = (self.params, jnp.zeros((1, _PREFILL_CHUNK), jnp.int32))
        thread = threading.Thread(
            target=lambda: jax.block_until_ready(
                row_of(run(*args, empty(), zero, one)[1])),
            daemon=True, name=f"psdt-build-ahead-chunks-{self.max_len}")
        thread.start()
        return thread

    def _prefill_in_chunks(self, padded: np.ndarray, real_len: int):
        """A long prompt (``padded`` [1, bucket]) through _chunk_runner,
        ``_PREFILL_CHUNK`` tokens at a time against the row so far; returns
        (the last real position's logits, the row)."""
        bucket = padded.shape[1]
        # (one program for every length: the row is filled a lane wide and
        # its bucket's positions are kept)
        total = self.max_len if _builds_few(self.model) else bucket
        if self._chunks_ahead is not None:
            self._chunks_ahead.join()   # (at once, but for a cold start)
        empty, row_of = _empty_row_runner(self.model, total)
        run = _chunk_runner(self.model, total)
        cache = empty()
        for done in range(0, real_len, _PREFILL_CHUNK):
            tokens = np.zeros((1, _PREFILL_CHUNK), np.int32)
            chunk = padded[:, done:done + _PREFILL_CHUNK]
            tokens[:, :chunk.shape[1]] = chunk
            last, cache = run(
                self.params, jnp.asarray(tokens), cache,
                jnp.asarray(done, jnp.int32),
                jnp.asarray(min(_PREFILL_CHUNK, real_len - done), jnp.int32))
        row = row_of(cache)
        if total != bucket:
            # K, V and the latent layers' rows lie by position; a snapshot
            # has no positions
            lead = 3 if self._latent_layers else 2
            row = tuple(leaf[:, :bucket] if i < lead else leaf
                        for i, leaf in enumerate(row))
        return last, row

    def _forward_leg(self, forwarded: int):
        """The leg in which an admission's forward goes to the device:
        padding, uploads, the runner's lookup and every dispatch (the
        draft's too).  It ends when the dispatch returns; the device's
        time is the first-token leg's."""
        self._admission["forwarded_tokens"] = forwarded
        return self._watch.leg(
            "serve/admit/forward", self._obs_admit_legs["forward"],
            forwarded_tokens=forwarded,
            behind_round=self._flight is not None)

    def _held(self) -> dict:
        """What the server held when a leg came out slow (obs/legs.py asks
        once, after the leg): slots, the round in flight, the admission in
        hand and the device's memory."""
        out = {"active_slots": self.active,
               "round_in_flight": self._flight is not None,
               **(self._admission or {})}
        device = next(iter(self._last.devices()))
        stats = device.memory_stats() or {}
        for key in ("bytes_in_use", "bytes_reserved",
                    "largest_free_block_bytes"):
            if key in stats:
                out[f"device_{key}"] = stats[key]
        return out

    def _admit_to_tree(self, pkey: tuple, last, row, d_row) -> None:
        """Insert an admitted prompt's rows into the radix tree (an
        edge split shares the descendant's handles — no device copy)
        and run the byte-budget LRU eviction pass."""
        tree = self._prefix_tree
        with self._watch.leg("serve/admit/tree",
                             self._obs_admit_legs["tree"]) as leg:
            splits = tree.splits
            node = tree.insert(
                pkey, last, RowRef(row, _row_nbytes(row),
                                   state_at=len(pkey) if self._state_layers
                                   else None),
                RowRef(d_row, _row_nbytes(d_row)) if d_row is not None
                else None)
            if tree.splits != splits:
                flight.record("serve.prefix.split", a=node.depth,
                              b=tree.nodes)
            held = tree.bytes
            evicted = tree.evict_over_budget()
            if evicted:
                flight.record("serve.prefix.evict", a=evicted, b=tree.bytes)
            leg.args.update(evicted=evicted, freed_bytes=held - tree.bytes,
                            tree_bytes=tree.bytes)

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens: int = 64, *,
               temperature: float | None = None,
               stop=()) -> int:
        """Admit a request into a free slot (prefill + cache splice).
        Raises RuntimeError when every slot is busy — callers queue above
        this layer.  Returns the request id.

        ``temperature`` overrides the server default for THIS request
        (0.0 = greedy; temperatures are a traced per-slot input, so
        mixed-temperature batches share one compiled step).  Speculative
        mode bakes the temperature into the verify round's acceptance
        rule, so per-request overrides are rejected there.  ``stop`` is
        an iterable of token ids that finish this request, checked
        alongside the server ``eos_id``."""
        if temperature is not None and self.draft is not None \
                and temperature != self._temperature:
            raise ValueError(
                "per-request temperature is not supported in speculative "
                "mode (the accept rule is compiled for the server "
                "temperature); construct the server with the temperature "
                "you need")
        self._maybe_rearm_speculation()
        slot = self._free_slot()
        if slot is None:
            raise RuntimeError("no free slot; drain with step() first")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        real_len = int(prompt.shape[0])
        if real_len == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        # speculative mode: a verify round may write draft_len+1 entries
        # past the committed frontier before the host truncates
        slack = self.draft_len + 1 if self.draft is not None else 0
        if real_len + max_new_tokens + slack > self.max_len:
            raise ValueError(
                f"prompt {real_len} + max_new {max_new_tokens} (+ "
                f"speculative slack {slack}) exceeds cache max_len "
                f"{self.max_len}")
        check_position_budget(self.model, real_len,
                              max_new_tokens + slack)
        if self.draft is not None:
            check_position_budget(self.draft, real_len,
                                  max_new_tokens + slack)
        start = self._watch.enter()
        try:
            with obs_trace.timed("serve/admit", self._obs_admit,
                                 prompt_tokens=real_len,
                                 request_id=self._next_id):
                return self._admit(
                    slot, prompt, real_len, max_new_tokens,
                    self._temperature if temperature is None else temperature,
                    frozenset(stop))
        finally:
            # what the caller's leg leaves out: an admission's seconds
            # are counted under its own legs
            self._admissions.append((start, self._watch.mark()))
            self._admission = None

    def _forward_prompt(self, prompt: np.ndarray, pkey: tuple | None,
                        anc, matched: int):
        """A prompt no node replays, forwarded and put into the tree:
        (last logits, its row, the draft's row | None, the experts layers'
        loads | None).  Shared-prefix extension serves the prompt phase
        whenever the tree holds ANY prefix of this prompt — including the
        interior of a longer cached prompt (the radix point) — and in
        speculative mode the draft row extends alongside the target row
        (_radix_extend), so spec admissions no longer fall back to full
        prefill (ISSUE 20 satellite); else a prefill, whole or in chunks
        (:func:`_prefills_whole`)."""
        real_len = int(prompt.shape[0])
        bucket = min(_bucket(real_len), self.max_len)
        tree = self._prefix_tree
        extended = (self._radix_extend(prompt, real_len, anc, matched)
                    if tree is not None else None)
        if extended is not None:
            # only the suffix ran a forward; the combined row
            # splices below under its own (wider) width
            last, row, d_row, loads = extended
            self._prefix_hits += 1
            flight.record("serve.prefix.hit",
                          a=min(matched, real_len - 1),
                          b=real_len - min(matched, real_len - 1))
        else:
            loads = None
            with self._forward_leg(real_len):
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :real_len] = prompt
                if (self.cache_dtype == "native"
                        and not _prefills_whole(self.model, bucket)):
                    last, row = self._prefill_in_chunks(padded, real_len)
                else:
                    last, row, loads = _prefill_runner(
                        self.model, bucket, self.cache_dtype)(
                        self.params, jnp.asarray(padded),
                        jnp.asarray(real_len, jnp.int32))
                d_row = None
                if self.draft is not None and self._k > 0:
                    # k=0 (controller disabled speculation): the
                    # draft cache is not read while disabled, so
                    # skip its prefill + splice; a later re-probe
                    # backfills via the cache-hit repair in _admit
                    _, d_row, _ = _prefill_runner(
                        self.draft, bucket, self.cache_dtype)(
                        self.draft_params, jnp.asarray(padded),
                        jnp.asarray(real_len, jnp.int32))
            self._prefill_tokens += real_len
            if tree is not None:
                self._build_ahead(row)
        if tree is not None:
            self._admit_to_tree(pkey, last, row, d_row)
        return last, row, d_row, loads

    def _admit(self, slot: int, prompt: np.ndarray, real_len: int,
               max_new_tokens: int, req_temp: float,
               stop: frozenset) -> int:
        """The admitting part of :meth:`submit`, after its checks, as its
        five legs (``serve/admit/<leg>``, histogram ``serve.admit_<leg>_s``;
        each watched, obs/legs.py): ``lookup``, the key's build and the
        prefix lookup; ``forward``, the prefill's or the suffix extension's
        way to the device; ``tree``, the insert (:meth:`_admit_to_tree`);
        ``first_token``, the sampling and the blocked fetch; ``splice``.  A
        whole-prompt hit runs no forward and no tree leg."""
        bucket = min(_bucket(real_len), self.max_len)
        tree = self._prefix_tree
        legs, hists = self._watch.leg, self._obs_admit_legs
        self._admission = {"prompt_tokens": real_len}
        pkey = hit = None
        anc, matched = None, 0
        routed = []    # what an admission's forwards routed, if it ran any
        if tree is not None:
            with legs("serve/admit/lookup", hists["lookup"],
                      prompt_tokens=real_len) as leg:
                pkey = tuple(prompt.tolist())
                anc, matched, partial = tree.lookup(pkey)
                leg.args["matched"] = self._admission["matched"] = matched
            if (matched == real_len and not partial
                    and anc.last is not None):
                hit = anc  # whole-prompt node: replayable logits + row
        # from the first dispatch to the first token on the host
        with obs_trace.timed("serve/admit/device", self._obs_admit_device):
            if hit is not None:
                tree.use(hit)  # the whole ancestor path, not one entry
                self._prompt_hits += 1
                self._prompt_tokens += real_len
                last = hit.last
                row = hit.handle.row
                d_row = hit.dhandle.row if hit.dhandle is not None else None
                if self.draft is not None and self._k > 0 and d_row is None:
                    # node was cached while the controller had speculation
                    # off (k=0 skips the draft prefill below); replaying it
                    # as-is after a re-probe re-armed k would skip the draft
                    # splice and leave this slot's _d_lengths/_prev stale —
                    # backfill the draft half and attach it to the node
                    with self._forward_leg(real_len):
                        padded = np.zeros((1, bucket), np.int32)
                        padded[0, :real_len] = prompt
                        _, d_row, _ = _prefill_runner(self.draft, bucket,
                                                      self.cache_dtype)(
                            self.draft_params, jnp.asarray(padded),
                            jnp.asarray(real_len, jnp.int32))
                    self._admit_to_tree(pkey, last, row, d_row)
            else:
                if self._state_layers and tree is not None:
                    shared = tree.shared(pkey)
                    if matched + _suffix_floor(self.model) <= shared \
                            < real_len:
                        # another prompt begins with these tokens and no
                        # row holds the states there (a snapshot cannot be
                        # cut back as K/V can): what two prompts share
                        # gets a row of its own, and this one and every
                        # later one extend it
                        routed.append(self._forward_prompt(
                            prompt[:shared], pkey[:shared], anc, matched)[3])
                        anc, matched, _ = tree.lookup(pkey)
                last, row, d_row, loads = self._forward_prompt(
                    prompt, pkey, anc, matched)
                routed.append(loads)
                self._prompt_tokens += real_len
            with legs("serve/admit/first_token", hists["first_token"]):
                self._rng, sub = jax.random.split(self._rng)
                first = int(sample_token(last[None], sub, req_temp,
                                         self._top_k, self._top_p)[0])
        for loads in routed:
            if loads is not None:
                # already on the host's side of the fetch of the first token
                self._count_routing(np.asarray(loads), admission=True)
        # splice widths come from the rows themselves: a radix-served
        # row is prefix-bucket + suffix-bucket wide, and the target and
        # draft rows may differ (each extended from its own ancestor
        # width)
        with legs("serve/admit/splice", hists["splice"]):
            length = jnp.asarray(real_len, jnp.int32)
            self._cache = _splice_runner(self.model, int(row[0].shape[1]),
                                         self.cache_dtype)(
                self._cache, row, jnp.asarray(slot, jnp.int32), length)
            if self.draft is not None and d_row is not None:
                self._d_cache = _splice_runner(self.draft,
                                               int(d_row[0].shape[1]),
                                               self.cache_dtype)(
                    self._d_cache, d_row, jnp.asarray(slot, jnp.int32),
                    length)
                self._d_lengths[slot] = real_len
                self._prev[slot] = int(prompt[-1])
        rid = self._next_id
        self._next_id += 1
        self._n_requests += 1
        entry = _Slot(request_id=rid, tokens=[first],
                      max_new=max_new_tokens, stop=stop)
        self._slot[slot] = entry
        self._lengths[slot] = real_len
        self._tokens[slot] = first
        self._temps[slot] = req_temp
        if self._finishes(entry, first):
            self._retire(slot)
        return rid

    # -------------------------------------------------------------- step
    def step(self) -> list[tuple[int, int]]:
        """One decode round's tokens (a speculative round's when a draft is
        configured — each slot may then advance several tokens).  Returns
        [(request_id, token), ...]: the newly decoded token(s) of every
        ACTIVE slot (already appended to its result).

        A plain round runs ONE ROUND AHEAD of the host: this call first
        dispatches the round after the one it returns, on that round's
        tokens where they lie on the device, and only then fetches.  The
        fetch, the bookkeeping and the caller's loop overlap the next
        round.  What a caller may assume:

        - every call returns one token for every slot that was active
          when the call before returned; the first call after the server
          was idle dispatches two rounds and fetches the first.  A request
          admitted since the last call joins the round this call
          dispatches: its next token comes with the NEXT call (that round
          could not start before the one in flight ended anyway);
        - a slot is freed, and ``idle`` / ``has_free_slot`` / finished()
          change, when the finishing token is FETCHED.  A request that
          ends on ``eos_id`` / ``stop`` has by then decoded one token
          more, which is discarded; an end by ``max_new_tokens`` is known
          ahead, and a round no request has budget for is not dispatched;
        - land() fetches the round in flight and returns its tokens, so
          that nothing is in flight: step_many() does it first and
          returns those tokens with its own, and a caller that stamps
          tokens with ``params_version`` does it before swap_params();
        - a sampled request draws from the key of the round after the one
          in flight at its admission: reproducible for one seed and one
          sequence of calls, and not the serial order's draw."""
        if self.idle:
            return []
        with self._round() as device:
            if self.draft is not None and self._k > 0:
                # k can reach 0 when the adaptive controller concludes
                # this draft cannot pay (optimal_draft_depth
                # allow_disable) — the server then serves plain greedy
                # rounds below, which read the same _tokens/_lengths
                # state the spec rounds kept.  Disable is NOT forever:
                # submit() re-probes at the next idle admission boundary
                # (see _maybe_rearm_speculation).
                return self._spec_step(device)
            self._plain_rounds += 1
            flight = self._flight
            if flight is None or not any(
                    self._slot[i] is entry
                    for i, entry in flight.lanes.items()):
                # nothing in flight that a live request waits for (the
                # server was idle, or all were admitted since): this
                # call's round first
                flight = self._dispatch(None)
            self._flight = self._dispatch(flight)
            return self._land(flight, device)

    def _dispatch(self, after: _Flight | None) -> _Flight | None:
        """Dispatch one plain round over all slots; None where no request
        has budget left for it.  ``after`` is the round whose tokens the
        host has not fetched: a request it decoded for takes its token
        from that round's output on the device, one further into its
        budget; every other lane takes the host's (an admission's first
        token; an idle lane's stale one, at a position that stays).  Where
        the model's rounds take the mask (``_mask_layers``), every lane but
        ``lanes`` goes up as ``_IDLE``."""
        ahead = after.lanes if after is not None else {}
        fresh = (np.full_like(self._tokens, _IDLE) if self._masked
                 else self._tokens.copy())
        lanes: dict[int, _Slot] = {}
        for i, entry in enumerate(self._slot):
            if entry is None:
                continue
            chained = ahead.get(i) is entry
            if len(entry.tokens) + chained < entry.max_new:
                lanes[i] = entry
                fresh[i] = -1 if chained else self._tokens[i]
        if not lanes:
            return None
        # (copies: the mirrors change while the round is in flight)
        lengths = self._lengths.copy()
        self._last, self._cache, self._rng, counted = self._step(
            self.params, self._last, jnp.asarray(fresh), self._cache,
            jnp.asarray(lengths), jnp.asarray(self._temps.copy()),
            self._rng)
        for i in lanes:
            self._lengths[i] += 1
        self._obs_rounds.add()
        if (fresh == -1).any():
            self._obs_chained.add()
        held = np.minimum(lengths + 1, self.max_len)
        block = self._full_block
        return _Flight((self._last, counted), lanes, int(held.sum()),
                       int((-(-held // block) * block).sum()) if block
                       else self.slots * self.max_len)

    def _land(self, flight: _Flight, device) -> list[tuple[int, int]]:
        """Fetch a round's tokens (``device``: the leg in which the host
        waits for them) and hand them to its requests.  A lane whose
        request ended or was cancelled after the dispatch decoded for
        nobody: its token is dropped here, as a retired lane's is."""
        with device:
            nxt, (loads, selected) = jax.device_get(flight.out)
        if loads is not None:
            self._count_routing(loads)
        self._count_mixers(selected, flight.positions, flight.fetched,
                           len(flight.lanes))
        emitted: list[tuple[int, int]] = []
        for i, entry in flight.lanes.items():
            if self._slot[i] is not entry:
                continue
            token = int(nxt[i])
            entry.tokens.append(token)
            emitted.append((entry.request_id, token))
            self._tokens[i] = token
            if self._finishes(entry, token):
                self._retire(i)
        self._n_steps += 1
        self._n_emitted += len(emitted)
        if self.idle:
            self._flight = None     # what is in flight decodes for nobody
        return emitted

    def step_many(self, max_rounds: int = 8) -> list[tuple[int, int]]:
        """Up to ``max_rounds`` decode rounds in ONE device dispatch
        (plain mode; speculative mode falls back to per-round step()s —
        its depth controller needs host decisions between rounds).

        Trades admission latency for dispatch overhead: new submissions
        wait until the fused rounds return, so call this when the
        admission queue is empty (bench_serve does between arrivals —
        the win is the per-round host<->device round-trip, not measured
        on the chip).  The round count is clamped to the minimum
        remaining budget across active slots (then rounded down to a
        power of two — one compiled scan per size class), so no slot
        overshoots max_new; a row finishing EARLY (eos/stop) keeps decoding garbage
        into its own lane for the rest of the fused block, exactly like
        a retired lane does between rounds — host truncation discards
        those tokens and the splice on reuse resets the cache rows.
        Token-exact vs the equivalent step() loop (identical rng
        sequence and math; tested).  A round that step() left in flight
        is landed first, and its tokens lead the list."""
        if self.idle:
            return []
        if self.draft is not None and self._k > 0:
            return self.step()
        emitted = self.land()
        if self.idle:
            return emitted
        remaining = [entry.max_new - len(entry.tokens)
                     for entry in self._slot if entry is not None]
        n = max(1, min([max_rounds] + remaining))
        # round DOWN to a power of two: a mixed-budget drain would
        # otherwise compile a separate scan per distinct n (each compile
        # costs far more than the dispatches it saves); log2(max_rounds)
        # programs cover every clamp
        n = 1 << (n.bit_length() - 1)
        if n == 1:
            return emitted + self.step()
        with self._round(rounds=n) as device:
            runner = _multi_step_runner(self.model, self.slots,
                                        self._top_k, self._top_p,
                                        self.cache_dtype, n)
            tokens = self._tokens
            if self._masked:
                tokens = np.where([entry is None for entry in self._slot],
                                  _IDLE, tokens).astype(tokens.dtype)
            inputs = (jnp.asarray(tokens), self._cache,
                      jnp.asarray(self._lengths),
                      jnp.asarray(self._temps))
            with device:
                outs, last, self._cache, self._rng = runner(
                    self.params, *inputs, self._rng)
                outs = np.asarray(outs)                   # [n, B]
                last = np.asarray(last)
            landed = len(emitted)
            for r in range(n):
                for i, entry in enumerate(self._slot):
                    if entry is None:
                        continue
                    token = int(outs[r, i])
                    entry.tokens.append(token)
                    emitted.append((entry.request_id, token))
                    if self._finishes(entry, token):
                        # later fused rounds decoded garbage
                        # continuations for this lane; they are simply
                        # not appended
                        self._retire(i)
            # mirror what the device wrote: every lane (retired included)
            # advanced n positions and holds its last fused token
            self._lengths += n
            self._tokens[:] = last
            self._n_steps += n
            self._n_emitted += len(emitted) - landed
            self._plain_rounds += n
        return emitted

    def land(self) -> list[tuple[int, int]]:
        """Fetch the round step() left in flight, if any, as a round of its
        own, and return its tokens as step() would have: afterwards nothing
        is in flight, the host's mirrors are all a round needs, and every
        token the old weights decoded has been handed out (swap_params)."""
        if self._flight is None:
            return []
        with self._round() as device:
            flight, self._flight = self._flight, None
            return self._land(flight, device)

    def _spec_step(self, device) -> list[tuple[int, int]]:
        """One speculative round: commit each slot's accepted prefix plus
        the target's correction token.  Free/garbage lanes advance their
        device-side frontiers like active ones (host state must mirror
        what the device wrote; a reused slot's splice resets both).
        ``device`` is the round's dispatch-to-host leg (see step())."""
        inputs = (jnp.asarray(self._tokens), jnp.asarray(self._prev),
                  self._cache, self._d_cache,
                  jnp.asarray(self._lengths), jnp.asarray(self._d_lengths))
        with device:
            (commit, n_commit, cur_new, y_new, self._cache, self._d_cache,
             self._rng) = self._spec_round(
                self.params, self.draft_params, *inputs, self._rng)
            commit = np.asarray(commit)
            n_commit = np.asarray(n_commit)
            cur_new = np.asarray(cur_new)
            y_new = np.asarray(y_new)
        emitted: list[tuple[int, int]] = []
        round_proposed = round_accepted = 0
        for i, entry in enumerate(self._slot):
            n = int(n_commit[i])
            if entry is not None:
                # active-slot acceptance stats: n-1 of this round's k
                # accepted (k is the adaptive depth, not the cap)
                round_proposed += self._k
                round_accepted += n - 1
                for t in commit[i, :n]:
                    token = int(t)
                    entry.tokens.append(token)
                    emitted.append((entry.request_id, token))
                    if self._finishes(entry, token):
                        # tokens past EOS/limit in this round's commit are
                        # discarded; the cache rows they wrote sit beyond
                        # the retired frontier and splice-reset on reuse
                        self._retire(i)
                        break
            self._lengths[i] += n
            self._d_lengths[i] += n
            self._tokens[i] = int(cur_new[i])
            self._prev[i] = int(y_new[i])
        self._spec_proposed += round_proposed
        self._spec_accepted += round_accepted
        self._adapt_depth(round_accepted, round_proposed)
        self._n_steps += 1
        self._n_emitted += len(emitted)
        return emitted

    @contextlib.contextmanager
    def _round(self, **args):
        """One decode round as its legs, into the process-wide obs registry
        (and the span buffer while obs/trace records).  Yields the leg to
        hold open while the host waits for the round's tokens
        (``serve.round_device_s``: of a plain round the REST of a round
        that has run since the call before dispatched it; of a speculative
        or fused one dispatch to tokens, as before); the rest of the block
        (a plain round's: the next round's dispatch and the bookkeeping)
        is ``serve.round_host_s``, the whole ``serve.round_s``.  What
        passed since the last round returned, while a slot was active, is
        the caller's time (its loop, its admissions): one observation of
        ``serve.between_rounds_s``; ``serve.round_s`` and it together are
        the period a user sees.  Slots in use and the draft's accept rate
        are in :attr:`stats`.  The three legs are watched (obs/legs.py):
        the caller's time LESS the admissions inside it, the device leg,
        and the block's own time, each a slow leg if it alone is over the
        limit."""
        watch = self._watch
        entered = watch.enter()
        t0 = entered[0]
        if self._round_returned is not None:
            self._obs_between.observe(t0 - self._round_returned[0])
            watch.over(obs_legs.CALLER, self._round_returned, entered,
                       less=self._admissions)
        self._admissions = []
        emitted = self._n_emitted
        with obs_trace.timed("serve/round/host", self._obs_round_host,
                             **args) as call:
            device = watch.wrap("serve/round/device", call.carve(
                "serve/round/device", self._obs_round_device))
            yield device
        returned = watch.mark()
        watch.over("serve/round/host", entered, returned, less=device.taken,
                   **args)
        self._round_returned = None if self.idle else returned
        self._obs_round.observe(returned[0] - t0)
        self._obs_tokens.add(self._n_emitted - emitted)

    def _cache_bytes_by_kind(self) -> dict[str, int]:
        """Bytes of the slot cache by kind of layer: ``full`` the layers
        stored by position, ``window`` the rings."""
        if isinstance(self._cache, KVCache):
            return self._cache.nbytes_by_kind()
        return {"full": sum(int(leaf.nbytes) for leaf in
                            jax.tree_util.tree_leaves(self._cache)),
                "window": 0, "state": 0, "latent": 0}

    def _count_mixers(self, selected: np.ndarray | None, positions: int,
                      fetched: int, live: int) -> None:
        """One decode round into the counters the sparse, linear and
        latent layers' metrics divide.  ``selected`` is the round's own
        [positions attended, kernels scored] over its sparse layers and
        every lane (idle ones too: the device computes them); beside it
        ``positions``, what those lanes held in THAT round (each lane's
        length with its new token; a sparse layer each) and the states
        the round advanced (``serve.linear.state_updates``: a lane and
        linear, kda or gdn layer each, and each of the ``live`` lanes the
        round decoded for and ssm layer, which leaves an idle lane's states
        as they are) of the places there are (``serve.linear.state_places``:
        a lane and layer each).  A latent layer needs its lanes'
        ``positions`` and reads its whole part: both are counted, a latent layer each; so are a full softmax
        layer's (``serve.full.positions_live`` of
        ``serve.full.positions_cached``), and beside them ``fetched``, the
        positions its arm fetched for the round
        (``serve.full.positions_read``): every lane's length rounded up to
        whole blocks through the kernel, the whole part through the
        einsums."""
        if selected is not None:
            self._obs_mixers["serve.sparse.positions_selected"].add(
                float(selected[0]))
            self._obs_mixers["serve.sparse.kernels_scored"].add(
                float(selected[1]))
            self._obs_mixers["serve.sparse.positions_cached"].add(
                float(self._sparse_layers * positions))
        if self._linear_layers:
            alone = self._live_lane_layers
            self._obs_mixers["serve.linear.state_updates"].add(
                live * alone + self.slots * (self._linear_layers - alone))
            self._obs_mixers["serve.linear.state_places"].add(
                self.slots * self._linear_layers)
        if self._latent_layers:
            self._obs_mixers["serve.latent.positions_read"].add(
                float(self._latent_layers * positions))
            self._obs_mixers["serve.latent.positions_cached"].add(
                float(self._latent_layers * self.slots * self.max_len))
        if self._full_layers:
            self._obs_mixers["serve.full.positions_live"].add(
                float(self._full_layers * positions))
            self._obs_mixers["serve.full.positions_cached"].add(
                float(self._full_layers * self.slots * self.max_len))
            self._obs_mixers["serve.full.positions_read"].add(
                float(self._full_layers * fetched))

    def _count_routing(self, loads: np.ndarray,
                       admission: bool = False) -> None:
        """One forward's tokens per expert ([L * E], every experts layer
        in order) into the counters a per-layer metric divides.  The
        loads are of the forward's REAL tokens: a pad position's and an
        idle lane's assignments belong to no group and are counted nowhere
        (``moe.dropless_experts``'s ``live``; the device still scores
        them).  Of every forward: assignments routed, and those of them the
        grouped matmul computed.  Of an admission's: the distinct experts
        it touched.  Of a decode round's: (layer, round) pairs seen,
        distinct experts touched over them, expert places over them, the
        largest expert's load over the mean, summed, and the round's
        assignments routed (``serve.moe.round_assignments``) of the places
        its static rows have (``serve.moe.round_assignment_places``: slots
        x ``moe_top_k`` x experts layers), whose ratio is the share of a
        round's rows that were somebody's.  Where the model holds a
        share of the experts (``moe_held``) a layer's last entry is what
        went to experts held elsewhere: it counts as routed, and every
        other count is over the HELD experts and the rows computed.  Where
        its selection is under a group limit as well (``moe_groups``), the
        rank places follow (``moe.dropless_experts``): they and the tokens
        routed (a layer's assignments over ``moe_top_k``) go to
        ``serve.moe.rank_places`` and ``serve.moe.tokens_routed``."""
        config = self.model.config
        loads = loads.reshape(self._moe_layers, -1)
        if config.moe_held and config.moe_groups > 1:
            self._obs_moe["rank_places"].add(int(loads[:, -1].sum()))
            loads = loads[:, :-1]
            self._obs_moe["tokens_routed"].add(
                int(loads.sum()) // config.moe_top_k)
        routed = int(loads.sum())
        self._obs_moe["assignments_routed"].add(routed)
        if not admission:
            self._obs_moe["round_assignments"].add(routed)
            self._obs_moe["round_assignment_places"].add(
                self.slots * config.moe_top_k * self._moe_layers)
        if config.moe_held:
            loads = loads[:, :-1]
        computed, touched = int(loads.sum()), int((loads > 0).sum())
        self._moe_assignments += computed
        self._obs_moe["assignments"].add(computed)
        if admission:
            self._obs_moe["admit_experts_touched"].add(touched)
            return
        self._obs_moe["layer_rounds"].add(loads.shape[0])
        self._obs_moe["experts_touched"].add(touched)
        self._obs_moe["expert_places"].add(loads.size)
        # (a share of the experts may see no token in a round)
        mean = loads.mean(axis=1)
        self._obs_moe["load_max_over_mean"].add(float(
            (loads.max(axis=1) / np.where(mean > 0, mean, 1.0)).sum()))

    def _finishes(self, entry: _Slot, token: int) -> bool:
        return (len(entry.tokens) >= entry.max_new
                or (self.eos_id is not None and token == self.eos_id)
                or token in entry.stop)

    def cancel(self, request_id: int) -> bool:
        """Free an in-flight request's slot WITHOUT recording a result —
        the abandoned-stream reap (fleet/decode.py: the client is gone,
        so decoding its remaining budget would burn a slot into a queue
        nobody reads).  The lane decodes garbage until reused, exactly
        like a retired lane; its token of a round in flight is dropped
        when the round lands.  False when the id is not in flight."""
        for i, entry in enumerate(self._slot):
            if entry is not None and entry.request_id == request_id:
                self._slot[i] = None
                return True
        return False

    def _retire(self, slot: int) -> None:
        entry = self._slot[slot]
        entry.done = True
        self._results[entry.request_id] = entry.tokens
        self._slot[slot] = None
        self._n_retired += 1
        # lengths/tokens stay — the lane decodes garbage until reused
        # (first in the round already in flight, from its real token);
        # the splice on reuse rewrites the cache rows that matter

    @property
    def stats(self) -> dict:
        """Serving counters since construction: device steps/rounds run,
        tokens emitted to active requests, requests admitted/completed,
        and (speculative mode) the measured draft acceptance rate."""
        out = {
            "steps": self._n_steps,
            "tokens_emitted": self._n_emitted,
            "requests_admitted": self._n_requests,
            "requests_completed": self._n_retired,
        }
        if self._n_swaps:
            out["weight_swaps"] = self._n_swaps
        if self.prompt_cache_size:
            out["prompt_cache_hits"] = self._prompt_hits
            out["prefix_hits"] = self._prefix_hits
            out["prefix_cache_nodes"] = self._prefix_tree.nodes
            out["prefix_cache_bytes"] = self._prefix_tree.bytes
            out["prefix_evictions"] = self._prefix_tree.evictions
        # prompt-phase reuse ratio inputs (serve.prefix_hit_pct): tokens the
        # prompt phase actually forwarded vs prompt tokens admitted
        out["prefill_tokens"] = self._prefill_tokens
        out["prompt_tokens"] = self._prompt_tokens
        kinds = self._cache_bytes_by_kind()
        out["cache_full_bytes"] = kinds["full"]
        out["cache_window_bytes"] = kinds["window"]
        if kinds["state"]:
            out["cache_state_bytes"] = kinds["state"]
        if kinds["latent"]:
            out["cache_latent_bytes"] = kinds["latent"]
        if self._moe_layers:
            out["moe_assignments"] = self._moe_assignments
        if self.draft is not None:
            out["draft_accept_rate"] = (
                self._spec_accepted / self._spec_proposed
                if self._spec_proposed else 0.0)
            out["tokens_per_round"] = (
                self._n_emitted / self._n_steps if self._n_steps else 0.0)
            out["draft_depth"] = self._k   # current adaptive depth
        return out

    # ------------------------------------------------------------ result
    def peek(self, request_id: int) -> list[int]:
        """Tokens generated so far for an IN-FLIGHT request (the prefill
        token appears here immediately after submit; finished requests
        live in result())."""
        for entry in self._slot:
            if entry is not None and entry.request_id == request_id:
                return list(entry.tokens)
        raise KeyError(f"request {request_id} is not in flight")

    def finished(self) -> list[int]:
        """Request ids whose results are ready to collect."""
        return list(self._results)

    def result(self, request_id: int) -> list[int]:
        """Generated tokens for a finished request (pops it)."""
        return self._results.pop(request_id)

    def run_to_completion(self) -> dict[int, list[int]]:
        """Drain all in-flight requests; returns {request_id: tokens}."""
        while not self.idle:
            self.step()
        out, self._results = self._results, {}
        return out
