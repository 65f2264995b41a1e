"""Autoregressive generation for the decoder Transformer, KV-cached.

The reference has no inference path at all (it has no model — SURVEY.md §1:
gradient computation is a 0.01-constant stub, reference src/worker.cpp:316-329);
a complete training framework with an LM flagship needs one.  TPU-first
design:

- one jitted **prefill** over the whole prompt (full-sequence forward via
  ``Transformer.apply_collect_kv``, MXU-shaped) that seeds the cache;
- one jitted **decode loop** (`lax.scan` over steps) where each step runs a
  single-token forward against the cache — static shapes throughout: the
  cache is pre-allocated at prompt_len + max_new_tokens and masked by
  position, so nothing retraces as generation proceeds;
- greedy or temperature/top-k sampling via `jax.random.categorical`.

The decode step calls the same layer helpers as the training forward
(``Transformer.qkv`` / ``attn_residual`` / ``ffn_residual`` /
``final_logits`` — the layer math exists exactly once); only the attention
itself differs: a dense dot against the cache, masked to positions <=
current — the cache analogue of models/transformer.py ``causal_attention``.
MoE layers decode drop-free (see ``Transformer.ffn_residual``): training's
capacity dropping is batch-global, so for tokens the training forward
dropped, cached decode legitimately differs; for all kept tokens the paths
are token-exact.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from functools import partial
from typing import Any, ClassVar, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import stats as obs_stats
from . import transformer as _transformer
from .transformer import STATE_MIXERS, Transformer

Array = jax.Array


# the minor dimension of a TPU vector register: a row of a cache part is
# laid along it
_LANES = 128


def heads_per_row(kv_heads: int, head_dim: int) -> int:
    """The layout rule of the cache, from shapes alone: how many whole K/V
    heads share one row of a part.  As many as fit the 128 lanes (two
    heads of 64, one of 128), and a divisor of the head count.  A head
    narrower than the lanes left alone is padded to them on the device, or
    the compiler turns the part around (positions onto the lanes) for its
    products and back for the write: either way a decode round copies the
    whole cache in and out (PERF.md, PR 28).

    A LATENT layer's row has no heads to pack: the normed latent and the
    shared key part lie side by side in ONE row, padded with zeros to whole
    registers (``TransformerConfig.latent_row``: 512 + 64 -> 640 lanes, 4.5
    registers' worth in 5).  Decided by what the compiler does: a part
    [slots, max_len, 576] the device lays with POSITIONS along the lanes
    (no padding that way), and the round copied all of it into rows for
    its scatter and its products and back, 2 x 1.2 GB a layer; 512 and 64
    as two arrays pad the 64 to 128 and come to the same 640 with one part
    more.  At 640 a round's scores read the part once for every head and
    its weighted sum once more, whole rows both
    (tests/test_chip_compile.py holds that neither copies the part)."""
    pack = max(1, _LANES // head_dim)
    while kv_heads % pack:
        pack -= 1
    return pack


def _lies_by_head(rows: int) -> bool:
    """Whether the device lays a part [B, M, rows, D'] BY HEAD, positions
    under each row of heads ([B, rows, M, D'] in memory): where the rows of
    heads a position has neither divide a register's eight sublanes nor
    fill whole registers (3, 5, 6, 7, 9, 10, ... 30: by head; 1, 2, 4, 8,
    16, 24, 32, 40: by position), bfloat16 and int8 parts alike: read from
    the round compiled for a v5e at every such count (PERF.md section 6, PR
    50; tests/test_chip_compile.py holds 4, 6, 10, 16 and 30).  The products
    take that layout as it is; a write of [rows, D'] windows does not
    (:func:`decode_block`'s ``written``), and copies the part there and
    back, K and V, every layer, every round."""
    return rows % 8 != 0 and 8 % rows != 0


def pack_heads(x: Array, pack: int) -> Array:
    """K or V by head [..., KV, D] as the cache stores it: ``pack`` heads
    side by side in a row, [..., KV / pack, pack * D]."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // pack, pack * x.shape[-1]))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Key/value cache: a part per LAYER, each an array of its own, so
    that a round reads and writes a layer where it lies (an index into a
    stacked array is an operation, and a copy of the layer).

    Four kinds of part.  ``k``/``v`` hold the layers stored BY POSITION
    (index j is position j), in layer order: every full-attention and
    every sparse layer.  A full layer's part is [B, max_len, KV / pack,
    pack * D] with ``pack`` = :func:`heads_per_row` heads side by side in
    a row; a SPARSE layer's lies BY HEAD, [B, KV, max_len, D]: its round
    gathers whole blocks of one head (``by_head`` says which parts).
    ``wk``/``wv`` hold the window layers as RINGS of W positions (position
    p lives at index p % W) however long the context, each
    [B, W, KV / pack, pack * D]; empty where the model has no window layer
    or the window is no shorter than ``max_len``.  ``ck`` holds a sparse
    layer's COMPRESSED KEYS beside its K/V (index i the mean of positions
    stride i .. stride i + kernel - 1, written once those are complete),
    each [B, KV, max_len / stride, D].  ``state`` holds the STATE of a
    layer that keeps no K/V, a TUPLE of arrays a layer
    (:func:`state_shape`): a linear layer's decayed outer products,
    ([B, H, D, D] float32,), a conv layer's shift register of its last
    gated inputs, ([B, K - 1, d_model],), a kda, gdn or ssm layer's both
    (the register of its convolutions' inputs and the matrix); none grows
    with the context, none can be rolled back.  ``latent`` holds a latent
    layer's rows by position, [B, max_len, latent_row]: the normed latent
    every head's K and V are expanded from and the key part they share
    (then zeros to whole registers).  ``ring_layers``, ``sparse_layers``,
    ``state_layers`` and ``latent_layers`` name the layers of each kind
    (static).  ``length`` is the number of valid positions (a traced
    scalar so decode never retraces).  ``devices`` says over how many
    devices the parts are spread (``serving._shard_cache`` sets it):
    GSPMD partitions a round over them, and it cannot cut a kernel."""
    k: tuple
    v: tuple
    length: Array
    wk: tuple = ()
    wv: tuple = ()
    ck: tuple = ()
    state: tuple = ()
    latent: tuple = ()
    ring_layers: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
    sparse_layers: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
    state_layers: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
    latent_layers: tuple = dataclasses.field(
        default=(), metadata=dict(static=True))
    max_len: int = dataclasses.field(default=0, metadata=dict(static=True))
    devices: int = dataclasses.field(default=1, metadata=dict(static=True))
    # the fields that hold a part per layer
    PARTS: ClassVar[tuple] = ("k", "v", "wk", "wv", "ck", "state", "latent")

    def place(self, layer: int) -> tuple[bool, int]:
        """(kept as a ring?, index within its part) of a layer that keeps
        K/V (a state layer keeps none and a latent layer its rows:
        ``state_layers`` and ``latent_layers`` place them)."""
        if layer in self.ring_layers:
            return True, self.ring_layers.index(layer)
        return False, layer - sum(
            1 for r in self.ring_layers + self.state_layers
            + self.latent_layers if r < layer)

    def by_head(self, index: int) -> bool:
        """Whether ``k[index]`` / ``v[index]`` is a sparse layer's."""
        return index in {self.place(layer)[1] for layer in self.sparse_layers}

    def nbytes_by_kind(self) -> dict[str, int]:
        """Bytes by kind of part; compressed keys count under ``full``
        (they lie by position beside the K/V they summarise)."""
        return {"full": sum(int(x.nbytes) for x in self.k + self.v + self.ck),
                "window": sum(int(x.nbytes) for x in self.wk + self.wv),
                "state": sum(int(x.nbytes)
                             for layer in self.state for x in layer),
                "latent": sum(int(x.nbytes) for x in self.latent)}


def heads_major(x: Array, kv_heads: int) -> Array:
    """K or V with positions then (packed) heads, [..., S, KV / pack,
    pack * D], as a sparse layer's part holds it: [..., KV, S, D]."""
    by_head = x.reshape(x.shape[:-2] + (kv_heads, -1))
    return jnp.swapaxes(by_head, -3, -2)


def positions_major(x: Array, pack: int) -> Array:
    """:func:`heads_major` undone: [..., KV, S, D] as a row holds it."""
    return pack_heads(jnp.swapaxes(x, -3, -2), pack)


def ring_layers_of(model: Transformer, max_len: int) -> tuple[int, ...]:
    """The layers a cache of ``max_len`` positions keeps as rings: those
    whose window is shorter than that.  One ring size per model."""
    c = model.config
    rings = tuple(i for i in range(c.n_layers)
                  if 0 < c.layer_spec(i).window < max_len)
    sizes = {c.layer_spec(i).window for i in rings}
    if len(sizes) > 1:
        raise ValueError(f"window layers of more than one size {sizes}: "
                         "the cache keeps one ring size")
    return rings


def state_shape(model: Transformer) -> tuple[tuple, ...]:
    """What ONE slot keeps of each of the model's state layers, in layer
    order: a tuple of (shape, dtype) a layer, as the layer's kind says
    (``Mixer.state``, models/mixers.py: a matrix a head in float32, the
    register of a short convolution's last ``conv_kernel - 1`` inputs in
    the model's dtype, or both)."""
    c = model.config
    return tuple(c.layer_spec(i).kind.state(c) for i in c.state_layers)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKVCache:
    """int8 KV cache, a part per layer like :class:`KVCache` (every layer
    by position): k/v int8 [B, max_len, KV / pack, pack * D] with a
    per-(position, head) f32 absmax scale [B, max_len, KV].  Long-context
    decode is cache-bandwidth-bound (the cache bytes streamed per token
    dwarf the weights once B*S is large), so int8 storage nearly halves
    the HBM traffic of every decode step; the int8->compute-dtype convert
    fuses into the attention einsums.  Scale overhead is 4/D bytes/elem
    (~6% at D=64).  Companion to the weight-only path in models/quant.py."""
    k: tuple
    v: tuple
    k_scale: tuple
    v_scale: tuple
    length: Array
    max_len: int = dataclasses.field(default=0, metadata=dict(static=True))
    PARTS: ClassVar[tuple] = ("k", "v", "k_scale", "v_scale")

    def place(self, layer: int) -> tuple[bool, int]:
        return False, layer


def _kv_quantize(x: Array) -> tuple[Array, Array]:
    """Symmetric int8 over the head_dim (last) axis: x [..., D] ->
    (int8 [..., D], f32 scale [...])."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def init_cache(model: Transformer, batch: int, max_len: int,
               cache_dtype: str = "native") -> KVCache | QuantKVCache:
    c = model.config
    if cache_dtype not in ("native", "int8"):
        raise ValueError(
            f"cache_dtype must be 'native' or 'int8', got {cache_dtype!r}")
    rings = ring_layers_of(model, max_len)
    pack = heads_per_row(c.kv_heads, c.head_dim)
    sparse, states = c.layers_of("sparse"), c.state_layers
    latents = c.layers_keeping("latent")

    def parts(count: int, positions: int, dtype) -> tuple:
        # GQA: the cache stores kv_heads (< n_heads) — n_heads/kv_heads x
        # less cache HBM; heads expand to the query count at attention time
        return tuple(jnp.zeros((batch, positions, c.kv_heads // pack,
                                pack * c.head_dim), dtype)
                     for _ in range(count))

    length = jnp.zeros((), jnp.int32)
    if cache_dtype == "int8":
        if rings or sparse or states or latents:
            raise ValueError("the int8 cache stores every layer's K/V by "
                             "position; a model with window, sparse, state "
                             "or latent layers takes the native cache")

        def scales() -> tuple:
            return tuple(jnp.ones((batch, max_len, c.kv_heads), jnp.float32)
                         for _ in range(c.n_layers))

        return QuantKVCache(
            k=parts(c.n_layers, max_len, jnp.int8),
            v=parts(c.n_layers, max_len, jnp.int8),
            k_scale=scales(), v_scale=scales(), length=length,
            max_len=max_len)
    window = c.layer_spec(rings[0]).window if rings else 0
    if rings and (sparse or states or latents):
        raise ValueError("rings beside sparse, state or latent layers: a "
                         "row holds every layer that keeps K/V, in layer "
                         "order, and what the other kinds keep beside them")
    if sparse and max_len % c.sparse.block:
        raise ValueError(f"a cache of {max_len} positions does not divide "
                         f"into a sparse layer's blocks of {c.sparse.block}")

    def stored() -> tuple:
        """k or v: a sparse layer's part by head, every other as packed"""
        return tuple(
            jnp.zeros((batch, c.kv_heads, max_len, c.head_dim), c.dtype)
            if i in sparse else parts(1, max_len, c.dtype)[0]
            for i in range(c.n_layers) if i not in rings + states + latents)

    return KVCache(
        k=stored(), v=stored(), length=length,
        wk=parts(len(rings), window, c.dtype),
        wv=parts(len(rings), window, c.dtype),
        ck=tuple(jnp.zeros((batch, c.kv_heads, max_len // c.sparse.stride,
                            c.head_dim), c.dtype) for _ in sparse),
        state=tuple(tuple(jnp.zeros((batch, *shape), dtype)
                          for shape, dtype in layer)
                    for layer in state_shape(model)),
        latent=tuple(jnp.zeros((batch, max_len, c.latent_row), c.dtype)
                     for _ in latents),
        ring_layers=rings, sparse_layers=sparse, state_layers=states,
        latent_layers=latents, max_len=max_len)


def ring_of_row(row: Array, length: Array, window: int) -> Array:
    """A ring of ``window`` positions from K or V stored by position:
    row [..., S, H, D] (positions on axis -3) -> [..., window, H, D] where
    index s holds the latest position p < length with p % window == s
    (any position where there is none yet: the ring's readers hide it)."""
    held = length - 1 - (length - 1 - jnp.arange(window)) % window
    return jnp.take(row, jnp.clip(held, 0, row.shape[-3] - 1), axis=-3)


def split_row(cache: KVCache, k: Array, v: Array, length: Array
              ) -> tuple[tuple, tuple, tuple, tuple]:
    """Every layer's K and V by position (k/v [L, ..., S, H, D], or a
    sequence of L such layers) as the parts of ``cache``, a layer each:
    (k, v) of the layers it stores by position and (wk, wv) of its rings,
    the last ring's worth of positions before ``length``."""
    window = cache.wk[0].shape[1] if cache.ring_layers else 0

    def split(row) -> tuple[tuple, tuple]:
        rings = range(len(row))
        return (tuple(row[i] for i in rings if i not in cache.ring_layers),
                tuple(ring_of_row(row[i], length, window)
                      for i in rings if i in cache.ring_layers))

    (k, wk), (v, wv) = split(k), split(v)
    return k, v, wk, wv


def _seeded(part: Array, block: Array) -> Array:
    """``part`` with ``block`` written from its origin."""
    return jax.lax.dynamic_update_slice(part, block.astype(part.dtype),
                                        (0,) * part.ndim)


def check_rolls_back(model: Transformer) -> None:
    """Speculative decoding rolls rejected positions back by moving the
    cache's length; a state layer's states (``STATE_MIXERS``) have no
    length to move."""
    if model.config.state_layers:
        kinds = ", ".join(STATE_MIXERS[:-1]) + " or " + STATE_MIXERS[-1]
        raise ValueError(
            f"speculative decoding rolls rejected positions back, and a "
            f"{kinds} layer's state cannot be rolled back: "
            f"decode a model with such layers without a draft")


def check_position_budget(model: Transformer, prompt_len: int,
                          max_new_tokens: int) -> None:
    """Learned-position models have a hard position ceiling (the embed/pos
    table); reject generations that would run past it instead of silently
    reusing the last row's embedding (Transformer.embed clips only for
    speculative slack lanes whose output is discarded)."""
    c = model.config
    if c.pos_emb == "learned" and prompt_len + max_new_tokens > c.max_seq:
        raise ValueError(
            f"prompt {prompt_len} + max_new {max_new_tokens} exceeds the "
            f"learned-position table max_seq={c.max_seq}")


def prefill(model: Transformer, params: Mapping[str, Array], tokens: Array,
            max_len: int, cache_dtype: str = "native",
            ) -> tuple[Array, KVCache | QuantKVCache]:
    """Run the prompt through the full-sequence forward; returns the last
    position's logits [B, vocab] and a cache holding the prompt's K/V
    (int8-quantized on write when ``cache_dtype="int8"``)."""
    batch, prompt_len = tokens.shape
    if prompt_len > max_len:
        raise ValueError(f"prompt {prompt_len} exceeds cache {max_len}")
    logits, kept = model.apply_collect_kv(params, tokens)
    cache = init_cache(model, batch, max_len, cache_dtype)
    c = model.config
    pack = heads_per_row(c.kv_heads, c.head_dim)
    length = jnp.asarray(prompt_len, jnp.int32)
    states = getattr(cache, "state_layers", ())
    latents = getattr(cache, "latent_layers", ())
    kvs = [kv for i, kv in enumerate(kept) if i not in states + latents]
    if states or latents or getattr(cache, "sparse_layers", ()):
        from ..ops.sparse_attention import compress_keys

        def stored(x, i):
            return (x.transpose(0, 2, 1, 3) if cache.by_head(i)
                    else pack_heads(x, pack))

        # (k, v, no rings, the sparse layers' compressed keys, the states,
        # the latent layers' rows)
        fresh = ([stored(k, i) for i, (k, _) in enumerate(kvs)],
                 [stored(v, i) for i, (_, v) in enumerate(kvs)], (), (),
                 [compress_keys(kvs[cache.place(i)[1]][0].transpose(
                     0, 2, 1, 3), c.sparse) for i in cache.sparse_layers],
                 [kept[i] for i in states], [kept[i] for i in latents])
    elif isinstance(cache, QuantKVCache):
        k, ks = zip(*(_kv_quantize(k) for k, _ in kvs))
        v, vs = zip(*(_kv_quantize(v) for _, v in kvs))
        fresh = ([pack_heads(x, pack) for x in k],
                 [pack_heads(x, pack) for x in v], ks, vs)
    else:
        fresh = split_row(
            cache, [pack_heads(k, pack) for k, _ in kvs],   # [B, S, KV', D']
            [pack_heads(v, pack) for _, v in kvs], length)
    return logits[:, -1], dataclasses.replace(cache, length=length, **{
        name: jax.tree.map(_seeded, getattr(cache, name), tuple(layers))
        for name, layers in zip(cache.PARTS, fresh)})


# a block of this many queries or more against this many positions or
# more runs blockwise attention (an extension of a long cached prefix);
# anything smaller the dense product a decode round has always run
_BLOCKWISE_QUERIES = 128


def decode_block(model: Transformer, params: Mapping[str, Array],
                 tokens: Array, cache: KVCache | QuantKVCache,
                 lengths: Array | None = None,
                 counts: Array | None = None,
                 route_stats: list | None = None,
                 sparse_stats: list | None = None,
                 only: Array | None = None,
                 ) -> tuple[Array, KVCache | QuantKVCache]:
    """Forward a block of ``tokens`` [B, T] against the cache at positions
    length..length+T-1, causally masked within the block — the verify
    step of speculative decoding (T=1 is ordinary single-token decode).
    Returns (logits [B, T, vocab] f32, cache with length advanced by T;
    rolling ``length`` back later simply re-exposes old positions — stale
    K/V beyond length are masked out and overwritten on the next write).

    ``lengths`` [B] switches to RAGGED mode: row b's block writes at its
    own positions lengths[b]..lengths[b]+T-1 (per-row scatter instead of
    one dynamic_update_slice) and attends within its own valid prefix.
    cache.length is then ignored and returned unchanged — callers track
    the per-row lengths.  This is what batched speculative decoding needs:
    rows accept different numbers of draft tokens, so their caches advance
    at different rates (models/generation.speculative_generate_batched).

    A layer's kind (``config.layer_spec``) decides its mask and where its
    K/V go.  A window layer the cache keeps as a RING attends the ring
    and then the block itself, and writes the block over the ring's
    oldest positions afterwards; a ring cannot be rolled back, and pad
    positions would overwrite live ones, so ``counts`` [B] says how many
    of a row's T tokens are real (default: all) and only those are
    written.  A window layer stored by position (an extension against a
    cached row) takes the window as a mask.  ``route_stats``, where
    given, gains each ``experts`` layer's tokens per expert; under
    ``counts`` such a layer routes the real tokens alone (a pad position's
    or an idle lane's assignments belong to no group, an expert only they
    chose is not read, and they are counted nowhere:
    ``moe.dropless_experts``'s ``live``).

    A LINEAR, CONV, KDA, GDN or SSM layer reads and advances its states
    (``counts`` keeps pads out of them, and like a ring they cannot be
    rolled back; a serving round hands an ssm model 0 for a lane that holds
    no request, whose states then stay as they are, and on a TPU an ssm
    layer's single token moves the matrices of the other lanes alone:
    ops/pallas/ssd_decode.py, ``transformer.round_arm`` says when).  A
    LATENT layer writes its rows by position and attends them: a block of
    ``_BLOCKWISE_QUERIES`` tokens or more against a long cache expands K
    and V from the rows and runs blockwise; anything shorter, a round's
    single token first of all, ABSORBS the expansion into the query and the
    output, so the rows are read as they lie, once for every head
    (:func:`_latent_cache_attention`).  A SPARSE layer
    writes its K/V by position like a full one and, in a cache that
    reaches ``dense_len``, keeps its compressed keys up to date and
    attends a selection of key blocks: a single token a row gathers them
    (rows under ``dense_len`` their whole context, in the same program), a
    longer block takes the selection as a mask.  ``sparse_stats``, where
    given, gains each such single-token layer's [positions attended,
    kernels scored] over the rows.  ``only`` [B] asks for the logits of
    one token of each row's block ([B, 1, vocab]): the head over a long
    block's every token is gigabytes nobody reads.
    """
    c = model.config
    batch, t = tokens.shape
    ragged = lengths is not None
    offsets = jnp.arange(t, dtype=jnp.int32)
    if ragged:
        positions = lengths[:, None] + offsets[None, :]      # [B, T]
    else:
        pos = cache.length                                   # scalar int32
        positions = pos + offsets[None, :].repeat(batch, 0)  # [B, T]

    def position_mask(window: int) -> Array:
        """[B or 1, 1, 1, T, M]: query j of row b may attend cache
        positions 0..position, and under a window only the last W."""
        where = positions if ragged else positions[:1]
        held = jnp.arange(cache.max_len)[None, None, :]
        mask = held <= where[:, :, None]
        if 0 < window < cache.max_len:
            mask &= where[:, :, None] - held < window
        return mask[:, None, None]

    # the plain causal mask first, as ever (a model of full layers only
    # compiles to the program it always has); a window's on first use
    masks: dict[int, Array] = {0: position_mask(0)}
    if ragged:
        bidx = jnp.arange(batch, dtype=jnp.int32)[:, None]
    # shared embed: adds learned positional embeddings at the ragged
    # positions when the config uses them (positions overshooting max_seq
    # for finished speculative rows hit embed's explicit mode="clip" —
    # those lanes' outputs are discarded)
    h = model.embed(params, tokens, positions)               # [B, T, d]
    quant = isinstance(cache, QuantKVCache)
    pack = heads_per_row(c.kv_heads, c.head_dim)

    def written(part: Array, block: Array) -> Array:
        """``part`` [B, M, ...] with the block's ``block`` [B, T, ...] at
        its positions: an update of the (donated) part where it lies."""
        block = block.astype(part.dtype)
        if ragged and part.ndim == 4 and _lies_by_head(part.shape[2]):
            # a row of D' lanes an index (slot, position, row of heads):
            # windows of [KV', D'] make the compiler turn the part around
            # for the write and back (written_by_head's reason)
            return part.at[bidx[:, :, None], positions[:, :, None],
                           jnp.arange(part.shape[2])[None, None, :]].set(
                               block, mode="drop")
        if ragged:
            # mode="drop": rows that finished generating keep advancing
            # their lengths each speculative round, so their scatter
            # positions intentionally overshoot cache.max_len — those
            # writes must be dropped, not clamped onto the last slot.
            return part.at[bidx, positions].set(block, mode="drop")
        return jax.lax.dynamic_update_slice(
            part, block, (0, pos) + (0,) * (part.ndim - 2))

    def written_by_head(part: Array, block: Array) -> Array:
        """The same for a sparse layer's part [B, KV, M, D] and a block
        [B, T, KV, D]."""
        block = block.astype(part.dtype)
        if ragged:
            # a row of D lanes an index (slot, head, position): windows of
            # [KV, D] make the compiler turn the part around for the write
            return part.at[bidx[:, :, None],
                           jnp.arange(part.shape[1])[None, None, :],
                           positions[:, :, None]].set(block, mode="drop")
        return jax.lax.dynamic_update_slice(
            part, block.transpose(0, 2, 1, 3), (0, 0, pos, 0))

    # a part per layer, each replaced by its written self as its layer runs
    parts = {name: list(getattr(cache, name)) for name in cache.PARTS}

    def stored_attention(q, keys, values, spec, i) -> Array:
        """q against a layer's K/V stored by position, the block written."""
        with jax.named_scope("cache_attn"), jax.named_scope("attn"), \
                jax.named_scope("window" if spec.window else "full"):
            if (not quant and t >= _BLOCKWISE_QUERIES
                    and cache.max_len >= model.BLOCKWISE_FROM):
                from ..ops.blockwise_attention import blockwise_attention

                by_head = keys.shape[:2] + (c.kv_heads, c.head_dim)
                return blockwise_attention(
                    q, keys.reshape(by_head), values.reshape(by_head),
                    positions[:, 0], window=spec.window)
            if (not 0 < spec.window < cache.max_len
                    and _round_arm("softmax", cache, q.shape,
                                   keys) == "kernel"):
                return _kernel_cache_attention(c, q, keys, values,
                                               positions[:, 0] + 1)
            if spec.window not in masks:
                masks[spec.window] = position_mask(spec.window)
            return _dense_cache_attention(
                c, q, keys, values, masks[spec.window],
                (parts["k_scale"][i], parts["v_scale"][i])
                if quant else None)

    def ffn(layer: int, spec, h: Array, router) -> Array:
        # the FFN's weights viewed where they are used, as ever (under
        # scan_layers a view is slices, and their place in the program is
        # part of what the compiler is handed)
        lp, p = model.layer_view(params, layer)
        # experts never drop and moe decodes drop-free; aux loss unused.
        # (An experts layer routes the block's real tokens alone.)
        return model.ffn_residual(lp, p, spec, h, decode=True,
                                  router_logits=router,
                                  route_stats=route_stats, counts=counts)[0]

    for layer in range(c.n_layers):
        # layer_view resolves either param layout (unrolled layer<i>/* or
        # scan_layers' stacked blocks/*)
        lp, p = model.layer_view(params, layer)
        spec = c.layer_spec(layer)
        router = model.pre_attention_router(lp, p, spec, h)
        mixer = spec.kind
        # where the layer's part lies among those of what it keeps
        ring, i = (cache.place(layer) if mixer.keeps == "kv" else
                   (False, getattr(cache, mixer.keeps + "_layers").index(
                       layer)))
        if mixer.keeps == "state" and mixer.residual is not None:
            # the whole branch one method over the layer's states; where
            # the kind has a kernel for a round, told which form runs
            states = parts["state"][i]
            arm = {} if mixer.round_kernel is None else {"arm": _round_arm(
                # (x [B, T, H, P] against the matrix [B, H, P, N])
                spec.mixer, cache, (batch, t, *states[-1].shape[1:3]),
                states[-1])}
            with jax.named_scope("cache_attn"):
                h, parts["state"][i] = getattr(model, mixer.residual)(
                    lp, p, h, states, counts, **arm)
            h = ffn(layer, spec, h, router)
            continue
        if mixer.keeps == "latent":
            with jax.named_scope("cache_attn"), jax.named_scope("attn"), \
                    jax.named_scope("latent"):
                q, rows = model.latent_rows(lp, p, h, positions)
                with jax.named_scope("cache_update"):
                    held = parts["latent"][i] = written(
                        parts["latent"][i], rows)
                h = model.latent_out(lp, p, h, _latent_cache_attention(
                    model, lp, p, q, held, positions, masks[0], cache))
            h = ffn(layer, spec, h, router)
            continue
        q, k, v = model.qkv(lp, p, h, positions, spec)  # k/v: [B, T, KV, D]
        if spec.mixer == "linear":
            from ..ops.linear_attention import linear_attention

            with jax.named_scope("cache_attn"), jax.named_scope("attn"), \
                    jax.named_scope("linear"):
                attn, state = linear_attention(
                    q, k, v, parts["state"][i][0], counts, model.LINEAR_CHUNK)
                parts["state"][i] = (state,)
                attn = (attn * c.head_dim ** -0.5).astype(c.dtype)
        elif ring:
            attn, parts["wk"][i], parts["wv"][i] = _ring_attention(
                c, q, pack_heads(k, pack), pack_heads(v, pack),
                parts["wk"][i], parts["wv"][i], positions, counts)
        elif spec.mixer == "sparse":
            j = cache.sparse_layers.index(layer)
            with jax.named_scope("cache_update"):
                keys = parts["k"][i] = written_by_head(parts["k"][i], k)
                values = parts["v"][i] = written_by_head(parts["v"][i], v)
            attn, parts["ck"][j] = _sparse_cache_attention(
                c, q, keys, values, parts["ck"][j], positions[:, 0],
                sparse_stats)
        else:
            with jax.named_scope("cache_update"):
                if quant:
                    k, ks = _kv_quantize(k)
                    v, vs = _kv_quantize(v)
                    parts["k_scale"][i] = written(parts["k_scale"][i], ks)
                    parts["v_scale"][i] = written(parts["v_scale"][i], vs)
                keys = parts["k"][i] = written(parts["k"][i],
                                               pack_heads(k, pack))
                values = parts["v"][i] = written(parts["v"][i],
                                                 pack_heads(v, pack))
            attn = stored_attention(q, keys, values, spec, i)
        h = ffn(layer, spec, model.attn_residual(lp, p, h, attn, spec),
                router)
    if only is not None:
        h = jnp.take_along_axis(h, only[:, None, None], axis=1)
    logits = model.final_logits(params, h)
    return logits, dataclasses.replace(
        cache, length=cache.length if ragged else pos + t,
        **{name: tuple(layers) for name, layers in parts.items()})


def _latent_cache_attention(model: Transformer, params, prefix: str,
                            q: Array, rows: Array, positions: Array,
                            mask: Array, cache) -> Array:
    """A latent layer against its part of ``cache``.  q [B, T, H, head_dim
    + qk_shared] at ``positions`` [B, T]; rows [B, M, latent_row] with
    the block already written; ``mask`` the causal mask
    [B or 1, 1, 1, T, M].  Two forms of one attention.  A long block
    against a long cache runs blockwise, as a full layer's extension
    does, and EXPANDS K and V from the rows a key block at a time inside
    that loop (``expand``): the whole row expanded is [B, M, H, head_dim +
    qk_shared] twice, 0.8 GB each at 128 heads and 16,384 positions, most
    of it past the context's end, where a block is 25 MB and a block the
    mask hides is never made.
    Anything else ABSORBS the expansion: a head's own query part goes
    through its key matrix into the latent's space (``absorb``), the
    scores and the weighted sum are taken against the rows as they lie
    (``cache``), and the sum comes back through the head's value matrix.
    Which implementation takes the absorbed queries is
    ``transformer.round_arm``'s to say, over as many devices as the cache
    is spread (:func:`_round_arm`): a round's single token a
    lane on one TPU device the kernel of ops/pallas/latent_decode.py (under
    ``attn_kernel``: every LIVE position's row is read once for all heads
    and the positions past a lane's length are not read at all); else
    plain XLA, where the part is read whole, once for the scores and once
    for the weighted sum.  Returns attn [B,
    T, H, head_dim]."""
    c = model.config
    t, held = q.shape[1], rows.shape[1]
    if t >= _BLOCKWISE_QUERIES and held >= model.BLOCKWISE_FROM:
        from ..ops.blockwise_attention import blockwise_attention

        return blockwise_attention(
            q, rows, None, positions[:, 0],
            expand=lambda block: model.latent_expand(params, prefix, block,
                                                     wide_values=False))
    up_k, up_v = model.latent_up(params, prefix)
    with jax.named_scope("absorb"):
        inner = jnp.einsum("bthd,lhd->bthl", q[..., :c.head_dim], up_k,
                           preferred_element_type=jnp.float32)
        wide = jnp.concatenate(
            [inner.astype(c.dtype), q[..., c.head_dim:],
             jnp.zeros(q.shape[:3] + (rows.shape[-1] - c.kv_latent
                                      - c.qk_shared,), c.dtype)],
            axis=-1)                                       # [B, T, H, row]
    with jax.named_scope("cache"):
        if _round_arm("latent", cache, wide.shape, rows) == "kernel":
            from ..ops.pallas import latent_decode

            with jax.named_scope("attn_kernel"):
                summed = latent_decode.latent_decode_attention(
                    wide[:, 0], rows, positions[:, 0] + 1,
                    q.shape[-1] ** -0.5)[:, None]
        else:
            scores = jnp.einsum("bthc,bmc->bhtm", wide, rows,
                                preferred_element_type=jnp.float32)
            scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
            scores = jnp.where(mask[:, 0], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
            # (the whole row: a slice of the part would be a copy of it)
            summed = jnp.einsum("bhtm,bmc->bthc", probs, rows,
                                preferred_element_type=jnp.float32)
    with jax.named_scope("absorb"):
        return jnp.einsum("bthl,lhd->bthd",
                          summed[..., :c.kv_latent].astype(c.dtype), up_v,
                          preferred_element_type=jnp.float32).astype(c.dtype)


def _sparse_cache_attention(c, q: Array, keys: Array, values: Array,
                            ck: Array, first: Array,
                            sparse_stats: list | None
                            ) -> tuple[Array, Array]:
    """A sparse layer against its parts of the cache.  q [B, T, H, D] at
    positions first[b] .. first[b] + T - 1; keys/values [B, KV, M, D] with
    the block already written; ck [B, KV, M / stride, D] the compressed
    keys.  One token a row: the compressed key the token completed, if
    any, is appended and the row gathers its blocks.  A longer block:
    every compressed key is made anew from the keys (one read of the part;
    a pad's place is overwritten with the key that replaces it) and the
    selection is a mask.  A cache shorter than ``dense_len`` keeps no
    compressed keys up to date: nothing reads them.  Returns (attn
    [B, T, H, D], ck)."""
    from ..ops import sparse_attention as sa

    spec = c.sparse
    batch, t = q.shape[:2]
    selects = keys.shape[2] >= spec.dense_len
    if t == 1:
        if selects:
            with jax.named_scope("cache_update"):
                index, key = sa.completed_key(keys, first + 1, spec)
                ck = ck.at[jnp.arange(batch)[:, None],
                           jnp.arange(ck.shape[1])[None, :],
                           index[:, None]].set(key.astype(ck.dtype),
                                               mode="drop")
        with jax.named_scope("cache_attn"):
            attn, counted = sa.sparse_decode_attention(q, keys, values, ck,
                                                       first, spec)
        if sparse_stats is not None:
            sparse_stats.append(counted)
        return attn, ck
    with jax.named_scope("cache_attn"):
        if selects:
            with jax.named_scope("attn"), jax.named_scope("sparse"), \
                    jax.named_scope("select"):
                ck = sa.compress_keys(keys, spec)
        attn = sa.sparse_blockwise_attention(q, keys, values, ck, first, spec)
    return attn, ck


def _query_rows(c, q: Array, pack: int) -> Array:
    """q [B, T, H, D] as the rows that meet a part's rows of ``pack``
    heads: [B, T, KV / pack, pack * G, pack * D], each head's queries in
    its own lanes of a row of zeros."""
    b, t = q.shape[:2]
    qg = q.reshape(b, t, c.kv_heads // pack, pack, c.kv_groups, c.head_dim)
    if pack > 1:
        mine = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
        qg = qg[:, :, :, :, :, None, :] * mine    # [B, T, KV', j, G, j', D]
    return qg.reshape(b, t, c.kv_heads // pack, pack * c.kv_groups,
                      pack * c.head_dim)


def _own_lanes(c, out: Array, pack: int) -> Array:
    """:func:`_query_rows` undone on a result [B, T, KV / pack, pack * G,
    pack * D]: a head's result is its own lanes of its row's, [B, T, H,
    D]."""
    b, t = out.shape[:2]
    if pack > 1:
        out = out.reshape(b, t, c.kv_heads // pack, pack, c.kv_groups, pack,
                          c.head_dim)
        mine = jnp.eye(pack, dtype=out.dtype)[:, None, :, None]
        out = (out * mine).sum(axis=5)
    return out.reshape(b, t, c.n_heads, c.head_dim)


def _cache_scores(c, q: Array, keys: Array) -> Array:
    """q [B, T, H, D] against ``keys`` [B, K, KV / pack, pack * D] (a part
    of the cache, or a block packed like one): [B, KV, G, T, K] in f32,
    unscaled.  GQA: query-head groups contract directly against the
    UNexpanded keys — the cache bytes streamed per step stay
    kv_heads-sized (the point of the smaller cache), no materialized
    repeat.  Where ``pack`` heads share a row, each head's queries sit in
    its own lanes of a row of zeros (:func:`_query_rows`), so the product
    reads the part as it is stored: ``pack`` times the multiplications, on
    a round that waits for the cache's bytes, and sums that differ from
    the unpacked ones by added zeros."""
    b, t = q.shape[:2]
    scores = jnp.einsum("bqhgd,bkhd->bhgqk",
                        _query_rows(c, q, c.kv_heads // keys.shape[2]), keys,
                        preferred_element_type=jnp.float32)
    return scores.reshape(b, c.kv_heads, c.kv_groups, t, keys.shape[1])


def _cache_weighted(c, probs: Array, values: Array) -> Array:
    """probs [B, KV, G, T, K] over ``values`` [B, K, KV / pack, pack * D]:
    [B, T, H, D] in f32.  The other half of :func:`_cache_scores`: a
    head's result is its own lanes of its row's (:func:`_own_lanes`)."""
    b, _, _, t, held = probs.shape
    pack = c.kv_heads // values.shape[2]
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd",
        probs.reshape(b, c.kv_heads // pack, pack * c.kv_groups, t, held),
        values, preferred_element_type=jnp.float32)
    return _own_lanes(c, out, pack)


def _round_arm(kind: str, cache, q_shape: tuple[int, ...], part) -> str:
    """``transformer.round_arm`` for a layer of ``kind``, q [B, T, H, D]
    against ``part`` of ``cache``, over as many devices as the cache is
    spread."""
    return _transformer.round_arm(kind, q_shape, part.shape, part.dtype,
                                  getattr(cache, "devices", 1))


def full_round_block(model: Transformer, cache, lanes: int) -> int:
    """The positions a plain decode round of ``lanes`` lanes fetches AT A
    TIME of each full layer's parts of ``cache``: the kernel's block where
    :func:`decode_block` runs the layer through ops/pallas/full_decode.py,
    0 where it reads the parts whole (what ``DecodeServer`` counts its
    ``serve.full.positions_read`` by)."""
    c = model.config
    parts = [part for i, part in enumerate(cache.k)
             if not (isinstance(cache, KVCache) and cache.by_head(i))]
    if not parts or _round_arm(
            "softmax", cache, (lanes, 1, c.n_heads, c.head_dim),
            parts[0]) != "kernel":
        return 0
    from ..ops.pallas import full_decode

    rows = parts[0].shape[2]
    return full_decode.block_positions(
        parts[0].shape, parts[0].dtype.itemsize, _lies_by_head(rows),
        c.n_heads // rows)


def _kernel_cache_attention(c, q: Array, keys: Array, values: Array,
                            lengths: Array) -> Array:
    """A decode round's single token a lane, q [B, 1, H, D], against a
    full layer's parts [B, M, KV / pack, pack * D] through the kernel of
    ops/pallas/full_decode.py (``transformer.round_arm`` says when):
    K and V are read a block of positions at a time, once, and no block
    past a lane's ``lengths`` (its live positions, the new token included)
    is fetched.  The queries meet a row of ``pack`` heads as
    :func:`_cache_scores` lays them, and a head takes its own lanes of the
    result as in :func:`_cache_weighted`.  The kernel takes a part where
    it lies: one the device lays by head (:func:`_lies_by_head`) goes in
    turned to [B, KV', M, D'], which is the same bytes in the same order
    (tests/test_chip_compile.py holds that the compiled round copies
    nothing)."""
    from ..ops.pallas import full_decode

    pack = c.kv_heads // keys.shape[2]
    by_head = _lies_by_head(keys.shape[2])
    if by_head:
        keys, values = (x.transpose(0, 2, 1, 3) for x in (keys, values))
    with jax.named_scope("attn_kernel"):
        out = full_decode.full_decode_attention(
            _query_rows(c, q, pack)[:, 0], keys, values, lengths,
            c.head_dim ** -0.5, by_head)
    return _own_lanes(c, out[:, None], pack).astype(c.dtype)


def _dense_cache_attention(c, q: Array, keys: Array, values: Array,
                           mask: Array, scales) -> Array:
    """Dense attention of q [B, T, H, D] against one layer's part of the
    cache stored by position (keys/values [B, M, KV / pack, pack * D]),
    f32 softmax.  int8 cache (``scales`` = (k_scale, v_scale), each
    [B, M, KV]): contract against the int8 array (only int8 bytes stream
    from HBM; the convert fuses into the einsum) and fold the
    per-(position, head) scale into the product afterwards."""
    quant = scales is not None
    scores = _cache_scores(c, q, keys.astype(c.dtype) if quant else keys)
    if quant:
        # k_scale: [B, M, H] -> [B, H, 1, 1, M] over score axes
        scores = scores * jnp.transpose(
            scales[0], (0, 2, 1))[:, :, None, None, :]
    scores = scores / jnp.sqrt(jnp.asarray(c.head_dim, jnp.float32))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    if quant:
        # fold v_scale into probs (tiny [.., M] multiply) so the value
        # contraction streams raw int8
        probs = probs * jnp.transpose(
            scales[1], (0, 2, 1))[:, :, None, None, :].astype(c.dtype)
    return _cache_weighted(
        c, probs, values.astype(c.dtype) if quant else values
    ).astype(c.dtype)


def _ring_attention(c, q: Array, k: Array, v: Array, ring_k: Array,
                    ring_v: Array, positions: Array,
                    counts: Array | None) -> tuple[Array, Array, Array]:
    """A window layer against its ring.  q [B, T, H, D] at
    ``positions`` [B, T]; k/v [B, T, KV', D'] the block's own, packed like
    the ring; ring_k/ring_v [B, W, KV', D'] with position p at index
    p % W.  The queries attend
    what the ring held BEFORE the block (index s: the latest position
    below the block's first that is congruent to s, seen while within the
    window) and then the block itself, causally; afterwards the block's
    real positions (the first ``counts[b]``; all by default) overwrite the
    ring's oldest.  Returns (attn [B, T, H, D], ring_k, ring_v)."""
    batch, t = positions.shape
    window = ring_k.shape[1]
    if t > window:
        raise ValueError(f"a block of {t} positions does not go through a "
                         f"ring of {window}: forward it against a cache "
                         "stored by position")
    k, v = k.astype(ring_k.dtype), v.astype(ring_v.dtype)
    scale = jnp.sqrt(jnp.asarray(c.head_dim, jnp.float32))
    first = positions[:, :1]                                  # [B, 1]
    with jax.named_scope("cache_attn"), jax.named_scope("attn"), \
            jax.named_scope("window"):
        held = first - 1 - (first - 1 - jnp.arange(window)[None]) % window
        # [B, T, W]: in the ring yet, and still within the query's window
        seen = ((held >= 0)[:, None, :]
                & (positions[:, :, None] - held[:, None, :] < window))
        offsets = jnp.arange(t)
        within = ((offsets[None, :] <= offsets[:, None])
                  & (offsets[:, None] - offsets[None, :] < window))
        mask = jnp.concatenate(
            [seen, jnp.broadcast_to(within[None], (batch, t, t))], axis=-1)
        scores = jnp.concatenate([_cache_scores(c, q, ring_k),
                                  _cache_scores(c, q, k)], axis=-1) / scale
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
        attn = (_cache_weighted(c, probs[..., :window], ring_v)
                + _cache_weighted(c, probs[..., window:], v)).astype(c.dtype)
    with jax.named_scope("cache_update"):
        at = positions % window
        if counts is not None:
            # a pad position's write falls outside the ring and is dropped
            at = jnp.where(offsets[None, :] < counts[:, None], at, window)
        bidx = jnp.arange(batch, dtype=jnp.int32)[:, None]
        ring_k = ring_k.at[bidx, at].set(k, mode="drop")
        ring_v = ring_v.at[bidx, at].set(v, mode="drop")
    return attn, ring_k, ring_v


def decode_step(model: Transformer, params: Mapping[str, Array],
                token: Array, cache: KVCache | QuantKVCache,
                ) -> tuple[Array, KVCache | QuantKVCache]:
    """One single-token forward against the cache.  token: [B] int32 ->
    (logits [B, vocab] float32, updated cache)."""
    logits, cache = decode_block(model, params, token[:, None], cache)
    return logits[:, 0], cache


def _truncate_logits(logits: Array, top_k: int, top_p: float) -> Array:
    """Top-k and/or nucleus truncation on temperature-scaled logits
    (shared by the scalar and per-row samplers)."""
    top_k = min(top_k, logits.shape[-1])  # top_k > vocab = no truncation
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if 0.0 < top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cumulative = jnp.cumsum(probs, axis=-1)
        # keep a token while the cumulative mass BEFORE it is < top_p
        # (the argmax token is always kept); cut logits below the
        # smallest kept one
        keep = (cumulative - probs) < top_p
        kth = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                      keepdims=True)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


def sample_token(logits: Array, rng: Array, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0) -> Array:
    """Greedy when temperature == 0; otherwise temperature softmax
    sampling, optionally truncated to the top_k logits and/or the nucleus
    (smallest set of tokens with cumulative probability >= top_p)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _truncate_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_token_rowwise(logits: Array, rng: Array, temps: Array,
                         top_k: int = 0, top_p: float = 0.0) -> Array:
    """Per-row temperature sampling in ONE traced program: row i is
    greedy when ``temps[i] == 0``, temperature-sampled otherwise
    (top_k/top_p truncation stays static — shared by all rows).  Lets a
    continuous-batching server honor per-request temperatures without a
    recompile per distinct value.  logits: [B, V]; temps: [B]."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    scaled = _truncate_logits(scaled, top_k, top_p)
    sampled = jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


# Compiled runner cache: one jitted wrapper per (model, generation config),
# keyed on the model's never-reused cache_token (id() can be recycled after
# GC).  jax.jit's own cache then handles distinct prompt shapes.
# Bounded LRU: a long-lived service sweeping generation settings would
# otherwise pin compiled executables (and their models) for process
# lifetime.  Lock-guarded — concurrent generate() calls share the cache.
_RUNNERS: "OrderedDict[tuple, object]" = OrderedDict()
# Room for one serving process's programs: a server holds an extension and
# a splice program per (resident row width, suffix bucket) — four resident
# documents by six buckets are already 57 with the prefills and the step —
# and a runner pushed out is retraced and loaded again mid-service, a stall
# of 0.2-0.7 s (chip runs, PR 27, at the 32 this was).
_RUNNERS_MAX = 256
_RUNNERS_LOCK = threading.Lock()


def _model_key(model) -> int:
    # cache_token is assigned in Transformer.__init__; getattr keeps
    # duck-typed model stand-ins (tests) working, accepting id()'s
    # recycling caveat only for those.
    token = getattr(model, "cache_token", None)
    return id(model) if token is None else token


def _cached_runner(key: tuple, build):
    """LRU-cached compiled runner: one lock/evict protocol for every
    runner flavor.  A concurrent miss may build twice (benign — last
    insert wins and the loser is garbage)."""
    with _RUNNERS_LOCK:
        run = _RUNNERS.get(key)
        if run is not None:
            _RUNNERS.move_to_end(key)
            return run
    run = build()
    # a program built where none should be (inside a serving window) then
    # has a name inside the program: the counter moves
    obs_stats.counter("serve.programs").add()
    with _RUNNERS_LOCK:
        _RUNNERS[key] = run
        while len(_RUNNERS) > _RUNNERS_MAX:
            _RUNNERS.popitem(last=False)
    return run


def _runner(model: Transformer, max_new_tokens: int, temperature: float,
            top_k: int, top_p: float, cache_dtype: str = "native"):
    key = (_model_key(model), max_new_tokens, temperature, top_k, top_p,
           cache_dtype)

    def build():
        @jax.jit
        def run(params, prompt, rng):
            max_len = prompt.shape[1] + max_new_tokens
            logits, cache = prefill(model, params, prompt, max_len,
                                    cache_dtype)
            rng0, rng = jax.random.split(rng)
            first = sample_token(logits, rng0, temperature, top_k, top_p)

            def body(carry, _):
                token, cache, rng = carry
                rng, sub = jax.random.split(rng)
                logits, cache = decode_step(model, params, token, cache)
                nxt = sample_token(logits, sub, temperature, top_k, top_p)
                return (nxt, cache, rng), token

            (_, _, _), tokens = jax.lax.scan(
                body, (first, cache, rng), None, length=max_new_tokens)
            return jnp.swapaxes(tokens, 0, 1)      # [B, max_new]

        return run

    return _cached_runner(key, build)


def _beam_runner(model: Transformer, max_new_tokens: int, beam_width: int,
                 eos_id: int | None, length_penalty: float):
    key = (_model_key(model), max_new_tokens, "beam", beam_width, eos_id,
           length_penalty)

    def build():
        @jax.jit
        def run(params, prompt):
            b, s = prompt.shape
            w = beam_width
            max_len = s + max_new_tokens
            logits, cache = prefill(model, params, prompt, max_len)
            logp = jax.nn.log_softmax(logits, axis=-1)        # [B, V]
            vocab = logp.shape[-1]
            scores, first = jax.lax.top_k(logp, w)            # [B, W]
            finished = (jnp.zeros((b, w), bool) if eos_id is None
                        else first == eos_id)
            lengths = jnp.ones((b, w), jnp.int32)

            # beams live interleaved in the cache batch dim: row b*W + j
            def over_rows(fn, cache):
                """fn on the batch axis of every part of the cache."""
                return jax.tree.map(
                    lambda x: fn(x) if x.ndim > 2 else x, cache)

            cache = over_rows(lambda x: jnp.repeat(x, w, axis=0), cache)
            seqs = jnp.zeros((b, w, max_new_tokens), jnp.int32)
            seqs = seqs.at[:, :, 0].set(first)

            def body(carry, i):
                seqs, scores, finished, lengths, cache = carry
                tok = jax.lax.dynamic_index_in_dim(
                    seqs, i - 1, axis=2, keepdims=False)       # [B, W]
                logits, cache = decode_step(model, params,
                                            tok.reshape(b * w), cache)
                logp = jax.nn.log_softmax(logits, axis=-1).reshape(
                    b, w, vocab)
                if eos_id is not None:
                    # a finished beam may only continue with EOS at logp 0:
                    # its joint score freezes and it stays comparable in
                    # the flat top-k against live beams
                    pad = jnp.full((vocab,), -jnp.inf).at[eos_id].set(0.0)
                    logp = jnp.where(finished[:, :, None],
                                     pad[None, None, :], logp)
                total = scores[:, :, None] + logp
                scores, flat = jax.lax.top_k(
                    total.reshape(b, w * vocab), w)            # [B, W]
                parent = flat // vocab                         # [B, W]
                token = (flat % vocab).astype(jnp.int32)
                # reorder histories and cache rows onto the winning beams
                seqs = jnp.take_along_axis(seqs, parent[:, :, None], axis=1)
                seqs = jax.lax.dynamic_update_slice_in_dim(
                    seqs, token[:, :, None], i, axis=2)
                finished = jnp.take_along_axis(finished, parent, axis=1)
                lengths = jnp.take_along_axis(lengths, parent, axis=1)
                # a beam already finished keeps its length; live beams
                # (including one finishing right now, whose EOS counts)
                # are i+1 tokens long
                lengths = jnp.where(finished, lengths, i + 1)
                if eos_id is not None:
                    finished = finished | (token == eos_id)
                rows = (jnp.arange(b)[:, None] * w + parent).reshape(-1)
                cache = over_rows(lambda x: jnp.take(x, rows, axis=0),
                                  cache)
                return (seqs, scores, finished, lengths, cache), None

            (seqs, scores, _, lengths, _), _ = jax.lax.scan(
                body, (seqs, scores, finished, lengths, cache),
                jnp.arange(1, max_new_tokens))
            if length_penalty:
                # GNMT normalization at final selection only (within-step
                # pruning stays raw-joint-log-prob): score / lp(len) with
                # lp = ((5 + len) / 6) ** alpha
                lp = ((5.0 + lengths.astype(jnp.float32)) / 6.0
                      ) ** length_penalty
                best = jnp.argmax(scores / lp, axis=1)
            else:
                best = jnp.argmax(scores, axis=1)
            out = jnp.take_along_axis(seqs, best[:, None, None],
                                      axis=1)[:, 0]            # [B, max_new]
            return out, jnp.take_along_axis(scores, best[:, None],
                                            axis=1)[:, 0]

        return run

    return _cached_runner(key, build)


def beam_search(model: Transformer, params: Mapping[str, Array],
                prompt: Array, max_new_tokens: int,
                beam_width: int = 4,
                eos_id: int | None = None,
                length_penalty: float = 0.0) -> tuple[Array, Array]:
    """Fixed-length beam search over ``max_new_tokens`` continuations:
    keeps the ``beam_width`` highest joint-log-prob prefixes each step,
    reordering the KV cache rows onto the surviving beams (beams live
    interleaved in the cache batch dim).  Returns (tokens [B, max_new],
    joint log-prob [B]) for each item's best beam.  beam_width=1 is
    greedy decoding.  With ``eos_id`` set, a beam that emits it finishes:
    its score freezes and it pads with EOS while live beams keep
    expanding (the scan still runs the static full length — shapes never
    change; trim at the first EOS on the host).  ``length_penalty``
    alpha > 0 applies GNMT length normalization (score / ((5+len)/6)^a)
    at the FINAL beam selection, countering the short-hypothesis bias
    EOS finishing introduces; 0 selects by raw joint log-prob."""
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if not 1 <= beam_width <= model.config.vocab:
        raise ValueError(f"beam_width={beam_width} must be in "
                         f"[1, vocab={model.config.vocab}]")
    if eos_id is not None and not 0 <= eos_id < model.config.vocab:
        raise ValueError(f"eos_id={eos_id} outside vocab "
                         f"{model.config.vocab}")
    check_position_budget(model, int(np.asarray(prompt).shape[1]),
                          max_new_tokens)
    return _beam_runner(model, max_new_tokens, beam_width, eos_id,
                        float(length_penalty))(params, prompt)


def _decode_step_runner(model: Transformer):
    key = (_model_key(model), "spec_step")
    return _cached_runner(key, lambda: jax.jit(
        lambda params, tok, cache: decode_step(model, params, tok, cache)))


def _decode_block_runner(model: Transformer, t: int):
    key = (_model_key(model), "spec_block", t)
    return _cached_runner(key, lambda: jax.jit(
        lambda params, toks, cache: decode_block(model, params, toks, cache)))


def accept_or_resample(p: "np.ndarray", q: "np.ndarray", x: int,
                       rng: "np.random.Generator") -> tuple[int, bool]:
    """The speculative-sampling rejection rule (Leviathan/Chen): accept
    draft token ``x`` (drawn from q) with probability min(1, p[x]/q[x]);
    on reject, sample from the residual normalize(max(p - q, 0)).  Over
    the randomness of (x ~ q, this rule), the returned token is EXACTLY
    distributed as p — tested empirically in tests/test_generation.py.
    Returns (token, accepted)."""
    if rng.uniform() < min(1.0, float(p[x]) / max(float(q[x]), 1e-20)):
        return x, True
    residual = np.maximum(p - q, 0.0)
    total = residual.sum()
    if total <= 0.0:   # p == q: acceptance was certain, but guard anyway
        return int(rng.choice(len(p), p=p / p.sum())), False
    return int(rng.choice(len(p), p=residual / total)), False


def speculative_generate(target: Transformer, target_params,
                         draft: Transformer, draft_params,
                         prompt: Array, max_new_tokens: int, *,
                         draft_len: int = 4, temperature: float = 0.0,
                         seed: int = 0) -> tuple[Array, dict]:
    """Greedy speculative decoding: the cheap ``draft`` model proposes
    ``draft_len`` tokens autoregressively, the ``target`` verifies them in
    ONE ``decode_block`` forward, and the longest agreeing prefix plus the
    target's own next token commit — per verify call the output advances
    1..draft_len+1 tokens at one target forward, while remaining
    TOKEN-EXACT vs target-alone greedy decoding (tested).  Rejection
    rollback is free: KVCache.length just moves back, stale entries are
    masked and overwritten.

    ``temperature=0`` is greedy (output token-exact vs target-alone
    greedy decoding); ``temperature>0`` is speculative SAMPLING with the
    rejection rule (:func:`accept_or_resample`), which preserves the
    target's temperature-adjusted sampling distribution exactly.

    Batch 1 (rows would accept different counts and the cache keeps one
    scalar length).  Returns (tokens [1, max_new], stats) where stats
    reports verify calls and acceptance counts — the speedup story on
    real hardware is target-forwards / tokens."""
    if prompt.shape[0] != 1:
        raise ValueError("speculative decoding is batch-1 (per-row "
                         "acceptance lengths diverge)")
    if target.config.vocab != draft.config.vocab:
        raise ValueError(
            f"vocab mismatch: target {target.config.vocab} vs draft "
            f"{draft.config.vocab}")
    if draft_len < 1:
        raise ValueError("draft_len must be >= 1")

    s = prompt.shape[1]
    # + draft_len + 1: a verify block may run past the committed length
    # before rolling back
    check_rolls_back(target)
    check_rolls_back(draft)
    check_position_budget(target, s, max_new_tokens + draft_len + 1)
    check_position_budget(draft, s, max_new_tokens + draft_len + 1)
    sampling = temperature > 0.0
    host_rng = np.random.default_rng(seed)

    def host_probs(logits_row) -> "np.ndarray":
        p = np.asarray(jax.nn.softmax(logits_row / temperature, axis=-1),
                       np.float64)
        return p / p.sum()

    # headroom: a verify block may write draft_len+1 entries past the
    # committed length before rolling back
    max_len = s + max_new_tokens + draft_len + 1
    t_logits, t_cache = prefill(target, target_params, prompt, max_len)
    _, d_cache = prefill(draft, draft_params, prompt, max_len)
    d_step = _decode_step_runner(draft)
    t_block = _decode_block_runner(target, draft_len + 1)

    out: list[int] = []
    if sampling:
        p0 = host_probs(t_logits[0])
        cur = int(host_rng.choice(len(p0), p=p0))
    else:
        cur = int(np.asarray(jnp.argmax(t_logits, axis=-1))[0])
    out.append(cur)
    pending: list[int] = []   # committed tokens not yet in the draft cache
    verify_calls = 0
    accepted_total = 0

    while len(out) < max_new_tokens:
        for tok in pending:   # catch the draft cache up to the context
            _, d_cache = d_step(draft_params,
                                jnp.asarray([tok], jnp.int32), d_cache)
        pending = []
        proposals: list[int] = []
        d_probs: list = []
        dtok = cur
        for _ in range(draft_len):
            dl, d_cache = d_step(draft_params,
                                 jnp.asarray([dtok], jnp.int32), d_cache)
            if sampling:
                q = host_probs(dl[0])
                dtok = int(host_rng.choice(len(q), p=q))
                d_probs.append(q)
            else:
                dtok = int(np.asarray(jnp.argmax(dl, axis=-1))[0])
            proposals.append(dtok)
        # target verifies [cur, p1..pk] in one forward: logits[i] scores
        # the target's token after ...cur,p1..p_i
        block = jnp.asarray([[cur] + proposals], jnp.int32)
        base = int(np.asarray(t_cache.length))
        logits, t_cache = t_block(target_params, block, t_cache)
        verify_calls += 1

        if sampling:
            rows = np.asarray(jax.nn.softmax(logits[0] / temperature,
                                             axis=-1), np.float64)
            p_all = [row / row.sum() for row in rows]  # one dispatch
            m = 0
            committed: list[int] = []
            while m < draft_len:
                token, ok = accept_or_resample(
                    p_all[m], d_probs[m], proposals[m], host_rng)
                if not ok:
                    committed.append(token)
                    break
                committed.append(token)
                m += 1
            else:
                # full accept: bonus token from the target's own dist
                committed.append(int(host_rng.choice(
                    len(p_all[draft_len]), p=p_all[draft_len])))
        else:
            greedy = np.asarray(jnp.argmax(logits, axis=-1))[0]  # [k+1]
            m = 0
            while m < draft_len and proposals[m] == int(greedy[m]):
                m += 1
            committed = proposals[:m] + [int(greedy[m])]
        accepted_total += m
        out.extend(committed)
        cur = committed[-1]
        if m == draft_len:
            # full accept + bonus token: every block entry (cur, p1..pk)
            # is committed context; the draft cache is missing p_k
            t_cache = dataclasses.replace(
                t_cache, length=jnp.asarray(base + draft_len + 1,
                                            jnp.int32))
            pending = [proposals[-1]]
        else:
            # cache keeps cur..p_{m-1} (m+1 entries); the draft cache
            # holds the same prefix plus rejected entries — roll both back
            t_cache = dataclasses.replace(
                t_cache, length=jnp.asarray(base + m + 1, jnp.int32))
            d_cache = dataclasses.replace(
                d_cache, length=jnp.asarray(base + m + 1, jnp.int32))

    tokens = np.asarray(out[:max_new_tokens], np.int32)[None]
    stats = {"verify_calls": verify_calls,
             "draft_accept_rate": (accepted_total
                                   / max(1, verify_calls * draft_len)),
             # +1: the prefill forward produced out[0] and also counts
             "tokens_per_target_forward": (tokens.shape[1]
                                           / (verify_calls + 1))}
    return tokens, stats


def _draft_propose(draft: Transformer, dparams, q_logits: Array,
                   d_cache, pc: Array, k_draft: int, temperature: float,
                   keys: list) -> tuple[Array, list, Any]:
    """The draft's k-proposal loop after its catch-up block: sample (or
    argmax) each proposal, collecting the tempered proposal distributions
    the rejection rule needs, stepping the draft cache k-1 times at the
    per-row ragged positions.  Returns (props [B, k], q_rows, d_cache).
    Shared single definition — see :func:`_greedy_accept`."""
    sampling = temperature > 0.0
    proposals = []
    q_rows: list = []
    for i in range(k_draft):
        if sampling:
            tok = jax.random.categorical(
                keys[i], q_logits / temperature, axis=-1).astype(jnp.int32)
            q_rows.append(jax.nn.softmax(q_logits / temperature, axis=-1))
        else:
            tok = jnp.argmax(q_logits, axis=-1).astype(jnp.int32)
        proposals.append(tok)
        if i < k_draft - 1:
            dl, d_cache = decode_block(draft, dparams, tok[:, None],
                                       d_cache, lengths=pc + 1 + i)
            q_logits = dl[:, 0]
    return jnp.stack(proposals, axis=1), q_rows, d_cache


def _greedy_accept(vlogits: Array, props: Array) -> tuple[Array, Array]:
    """Longest-matching-prefix acceptance for a verify block
    [cur, p_1..p_k]: (m accepted counts [B], corr next token [B]).
    Shared by the one-shot batched decoder and the serving round runner
    (models/serving.py) so the acceptance math exists once."""
    k_draft = props.shape[1]
    g = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)     # [B, k+1]
    match = (props == g[:, :k_draft]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # [B]
    corr = jnp.take_along_axis(g, m[:, None], 1)[:, 0]
    return m, corr


def _sampling_accept(vlogits: Array, props: Array, q_rows: list,
                     temperature: float, key_u: Array, key_resample: Array,
                     key_bonus: Array) -> tuple[Array, Array]:
    """Vectorized Leviathan/Chen rejection for a verify block
    [cur, p_1..p_k]: accept each proposal with prob min(1, p/q), resample
    the reject position from the residual (clamped gather; overridden by
    the bonus draw when everything accepted).  Preserves the target's
    temperature-adjusted distribution exactly.  Shared single definition
    — see :func:`_greedy_accept`."""
    k_draft = props.shape[1]
    probs_t = jax.nn.softmax(vlogits / temperature, axis=-1)
    probs_q = jnp.stack(q_rows, axis=1)                    # [B, k, V]
    px = jnp.take_along_axis(
        probs_t[:, :k_draft], props[..., None], 2)[..., 0]
    qx = jnp.take_along_axis(probs_q, props[..., None], 2)[..., 0]
    u = jax.random.uniform(key_u, px.shape)
    acc = u < px / jnp.maximum(qx, 1e-20)
    m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), 1), 1)
    gather_m = jnp.clip(m, 0, k_draft - 1)[:, None, None]
    p_m = jnp.take_along_axis(probs_t[:, :k_draft], gather_m, 1)[:, 0]
    q_m = jnp.take_along_axis(probs_q, gather_m, 1)[:, 0]
    residual = jnp.maximum(p_m - q_m, 0.0)
    total = jnp.sum(residual, -1, keepdims=True)
    residual = jnp.where(total > 0, residual, p_m)
    resampled = jax.random.categorical(
        key_resample, jnp.log(residual + 1e-30), axis=-1)
    bonus = jax.random.categorical(
        key_bonus, jnp.log(probs_t[:, k_draft] + 1e-30), axis=-1)
    corr = jnp.where(m == k_draft, bonus, resampled).astype(jnp.int32)
    return m, corr


def _init_spec_carry(target, tparams, draft, dparams, prompt, cap: int,
                     max_len: int, temperature: float, seed: int,
                     cache_dtype: str):
    """Prefill both models and build the carry the speculative segment
    runners thread: (n_out, out, cur, y, lt, pc, t_cache, d_cache, rng,
    stats[verifies, accepts, active_rows]) — the single definition of
    the speculative decode state, shared by the fixed-depth and
    adaptive paths."""
    batch, s = prompt.shape
    t_logits, t_cache = prefill(target, tparams, prompt, max_len,
                                cache_dtype)
    _, d_cache = prefill(draft, dparams, prompt, max_len, cache_dtype)
    rng = jax.random.key(seed)
    if temperature > 0.0:
        rng, k0 = jax.random.split(rng)
        cur = jax.random.categorical(k0, t_logits / temperature,
                                     axis=-1).astype(jnp.int32)
    else:
        cur = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
    out = jnp.zeros((batch, cap), jnp.int32).at[:, 0].set(cur)
    return (jnp.ones((batch,), jnp.int32), out, cur,
            jnp.asarray(prompt[:, -1], jnp.int32),
            jnp.full((batch,), s, jnp.int32),
            jnp.full((batch,), s, jnp.int32),
            t_cache, d_cache, rng, jnp.zeros((3,), jnp.int32))


def _spec_round_runner(target: Transformer, draft: Transformer,
                       draft_len: int, cache_dtype: str,
                       temperature: float = 0.0):
    """Jitted per (target, draft, k, T): ONE speculative round over ALL
    slots — draft catch-up block + k-1 single proposals, one target
    verify block, vectorized acceptance.  The same math as
    generation._spec_segment_runner's loop body, but one round per call
    so the host can admit/retire requests between rounds (continuous
    batching).  Greedy (T=0, longest matching prefix) is token-exact
    whatever each slot's accept rate; T>0 applies the Leviathan/Chen
    rejection rule, preserving the target's sampling distribution.
    Returns (commit [B, k+1], n_commit [B], cur_new [B], y_new [B],
    t_cache, d_cache, rng)."""
    key = (_model_key(target), _model_key(draft), "serve_spec_round",
           draft_len, cache_dtype, temperature)
    k_draft = draft_len
    sampling = temperature > 0.0

    def build():
        @partial(jax.jit, donate_argnums=(4, 5))
        def run(tparams, dparams, cur, y, t_cache, d_cache, lt, pc, rng):
            batch = cur.shape[0]
            iota_k1 = jnp.arange(k_draft + 1, dtype=jnp.int32)
            # draft: catch-up block [y, cur] (re-writing y's slot is a
            # no-op; writing fresh is the full-accept catch-up), then
            # k-1 single steps
            dl, d_cache = decode_block(
                draft, dparams, jnp.stack([y, cur], axis=1), d_cache,
                lengths=pc - 1)
            rng, *keys = jax.random.split(rng, k_draft + 4)
            props, q_rows, d_cache = _draft_propose(
                draft, dparams, dl[:, 1], d_cache, pc, k_draft,
                temperature, keys)
            # target verifies [cur, p_1..p_k] in one ragged forward
            block = jnp.concatenate([cur[:, None], props], axis=1)
            vlogits, t_cache = decode_block(target, tparams, block,
                                            t_cache, lengths=lt)
            if sampling:
                m, corr = _sampling_accept(
                    vlogits, props, q_rows, temperature, keys[k_draft],
                    keys[k_draft + 1], keys[k_draft + 2])
            else:
                m, corr = _greedy_accept(vlogits, props)
            ext = jnp.concatenate(
                [props, jnp.zeros((batch, 1), jnp.int32)], axis=1)
            commit = jnp.where(iota_k1[None, :] < m[:, None], ext,
                               corr[:, None])             # [B, k+1]
            prev = jnp.take_along_axis(
                props, jnp.clip(m - 1, 0, k_draft - 1)[:, None], 1)[:, 0]
            y_new = jnp.where(m == 0, cur, prev)
            return commit, m + 1, corr, y_new, t_cache, d_cache, rng

        return run

    return _cached_runner(key, build)


def _invert_accept_fraction(f: float, k: int) -> float:
    """Per-proposal agreement p from a measured accept FRACTION
    f = E[m]/k at depth k, under the geometric-acceptance model
    E[m] = sum_{i=1..k} p^i (each proposal agrees independently with
    probability p; the round commits the longest agreeing prefix).
    Monotone in p -> bisection."""
    if f <= 0.0:
        return 0.0
    if f >= 1.0:
        return 1.0
    lo, hi = 0.0, 1.0
    for _ in range(40):
        mid = (lo + hi) / 2
        if sum(mid ** i for i in range(1, k + 1)) / k < f:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def optimal_draft_depth(accept_frac: float, k: int, k_max: int,
                        cost_ratio: float,
                        round_overhead: float = 0.25,
                        allow_disable: bool = False) -> int:
    """The depth maximizing expected tokens per round COST: a round at
    depth j commits E(p, j) = (1 - p^(j+1)) / (1 - p) tokens (accepted
    prefix + correction/bonus) and costs ``round_overhead`` + 1 target
    forward + j draft forwards at ``cost_ratio`` target-units each.
    ``round_overhead`` is the spec round's fixed overhead IN EXCESS OF
    a plain greedy step (extra dispatches: draft catch-up block, wider
    verify, commit bookkeeping) — defined that way, plain greedy scores
    exactly 1.0 token/unit, which is what the ``allow_disable``
    threshold compares against; it also breaks the cost_ratio=1.0 tie
    toward deeper drafts (fewer rounds, less excess overhead).
    ``accept_frac`` is the measured fraction at the CURRENT depth k
    (inverted to per-proposal agreement p first — fractions are not
    comparable across depths).  At p=0.57 and rho~1/3 the model picks
    k* in {1, 2} and scores k=4 below 1 (the over-speculation regime);
    the speed of either is not measured on the chip."""
    p = _invert_accept_fraction(accept_frac, k)
    best_k, best = 1, -1.0
    for j in range(1, max(1, k_max) + 1):
        expect = (j + 1.0 if p >= 1.0
                  else (1.0 - p ** (j + 1)) / (1.0 - p))
        score = expect / (round_overhead + 1.0 + cost_ratio * j)
        if score > best:
            best, best_k = score, j
    if allow_disable and best < 1.0:
        # even the best depth expects fewer tokens per cost than plain
        # greedy decoding (score 1.0): speculation cannot pay with this
        # draft — k=0 means "decode greedy", the arm that makes adaptive
        # speculation never lose beyond its calibration segment
        return 0
    return best_k


def _spec_segment_runner(target: Transformer, draft: Transformer,
                         cap: int, max_new_tokens: int, draft_len: int,
                         temperature: float, cache_dtype: str):
    """Resumable segment of the whole-loop batched speculative decoder:
    the speculative while_loop body over an explicit carry: the
    carry is an argument/result and the loop runs until every row
    reaches a TRACED ``seg_target`` — so an adaptive driver can run a
    few segments with different depths k (one compiled program per k,
    shared carry shapes sized by ``cap``/k_max) and re-pick k between
    them from the measured accept rate, keeping the decode device-bound
    (host syncs per SEGMENT, not per round)."""
    key_tuple = (_model_key(target), _model_key(draft), "spec_segment",
                 cap, max_new_tokens, draft_len, temperature, cache_dtype)
    k_draft = draft_len
    sampling = temperature > 0.0

    def build():
        @jax.jit
        def run(tparams, dparams, carry, seg_target):
            batch = carry[0].shape[0]
            bidx = jnp.arange(batch, dtype=jnp.int32)[:, None]
            iota_k1 = jnp.arange(k_draft + 1, dtype=jnp.int32)

            def cond(carry):
                return jnp.any(carry[0] < seg_target)

            def body(carry):
                (n_out, out, cur, y, lt, pc, t_cache, d_cache, rng_key,
                 stats) = carry
                active = n_out < max_new_tokens

                dl, d_cache = decode_block(
                    draft, dparams, jnp.stack([y, cur], axis=1), d_cache,
                    lengths=pc - 1)
                rng_key, *keys = jax.random.split(rng_key, k_draft + 3)
                props, q_rows, d_cache = _draft_propose(
                    draft, dparams, dl[:, 1], d_cache, pc, k_draft,
                    temperature, keys)

                block = jnp.concatenate([cur[:, None], props], axis=1)
                vlogits, t_cache = decode_block(target, tparams, block,
                                                t_cache, lengths=lt)

                if sampling:
                    rng_key, kr, kb = jax.random.split(rng_key, 3)
                    m, corr = _sampling_accept(vlogits, props, q_rows,
                                               temperature, keys[k_draft],
                                               kr, kb)
                else:
                    m, corr = _greedy_accept(vlogits, props)

                ext = jnp.concatenate([props, jnp.zeros((batch, 1),
                                                        jnp.int32)], 1)
                commit = jnp.where(iota_k1[None, :] < m[:, None], ext,
                                   corr[:, None])            # [B, k+1]
                n_commit = m + 1
                idx = jnp.clip(n_out[:, None] + iota_k1[None, :], 0,
                               cap - 1)
                out = out.at[bidx, idx].set(commit)
                prev = jnp.take_along_axis(
                    props, jnp.clip(m - 1, 0, k_draft - 1)[:, None],
                    1)[:, 0]
                y_new = jnp.where(m == 0, cur, prev)
                stats = stats + jnp.stack(
                    [jnp.ones((), jnp.int32),
                     jnp.sum(jnp.where(active, m, 0)),
                     jnp.sum(active.astype(jnp.int32))])
                return (n_out + n_commit, out, corr, y_new, lt + n_commit,
                        pc + n_commit, t_cache, d_cache, rng_key, stats)

            return jax.lax.while_loop(cond, body, carry)

        return run

    return _cached_runner(key_tuple, build)


def _greedy_segment_runner(target: Transformer, cap: int,
                           max_new_tokens: int, temperature: float,
                           cache_dtype: str):
    """Plain-greedy segment over the SAME carry as
    :func:`_spec_segment_runner` — the k=0 arm of adaptive speculation:
    when the controller concludes speculation cannot pay (see
    :func:`optimal_draft_depth` ``allow_disable``), remaining tokens
    decode one-per-round with the target alone.  Draft-side carry fields
    (y, pc, d_cache) pass through untouched (stale but unused)."""
    key_tuple = (_model_key(target), "greedy_segment", cap,
                 max_new_tokens, temperature, cache_dtype)
    sampling = temperature > 0.0

    def build():
        @jax.jit
        def run(tparams, carry, seg_target):
            batch = carry[0].shape[0]
            bidx = jnp.arange(batch, dtype=jnp.int32)

            def cond(carry):
                return jnp.any(carry[0] < seg_target)

            def body(carry):
                (n_out, out, cur, y, lt, pc, t_cache, d_cache, rng_key,
                 stats) = carry
                logits, t_cache = decode_block(target, tparams,
                                               cur[:, None], t_cache,
                                               lengths=lt)
                if sampling:
                    rng_key, kk = jax.random.split(rng_key)
                    nxt = jax.random.categorical(
                        kk, logits[:, 0] / temperature,
                        axis=-1).astype(jnp.int32)
                else:
                    nxt = jnp.argmax(logits[:, 0],
                                     axis=-1).astype(jnp.int32)
                out = out.at[bidx, jnp.clip(n_out, 0, cap - 1)].set(nxt)
                stats = stats + jnp.stack(
                    [jnp.ones((), jnp.int32), jnp.zeros((), jnp.int32),
                     jnp.zeros((), jnp.int32)])
                return (n_out + 1, out, nxt, y, lt + 1, pc, t_cache,
                        d_cache, rng_key, stats)

            return jax.lax.while_loop(cond, body, carry)

        return run

    return _cached_runner(key_tuple, build)


def _spec_catchup_runner(draft: Transformer, gap: int, cache_dtype: str):
    """Advance the DRAFT cache over ``gap`` committed tokens the target
    decoded alone (the greedy calibration probe leaves d_cache/pc/y
    untouched).  The spec round's own catch-up block only rewrites the
    last two positions, so without this a k>0 finish segment after the
    greedy probe would condition the draft on a prefix with a
    ``gap``-token hole.  Feeds the committed tokens at sequence positions
    pc-1 .. lt-2 (out columns n_out-gap-2 ..) through one ragged
    decode_block, then restores the segment invariant: pc = lt, y = the
    token at position lt-1."""
    key = (_model_key(draft), "spec_catchup", gap, cache_dtype)

    def build():
        @jax.jit
        def run(dparams, carry):
            (n_out, out, cur, y, lt, pc, t_cache, d_cache, rng_key,
             stats) = carry
            batch = out.shape[0]
            bidx = jnp.arange(batch, dtype=jnp.int32)[:, None]
            cols = ((n_out - gap - 2)[:, None]
                    + jnp.arange(gap, dtype=jnp.int32)[None, :])
            block = out[bidx, jnp.clip(cols, 0, out.shape[1] - 1)]
            _, d_cache = decode_block(draft, dparams, block, d_cache,
                                      lengths=pc - 1)
            y_new = out[jnp.arange(batch, dtype=jnp.int32),
                        jnp.clip(n_out - 2, 0, out.shape[1] - 1)]
            return (n_out, out, cur, y_new, lt, lt, t_cache, d_cache,
                    rng_key, stats)

        return run

    return _cached_runner(key, build)


# Calibrated depths memoized per (target, draft, sampling, cache) pair:
# the first adaptive call pays a segmented calibration run; every later
# call jumps straight to the winning FUSED program (whole-loop spec at
# k*, or plain generate when speculation cannot pay) — steady-state
# adaptive throughput equals the best fixed configuration by
# construction.  Keys use _model_key (the never-reused cache_token, not a
# recyclable id()).  Params are assumed fixed per model object (true for
# serving and benching); retraining under the same object must call
# :func:`clear_depth_memo`, since the calibrated depth is a property of
# the PARAMS (target/draft agreement), not the architecture.  Bounded
# LRU + lock, same protocol as _RUNNERS.
_DEPTH_MEMO: "OrderedDict[tuple, int]" = OrderedDict()
_DEPTH_MEMO_MAX = 64
_DEPTH_MEMO_LOCK = threading.Lock()


def clear_depth_memo(model=None) -> int:
    """Invalidate memoized calibrated draft depths — all of them, or only
    the entries involving ``model`` (as target OR draft).  Returns the
    number of entries dropped.  Call after swapping params under a model
    object you keep reusing (e.g. reloading a checkpoint mid-process):
    the next adaptive call re-calibrates against the new params."""
    with _DEPTH_MEMO_LOCK:
        if model is None:
            n = len(_DEPTH_MEMO)
            _DEPTH_MEMO.clear()
            return n
        mkey = _model_key(model)
        stale = [k for k in _DEPTH_MEMO if mkey in k[:2]]
        for k in stale:
            del _DEPTH_MEMO[k]
        return len(stale)


def _depth_memo_get(key: tuple) -> int | None:
    with _DEPTH_MEMO_LOCK:
        k = _DEPTH_MEMO.get(key)
        if k is not None:
            _DEPTH_MEMO.move_to_end(key)
        return k


def _depth_memo_put(key: tuple, k: int) -> None:
    with _DEPTH_MEMO_LOCK:
        _DEPTH_MEMO[key] = k
        _DEPTH_MEMO.move_to_end(key)
        while len(_DEPTH_MEMO) > _DEPTH_MEMO_MAX:
            _DEPTH_MEMO.popitem(last=False)


def _speculative_adaptive(target, tparams, draft, dparams, prompt,
                          max_new_tokens: int, k_max: int,
                          temperature: float, seed: int, cache_dtype: str,
                          cost_ratio: float,
                          calibration: str = "measured"
                          ) -> tuple[Array, dict]:
    """Adaptive-depth speculative decoding (see
    :func:`speculative_generate_batched` ``adaptive=True``).

    The generation runs as a handful of on-device SEGMENTS of the
    whole-loop decoder (:func:`_spec_segment_runner` — carry threaded
    through, one compiled program per depth), and between segments the
    controller re-picks the depth k via :func:`optimal_draft_depth`:
    invert the segment's accept fraction to per-proposal agreement p,
    then argmax expected-tokens/round-cost over 1..k_max with the
    caller-measured draft/target ``cost_ratio``.  A fixed k=4 at accept
    0.36 over-speculates; this controller lands on the depth its cost
    model scores best instead, at ~4 host syncs per generation (the
    gain is not measured on the chip).
    Token-exact for greedy at ANY depth sequence."""
    sampling = temperature > 0.0
    if calibration not in ("measured", "model"):
        raise ValueError(f"calibration must be 'measured' or 'model', "
                         f"got {calibration!r}")
    memo_key = (_model_key(target), _model_key(draft), k_max,
                temperature, cache_dtype, cost_ratio, calibration)
    k_known = _depth_memo_get(memo_key)
    if k_known == 0:
        # calibration concluded speculation cannot pay: steady state IS
        # plain fused decoding (token-exact for greedy; for temperature
        # sampling the speculative path preserves the same distribution)
        out = generate(target, tparams, prompt, max_new_tokens,
                       temperature=temperature, rng=seed,
                       cache_dtype=cache_dtype)
        return np.asarray(out), {
            "verify_calls": max_new_tokens,
            "draft_accept_rate": 0.0,
            "tokens_per_target_forward": 1.0,
            "draft_depth": 0, "draft_depths": ["memo"],
        }
    if k_known is not None:
        # steady state at the calibrated depth: one full-length compiled
        # segment (no calibration boundaries, no extra host syncs)
        out, stats = _run_fixed_spec(
            target, tparams, draft, dparams, prompt, max_new_tokens,
            k_known, temperature, seed, cache_dtype)
        stats["draft_depth"] = k_known
        stats["draft_depths"] = ["memo"]
        return out, stats

    # ---- first call for this pair: MEASURED calibration.  Two timed
    # probes on this host — a spec segment at k0 and a greedy segment —
    # decide empirically (wall-clock tokens/sec), with the analytic model
    # only extrapolating the spec rate across depths.  Each probe runs
    # twice from the same carry (pure function): the first run absorbs
    # compilation, the second is the measurement.
    import time as _time

    prompt = jnp.asarray(prompt, jnp.int32)
    batch, s = prompt.shape
    cap = max_new_tokens + k_max + 1
    max_len = s + cap + k_max + 2
    carry = _init_spec_carry(target, tparams, draft, dparams, prompt,
                             cap, max_len, float(temperature), seed,
                             cache_dtype)
    k0 = min(2, k_max)
    seg = max(8, min(24, max_new_tokens // 4))
    t1 = min(max_new_tokens, seg)
    t2 = min(max_new_tokens, 3 * seg)
    spec_runner = _spec_segment_runner(target, draft, cap,
                                       max_new_tokens, k0,
                                       float(temperature), cache_dtype)
    greedy_runner = _greedy_segment_runner(target, cap, max_new_tokens,
                                           float(temperature),
                                           cache_dtype)

    def timed(runner, args, carry, target_n):
        tgt = jnp.asarray(target_n, jnp.int32)
        warm = runner(*args, carry, tgt)
        np.asarray(warm[0])                     # compile + drain
        t0 = _time.perf_counter()
        res = runner(*args, carry, tgt)
        np.asarray(res[0])
        return res, _time.perf_counter() - t0

    tokens_before = int(np.asarray(carry[0], np.int64).sum())
    carry, dt_spec = timed(spec_runner, (tparams, dparams), carry, t1)
    stats1 = np.asarray(carry[9], np.int64)
    spec_tokens = int(np.asarray(carry[0], np.int64).sum()) - tokens_before
    rate_spec = spec_tokens / max(dt_spec, 1e-9)
    frac = float(stats1[1]) / max(1, int(stats1[2]) * k0)
    proposed_total = int(stats1[2]) * k0
    depths: list[int] = [k0]

    p = _invert_accept_fraction(frac, k0)
    rate_greedy = float("nan")
    if calibration == "measured":
        # greedy probe, then extrapolate the measured spec rate across
        # depths with the model's RELATIVE scores and compare measured
        # against measured
        tokens_before = int(np.asarray(carry[0], np.int64).sum())
        carry, dt_greedy = timed(greedy_runner, (tparams,), carry, t2)
        greedy_tokens = (int(np.asarray(carry[0], np.int64).sum())
                         - tokens_before)
        rate_greedy = greedy_tokens / max(dt_greedy, 1e-9)
        depths.append(0)

        def score(j):
            expect = (j + 1.0 if p >= 1.0
                      else (1.0 - p ** (j + 1)) / (1.0 - p))
            return expect / (0.25 + 1.0 + cost_ratio * j)

        best_j = max(range(1, max(1, k_max) + 1), key=score)
        est_best = rate_spec * score(best_j) / score(k0)
        k = best_j if est_best > rate_greedy * 1.02 else 0
    else:
        # "model": deterministic, timing-free decision (tests; hosts
        # where two short probes cannot be timed meaningfully)
        k = optimal_draft_depth(frac, k0, k_max, cost_ratio,
                                allow_disable=True)
    _depth_memo_put(memo_key, k)

    # ---- finish the remaining tokens at the decided configuration
    if k == 0:
        carry = greedy_runner(tparams, carry,
                              jnp.asarray(max_new_tokens, jnp.int32))
        depths.append(0)
    else:
        gap = int(np.asarray(carry[4])[0] - np.asarray(carry[5])[0])
        if gap > 0:
            # measured calibration ran a greedy probe: catch the draft up
            # over the probe's committed tokens before speculating again
            carry = _spec_catchup_runner(draft, gap, cache_dtype)(
                dparams, carry)
        runner = (_spec_segment_runner(target, draft, cap,
                                       max_new_tokens, k,
                                       float(temperature), cache_dtype)
                  if k != k0 else spec_runner)
        pre = np.asarray(carry[9], np.int64)
        carry = runner(tparams, dparams, carry,
                       jnp.asarray(max_new_tokens, jnp.int32))
        post = np.asarray(carry[9], np.int64)
        proposed_total += int(post[2] - pre[2]) * k
        depths.append(k)
    final = np.asarray(carry[9], np.int64)
    verifies, accepted = int(final[0]), int(final[1])
    tokens = np.asarray(carry[1])[:, :max_new_tokens]
    return tokens, {
        "verify_calls": verifies,
        "draft_accept_rate": accepted / max(1, proposed_total),
        "tokens_per_target_forward": tokens.size / max(
            1, batch * (verifies + 1)),
        "draft_depth": k,            # depth the controller settled on
        "draft_depths": depths,      # [probe_k, 0(greedy probe), chosen]
        "calibration": {"rate_spec": rate_spec,
                        "rate_greedy": rate_greedy, "p": p},
    }


def _run_fixed_spec(target, tparams, draft, dparams, prompt,
                    max_new_tokens: int, k: int, temperature: float,
                    seed: int, cache_dtype: str) -> tuple[Array, dict]:
    """One fixed-depth run (shared by the non-adaptive path and the
    adaptive steady state): init the carry, run ONE full-length segment
    of the compiled while_loop, convert stats."""
    prompt = jnp.asarray(prompt, jnp.int32)
    batch, s = prompt.shape
    cap = max_new_tokens + k + 1
    max_len = s + cap + k + 2
    carry = _init_spec_carry(target, tparams, draft, dparams, prompt,
                             cap, max_len, float(temperature), seed,
                             cache_dtype)
    runner = _spec_segment_runner(target, draft, cap, max_new_tokens, k,
                                  float(temperature), cache_dtype)
    carry = runner(tparams, dparams, carry,
                   jnp.asarray(max_new_tokens, jnp.int32))
    verifies, accepted, active_rows = (
        int(x) for x in np.asarray(carry[9]))
    return np.asarray(carry[1])[:, :max_new_tokens], {
        "verify_calls": verifies,
        "draft_accept_rate": accepted / max(1, active_rows * k),
        # +1: the prefill forward produced each row's first token
        "tokens_per_target_forward": batch * max_new_tokens / max(
            1, batch * (verifies + 1)),
    }


def speculative_generate_batched(
        target: Transformer, target_params, draft: Transformer,
        draft_params, prompt: Array, max_new_tokens: int, *,
        draft_len: int = 4, temperature: float = 0.0,
        seed: int = 0, cache_dtype: str = "native",
        adaptive: bool = False, draft_cost_ratio: float = 0.5,
        calibration: str = "measured") -> tuple[Array, dict]:
    """Batched speculative decoding with the WHOLE loop on device.

    Unlike :func:`speculative_generate` (batch-1, host accept loop — kept
    as the readable reference implementation its tests cross-check), this
    runs prefill + a ``lax.while_loop`` of draft-propose / verify /
    vectorized accept-or-resample inside one jit: no per-token host
    round-trips, so decode throughput is device-bound — the serving path.

    Batch > 1 works because rows accept DIFFERENT numbers of draft tokens
    per round: each row's KV caches advance at their own rate via ragged
    ``decode_block`` (per-row lengths), committed tokens scatter into a
    per-row output frontier, and rows that reach ``max_new_tokens`` keep
    verifying into slack slots until the slowest row finishes (their
    stats are masked out).

    ``temperature=0`` is greedy and token-exact vs target-alone greedy
    decoding (tested per row); ``temperature>0`` applies the
    Leviathan/Chen rejection rule vectorized on device, preserving the
    target's sampling distribution exactly (tested empirically).

    ``cache_dtype="int8"`` stores BOTH models' KV caches quantized
    (QuantKVCache; the ragged per-row scatter paths quantize on write) —
    K/V depend only on (token, position, params), so block-verify and
    single-step writes quantize identically and the greedy token-exactness
    vs an int8-cache target-alone decode is preserved (tested).

    Returns (tokens [B, max_new_tokens], stats).
    """
    if target.config.vocab != draft.config.vocab:
        raise ValueError(
            f"vocab mismatch: target {target.config.vocab} vs draft "
            f"{draft.config.vocab}")
    if draft_len < 1:
        raise ValueError("draft_len must be >= 1")
    prompt_len = int(np.asarray(prompt).shape[1])
    # + draft_len: the last verify round may write a full draft block
    # before the loop notices every row is done (active lanes only —
    # finished rows clip into discarded slack)
    check_rolls_back(target)
    check_rolls_back(draft)
    check_position_budget(target, prompt_len, max_new_tokens + draft_len)
    check_position_budget(draft, prompt_len, max_new_tokens + draft_len)
    if adaptive:
        # draft_len becomes the depth CAP; the controller re-picks k
        # between on-device segments from the measured accept rate and
        # the caller's draft/target cost ratio (_speculative_adaptive)
        return _speculative_adaptive(
            target, target_params, draft, draft_params, prompt,
            max_new_tokens, draft_len, float(temperature), seed,
            cache_dtype, float(draft_cost_ratio), calibration)
    return _run_fixed_spec(target, target_params, draft, draft_params,
                           prompt, max_new_tokens, draft_len,
                           float(temperature), seed, cache_dtype)


def generate(model: Transformer, params: Mapping[str, Array],
             prompt: Array, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             rng: Array | int = 0, cache_dtype: str = "native") -> Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` [B, S] int32.
    Returns [B, max_new_tokens].  Prefill and the whole decode scan are
    jitted with static shapes; the compiled runner is cached per
    (model, max_new_tokens, temperature, top_k, top_p, cache_dtype), so
    repeated calls with the same shapes do not retrace.
    ``cache_dtype="int8"`` stores the KV cache quantized (QuantKVCache) —
    composes with a models/quant.py weight-quantized ``params`` for the
    fully int8-bandwidth serving path."""
    check_position_budget(model, int(prompt.shape[1]), max_new_tokens)
    if isinstance(rng, int):
        rng = jax.random.key(rng)
    return _runner(model, max_new_tokens, temperature, top_k, top_p,
                   cache_dtype)(params, prompt, rng)
