"""Block-selected sparse attention (MiniCPM4's InfLLM v2: arXiv:2506.07900
section 2.2, arXiv:2509.24663) over grouped K/V heads, in plain XLA.

A query with a context of n keys attends densely while n < ``dense_len``.
From there on it attends a SELECTION of key blocks of ``block`` positions,
one selection per K/V head, shared by the head's group of query heads:

1. compressed keys c_i = mean(k[stride i : stride i + kernel]), visible
   once the kernel is complete (stride i + kernel <= n);
2. per query head p = softmax_i(q . c_i / sqrt(D)); P = sum over the group;
3. a block's score is the largest P_i among the kernels that overlap it;
4. selected: the first ``init_blocks`` blocks, the ``window / block``
   blocks ending at the query's own, and the ``topk`` highest-scoring of
   the rest (ties to the lower block);
5. softmax attention over the causal positions of the selected blocks.

K, V and the compressed keys arrive BY HEAD, [B, KV, M, D] (positions on
axis 2): a block of one head is then one contiguous [block, D] piece, which
a gather can fetch; with the heads side by side in a row (the layout of a
full layer's cache part) one head's lanes alone cost a copy of the whole
part (read from the round compiled for a v5e, PERF.md PR 32).

Two forms share steps 1-4 (:func:`select`): a decode round's single query
a slot GATHERS its blocks from the cache part where it lies
(:func:`sparse_decode_attention`); a block of queries (a whole sequence,
an extension of a cached prefix) runs blockwise with the selection as a
block MASK, a block of scores at a time (:func:`sparse_blockwise_attention`).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    kernel: int = 32
    stride: int = 16
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if (self.kernel % self.stride or self.block % self.stride
                or self.window % self.block or self.dense_len % self.block):
            raise ValueError(
                f"{self}: kernel and block are multiples of stride, window "
                "and dense_len multiples of block")
        if self.dense_len < self.window + self.init_blocks * self.block:
            raise ValueError(
                f"{self}: selection starts past the initial blocks and the "
                "window (dense_len >= window + init_blocks * block)")

    @property
    def window_blocks(self) -> int:
        return self.window // self.block

    @property
    def n_selected(self) -> int:
        """Blocks a selecting query attends: 97 as published."""
        return self.init_blocks + self.window_blocks + self.topk

    @property
    def n_gathered(self) -> int:
        """Block places a decode round gathers a slot: enough for a
        selection and for a whole context under ``dense_len``."""
        return max(self.n_selected, self.dense_len // self.block)


def compress_keys(k: Array, spec: SparseSpec, axis: int = 2) -> Array:
    """Every compressed key of k (positions on ``axis``, M of them): the
    same shape with ceil(M / stride) places there, in k's dtype, index i
    the mean of positions stride i .. stride i + kernel - 1 (short by what
    lies past M: such a kernel is not complete, and its readers hide it)."""
    m = k.shape[axis]
    pad = -m % spec.stride

    def padded(x, before, after):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (before, after)
        return jnp.pad(x, widths)

    if pad:
        k = padded(k, 0, pad)
    n = (m + pad) // spec.stride
    sums = k.reshape(k.shape[:axis] + (n, spec.stride) + k.shape[axis + 1:]
                     ).astype(jnp.float32).sum(axis=axis + 1)
    total = sums
    for j in range(1, spec.kernel // spec.stride):
        total = total + padded(
            jax.lax.slice_in_dim(sums, j, n, axis=axis), 0, j)
    return (total / spec.kernel).astype(k.dtype)


def completed_key(k_part: Array, lengths: Array, spec: SparseSpec
                  ) -> tuple[Array, Array]:
    """The compressed key that a context of ``lengths`` [B] positions has
    just completed, from the part k_part [B, KV, M, D] that holds them:
    (its index [B], past the last where none was completed; the key
    [B, KV, D] in float32)."""
    first = lengths - spec.kernel
    done = (first >= 0) & (first % spec.stride == 0)
    # (rows by index, as the round's write reaches them: a slice a row
    # makes the compiler turn the whole part around)
    held = k_part[jnp.arange(k_part.shape[0])[:, None, None],
                  jnp.arange(k_part.shape[1])[None, :, None],
                  (jnp.maximum(first, 0)[:, None]
                   + jnp.arange(spec.kernel))[:, None, :]]
    index = jnp.where(done, first // spec.stride,
                      k_part.shape[2] // spec.stride + 1)
    return index, held.astype(jnp.float32).mean(axis=2)     # [B, KV, D]


def _block_scores(q: Array, ck: Array, q_pos: Array, spec: SparseSpec
                  ) -> tuple[Array, Array]:
    """Steps 2 and 3.  q [B, T, KV, G, D] at positions q_pos [B, T]; ck
    [B, KV, NK, D].  Returns (block scores [B, KV, T, NB] float32, zero
    where no visible kernel overlaps; kernels visible [B, T])."""
    per_block = spec.block // spec.stride
    reach = spec.kernel // spec.stride - 1    # kernels reaching in from before
    nk = ck.shape[2]
    pad = -nk % per_block
    if pad:
        ck = jnp.pad(ck, ((0, 0), (0, 0), (0, pad), (0, 0)))
        nk += pad
    scores = jnp.einsum("btkgd,bknd->bkgtn", q, ck,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    ends = jnp.arange(nk) * spec.stride + spec.kernel
    visible = ends[None, None, :] <= (q_pos + 1)[:, :, None]   # [B, T, NK]
    scores = jnp.where(visible[:, None, None], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    weight = jnp.exp(scores - jnp.where(jnp.isneginf(top), 0.0, top))
    prob = weight / jnp.maximum(weight.sum(axis=-1, keepdims=True), 1e-30)
    prob = prob.sum(axis=2)                                    # [B, KV, T, NK]
    nb = nk // per_block
    block = prob.reshape(*prob.shape[:-1], nb, per_block).max(axis=-1)
    for j in range(1, reach + 1):
        # kernel per_block * b - j starts before block b and ends inside it
        before = jnp.pad(prob, ((0, 0),) * 3 + ((j, 0),))[
            ..., 0:nk:per_block]
        block = jnp.maximum(block, before)
    return block, visible.sum(axis=-1)


def _causal_and_forced(own: Array, nb: int, spec: SparseSpec
                       ) -> tuple[Array, Array]:
    """([B, T, nb] the blocks up to each query's own, and of those the
    ones every selecting query attends: the initial blocks and the
    window's)."""
    blocks = jnp.arange(nb)
    causal = blocks[None, None, :] <= own[:, :, None]
    forced = (blocks < spec.init_blocks)[None, None, :] | (
        causal & (blocks[None, None, :]
                  > (own - spec.window_blocks)[:, :, None]))
    return causal, forced


def select(q: Array, ck: Array, q_pos: Array, spec: SparseSpec):
    """Steps 2 to 4 for q [B, T, KV, G, D] at positions q_pos [B, T]
    against compressed keys ck [B, KV, NK, D].  Returns (chosen: the
    ``topk`` block indices [B, KV, T, topk] beside the forced ones, valid:
    which of them are real choices, own: the query's own block [B, T],
    dense: the query attends its whole context [B, T], scores
    [B, KV, T, NB], kernels visible [B, T])."""
    with jax.named_scope("select"):
        scores, kernels = _block_scores(q, ck, q_pos, spec)
        own = q_pos // spec.block
        causal, forced = _causal_and_forced(own, scores.shape[-1], spec)
        candidates = (causal & ~forced)[:, None]
        value, chosen = jax.lax.top_k(
            jnp.where(candidates, scores, -1.0),
            min(spec.topk, scores.shape[-1]))
        return (chosen, value >= 0.0, own, q_pos + 1 < spec.dense_len,
                scores, kernels)


def selection_mask(chosen: Array, valid: Array, own: Array, dense: Array,
                   nb: int, spec: SparseSpec) -> Array:
    """[B, KV, T, nb]: the blocks each query attends."""
    causal, forced = _causal_and_forced(own, nb, spec)
    picked = jnp.any((chosen[..., None] == jnp.arange(nb))
                     & valid[..., None], axis=-2)              # [B,KV,T,nb]
    return jnp.where(dense[:, None, :, None], causal[:, None],
                     forced[:, None] | picked)


def sparse_decode_attention(q: Array, k: Array, v: Array, ck: Array,
                            q_pos: Array, spec: SparseSpec
                            ) -> tuple[Array, Array]:
    """One query a slot.  q [B, 1, H, D] at positions q_pos [B]; k / v
    [B, KV, M, D] hold position j at index j (the query's own included);
    ck [B, KV, M / stride, D] the compressed keys.  A slot under
    ``dense_len`` attends its whole context, one past it its selection, in
    one program: both gather ``n_gathered`` block places (every block,
    where the part holds fewer), and the places a slot does not use are
    hidden.  Returns (attn [B, 1, H, D] in q's dtype, [positions attended,
    kernels scored] summed over the slots, float32)."""
    batch, _, heads, dim = q.shape
    kv_heads, m = k.shape[1], k.shape[2]
    if m % spec.block:
        raise ValueError(f"a cache of {m} positions does not divide into "
                         f"blocks of {spec.block}")
    nb = m // spec.block
    places = spec.n_gathered if m >= spec.dense_len else nb
    groups = heads // kv_heads
    qg = q.reshape(batch, 1, kv_heads, groups, dim)
    whole = jnp.arange(places)
    with jax.named_scope("attn"), jax.named_scope("sparse"):
        own = q_pos // spec.block                              # [B]
        if m < spec.dense_len:
            # no slot of this part can select: no scores, no kernels
            place = jnp.broadcast_to(whole, (batch, kv_heads, places))
            place_ok = jnp.broadcast_to((whole[None, :] <= own[:, None])
                                        [:, None], place.shape)
            kernels = jnp.zeros((), jnp.float32)
        else:
            chosen, valid, _, dense, _, kernels = select(
                qg, ck, q_pos[:, None], spec)
            with jax.named_scope("select"):
                chosen, valid = chosen[:, :, 0], valid[:, :, 0]  # [B,KV,topk]
                dense = dense[:, 0]
                near = own[:, None] - spec.window_blocks + 1 + jnp.arange(
                    spec.window_blocks)                        # [B, W]
                forced = jnp.concatenate([
                    jnp.broadcast_to(jnp.arange(spec.init_blocks),
                                     (batch, spec.init_blocks)), near], 1)
                forced_ok = jnp.concatenate([
                    jnp.ones((batch, spec.init_blocks), jnp.bool_),
                    near >= spec.init_blocks], axis=1)
                shape = (batch, kv_heads, forced.shape[1])
                picked = jnp.concatenate(
                    [jnp.broadcast_to(forced[:, None], shape), chosen], -1)
                picked_ok = jnp.concatenate(
                    [jnp.broadcast_to(forced_ok[:, None], shape), valid], -1)
                spare = places - picked.shape[-1]
                picked, picked_ok = (
                    jnp.pad(x, ((0, 0), (0, 0), (0, spare)))
                    for x in (picked, picked_ok))
                place = jnp.where(dense[:, None, None], whole, picked)
                place_ok = jnp.where(
                    dense[:, None, None],
                    (whole[None, :] <= own[:, None])[:, None], picked_ok)
                place = jnp.clip(jnp.where(place_ok, place, 0), 0, nb - 1)
        with jax.named_scope("attend"):
            rows = jnp.arange(batch)[:, None, None]
            head = jnp.arange(kv_heads)[None, :, None]

            def gathered(x):            # [B, KV, places, block, D]
                return x.reshape(batch, kv_heads, nb, spec.block, dim)[
                    rows, head, place]

            at = place[..., None] * spec.block + jnp.arange(spec.block)
            seen = place_ok[..., None] & (at <= q_pos[:, None, None, None])
            scores = jnp.einsum("bkgd,bksjd->bkgsj", qg[:, 0], gathered(k),
                                preferred_element_type=jnp.float32)
            scores = jnp.where(seen[:, :, None],
                               scores / math.sqrt(dim), -jnp.inf)
            flat = scores.reshape(batch, kv_heads, groups, -1)
            probs = jax.nn.softmax(flat, axis=-1).reshape(scores.shape)
            out = jnp.einsum("bkgsj,bksjd->bkgd", probs.astype(v.dtype),
                             gathered(v), preferred_element_type=jnp.float32)
            counted = jnp.stack([
                seen.sum().astype(jnp.float32) / kv_heads,
                kernels.sum().astype(jnp.float32)])
    return out.reshape(batch, 1, heads, dim).astype(q.dtype), counted


def sparse_blockwise_attention(q: Array, k: Array, v: Array, ck: Array,
                               starts: Array, spec: SparseSpec, *,
                               block_q: int = 512, block_k: int = 512,
                               with_mask: bool = False):
    """A block of queries.  q [B, T, H, D] at positions starts[b] ..
    starts[b] + T - 1; k / v [B, KV, M, D] hold position j at index j; ck
    [B, KV, >= M / stride, D] their compressed keys (not read where
    M < ``dense_len``: no query can select, and none pays for selection).
    Query blocks run one after another; each selects its key blocks (steps
    1-4) and then meets the key positions ``block_k`` at a time under the
    selection as a mask, up to the last one any of its queries sees, so the
    memory is one block of scores.  Returns attn [B, T, H, D] in q's dtype,
    and with ``with_mask`` also the selection [B, KV, T, NB] (which blocks
    each query attended)."""
    batch, t, heads, dim = q.shape
    kv_heads = k.shape[1]
    groups = heads // kv_heads
    selects = k.shape[2] >= spec.dense_len
    block_q = min(block_q, t)
    block_k = max(spec.block, block_k // spec.block * spec.block)
    pad_q = -t % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    pad_k = -k.shape[2] % block_k
    if pad_k:
        k, v = (jnp.pad(x, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
                for x in (k, v))
    nq, nk = (t + pad_q) // block_q, k.shape[2] // block_k
    nb, per_step = k.shape[2] // spec.block, block_k // spec.block
    ck = ck[:, :, :k.shape[2] // spec.stride]
    qg = q.reshape(batch, nq, block_q, kv_heads, groups, dim)
    offsets = jnp.arange(block_q, dtype=jnp.int32)
    k_offsets = jnp.arange(block_k, dtype=jnp.int32)
    scale = 1.0 / math.sqrt(dim)

    def query_block(args):
        qi, q_blk = args                                 # [B, bq, KV, G, D]
        q_pos = starts[:, None] + qi * block_q + offsets            # [B, bq]
        if selects:
            chosen, valid, own, dense, scores, _ = select(q_blk, ck, q_pos,
                                                          spec)
            with jax.named_scope("select"):
                mask = selection_mask(chosen, valid, own, dense,
                                      scores.shape[-1], spec)
                mask = jnp.pad(mask[..., :nb], ((0, 0),) * 3
                               + ((0, max(0, nb - mask.shape[-1])),))
        else:
            mask = jnp.broadcast_to(
                (jnp.arange(nb)[None, None, :]
                 <= (q_pos // spec.block)[:, :, None])[:, None],
                (batch, kv_heads, block_q, nb))
        last = jnp.max(q_pos)

        def key_block(carry, kb):
            def update(carry):
                acc, top, denom = carry
                begin = kb * block_k
                k_j = jax.lax.dynamic_slice_in_dim(k, begin, block_k, 2)
                v_j = jax.lax.dynamic_slice_in_dim(v, begin, block_k, 2)
                scores = jnp.einsum(
                    "bqkgd,bkjd->bkgqj", q_blk, k_j,
                    preferred_element_type=jnp.float32) * scale
                allowed = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    mask, kb * per_step, per_step, 3), spec.block, axis=-1)
                seen = allowed & ((begin + k_offsets)[None, None, None, :]
                                  <= q_pos[:, None, :, None])
                scores = jnp.where(seen[:, :, None], scores, -jnp.inf)
                new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
                shift = jnp.where(jnp.isneginf(new_top), 0.0, new_top)
                alpha = jnp.exp(top - shift)
                p = jnp.exp(scores - shift[..., None])
                pv = jnp.einsum("bkgqj,bkjd->bkgqd", p.astype(v.dtype), v_j,
                                preferred_element_type=jnp.float32)
                return (acc * alpha[..., None] + pv, new_top,
                        denom * alpha + jnp.sum(p, axis=-1))

            return jax.lax.cond(kb * block_k > last, lambda c: c, update,
                                carry), None

        with jax.named_scope("attend"):
            init = (jnp.zeros((batch, kv_heads, groups, block_q, dim),
                              jnp.float32),
                    jnp.full((batch, kv_heads, groups, block_q), -jnp.inf,
                             jnp.float32),
                    jnp.zeros((batch, kv_heads, groups, block_q),
                              jnp.float32))
            (acc, _, denom), _ = jax.lax.scan(
                key_block, init, jnp.arange(nk, dtype=jnp.int32))
            out = acc / jnp.maximum(denom[..., None], 1e-30)
            out = jnp.moveaxis(out, 3, 1).astype(q.dtype)  # [B,bq,KV,G,D]
        return (out, mask) if with_mask else out

    with jax.named_scope("attn"), jax.named_scope("sparse"):
        blocks = jax.lax.map(query_block, (jnp.arange(nq, dtype=jnp.int32),
                                           jnp.moveaxis(qg, 1, 0)))
    out, mask = blocks if with_mask else (blocks, None)
    out = jnp.moveaxis(out, 0, 1).reshape(batch, nq * block_q, heads, dim)
    if not with_mask:
        return out[:, :t]
    # [nq, B, KV, bq, NB] -> [B, KV, T, NB]
    mask = jnp.moveaxis(mask, 0, 2).reshape(batch, kv_heads, nq * block_q, nb)
    return out[:, :t], mask[:, :, :t]
