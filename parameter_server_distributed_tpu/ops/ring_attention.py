"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Long-context training shards the sequence axis across the mesh's ``seq``
axis.  Causal attention then needs cross-device K/V:

- **Ring attention** (`make_ring_attention`): K/V blocks rotate around the
  ring via `ppermute` while each device accumulates its queries' output
  with an online (flash-style) softmax — O(seq/N) activation memory per
  device and compute overlapped with ICI transfers.  The blockwise-
  parallel-transformer / ring-attention construction, in shard_map.
- **Ulysses all-to-all** (`make_ulysses_attention`): `all_to_all` swaps the
  sharded axis from sequence to heads, each device runs causal attention
  on the full sequence for its head subset (by the arm its shapes take,
  `models.transformer.device_arm`), then swaps back.  Cheaper at moderate
  sequence lengths, needs heads % seq_axis == 0.

Both return an ``attention_fn(q, k, v) -> out`` with the same signature as
`models.transformer.causal_attention` ([B, S, H, D] -> [B, S, H, D]), so the
Transformer takes them as drop-in `attention_fn`.  K/V may arrive with the
GQA kv_heads-sized head axis: the ring rotates and Ulysses all-to-alls the
SMALL unexpanded tensors (n_heads/kv_heads fewer bytes on ICI) and expands
only at the math.  There is no reference analogue — the reference has no
model, no sequence axis (SURVEY.md §5); this is required TPU-native scale
capability.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # avoid true -inf: exp/where arithmetic stays NaN-free


def _prepare_gqa_kv(q, k, v, n_tp: int):
    """models.transformer.prepare_gqa_kv, imported lazily (the transformer
    module is the single home for the GQA-vs-tensor-axis rule)."""
    from ..models.transformer import prepare_gqa_kv

    return prepare_gqa_kv(q, k, v, n_tp)


def _block_attention_update(q32, k_blk, v_blk, q_pos, k_pos, m, l, acc):
    """One online-softmax accumulation step over a K/V block.

    q32 [B,H,Sq,D] f32; k_blk/v_blk [B,Sk,H,D] or the GQA [B,Sk,KV,D]
    (expanded here — the ring rotates the small unexpanded tensors);
    m,l [B,H,Sq]; acc [B,H,Sq,D].
    """
    d = q32.shape[-1]
    groups = q32.shape[1] // k_blk.shape[2]
    if groups > 1:
        k_blk = jnp.repeat(k_blk, groups, axis=2)
        v_blk = jnp.repeat(v_blk, groups, axis=2)
    k32 = k_blk.astype(jnp.float32)
    v32 = v_blk.astype(jnp.float32)
    scores = jnp.einsum("bhqd,bkhd->bhqk", q32, k32) / math.sqrt(d)
    mask = q_pos[:, None] >= k_pos[None, :]           # causal [Sq, Sk]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    s_max = jnp.max(scores, axis=-1)                   # [B,H,Sq]
    m_new = jnp.maximum(m, s_max)
    # rows with no visible keys yet keep m == NEG_INF; exp underflows to 0
    p = jnp.exp(scores - m_new[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    alpha = jnp.exp(m - m_new)                         # [B,H,Sq]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v32)
    return m_new, l_new, acc_new


def _finalize(acc, l):
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B,H,Sq,D]
    return jnp.transpose(out, (0, 2, 1, 3))            # -> [B,Sq,H,D]


def make_ring_attention(mesh: Mesh, seq_axis: str = "seq",
                        batch_axes: tuple[str, ...] = ("data", "fsdp"),
                        head_axis: str = "tensor"):
    """Causal ring attention over ``mesh``'s sequence axis."""
    n = mesh.shape[seq_axis]
    heads_spec = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    spec = P(batch_axes, seq_axis, heads_spec, None)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # remat each block update: the [B,H,Sq,Sk] score tile is recomputed in
    # the backward pass instead of saved — per-step backward residuals
    # shrink to the O(Sq*D) carries, the whole point of ring attention's
    # O(S/N) activation-memory claim at long context
    block_update = jax.checkpoint(_block_attention_update)
    n_tp = mesh.shape.get(head_axis, 1)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def ring(q, k, v):
        b, s_loc, h, d = q.shape
        my = jax.lax.axis_index(seq_axis)
        q32 = jnp.transpose(q.astype(jnp.float32), (0, 2, 1, 3))  # [B,H,Sq,D]
        q_pos = my * s_loc + jnp.arange(s_loc)
        m = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, s_loc), jnp.float32)
        acc = jnp.zeros((b, h, s_loc, d), jnp.float32)
        k_cur, v_cur = k, v
        for step in range(n):
            src = (my - step) % n                      # origin of k_cur block
            k_pos = src * s_loc + jnp.arange(s_loc)
            # blocks from future shards (src > my) are entirely above the
            # causal diagonal: skip their update (the rotation must still
            # happen so later steps see the right block).  Saves ~half the
            # attention FLOPs across the ring for causal LM training.
            m, l, acc = jax.lax.cond(
                src <= my,
                lambda ops: block_update(q32, *ops, q_pos, k_pos,
                                         m, l, acc),
                lambda ops: (m, l, acc),
                (k_cur, v_cur))
            if step < n - 1:
                k_cur = jax.lax.ppermute(k_cur, seq_axis, perm)
                v_cur = jax.lax.ppermute(v_cur, seq_axis, perm)
        return _finalize(acc, l).astype(q.dtype)

    def ring_gqa(q, k, v):
        k, v = _prepare_gqa_kv(q, k, v, n_tp)
        return ring(q, k, v)

    return ring_gqa


def make_ulysses_attention(mesh: Mesh, seq_axis: str = "seq",
                           batch_axes: tuple[str, ...] = ("data", "fsdp"),
                           head_axis: str = "tensor"):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism: swap the
    sharded axis seq -> heads, run causal attention over the full
    sequence, swap back.  Heads (after any tensor sharding) must divide by
    the seq-axis size.

    After the gather each device holds [B, S, H/n, D] at aligned positions
    and attends them as any device attends whole sequences
    (``models/transformer.device_arm``): the blockwise kernel where
    the shape fits on a TPU, blockwise in plain XLA for a long sequence
    elsewhere, the einsum for a short one."""
    from ..models.transformer import attend_by, device_arm, expand_gqa

    n = mesh.shape[seq_axis]
    n_tp = mesh.shape.get(head_axis, 1)
    heads_spec = head_axis if n_tp > 1 else None
    spec = P(batch_axes, seq_axis, heads_spec, None)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def ulysses(q, k, v):
        def gather_seq(x):  # [B, S/n, H, D] -> [B, S, H/n, D]
            return jax.lax.all_to_all(x, seq_axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def scatter_seq(x):  # [B, S, H/n, D] -> [B, S/n, H, D]
            return jax.lax.all_to_all(x, seq_axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        # GQA: all-to-all the small kv_heads-sized K/V when kv_heads
        # divides the seq axis (groups/n fewer bytes on the wire) and let
        # the device's attention group them; otherwise expand first
        # (correct for any head count, at the expanded-transfer cost)
        if k.shape[2] % n:
            k, v = expand_gqa(q, k, v)
        q, k, v = gather_seq(q), gather_seq(k), gather_seq(v)
        return scatter_seq(attend_by(device_arm(q.shape, k.shape), q, k, v))

    def ulysses_gqa(q, k, v):
        k, v = _prepare_gqa_kv(q, k, v, n_tp)
        return ulysses(q, k, v)

    return ulysses_gqa
