"""Accelerator-resident apply support (ISSUE 11): env gate, the exact
kernel library, dequantize-on-device, and the device fold.

``PSDT_DEVICE_APPLY=1`` moves the PS barrier close off host numpy: fold
chunks land as jax Arrays (quantized payloads dequantize ON DEVICE — the
EQuARX direction, arXiv:2506.17615 — so int8 wire bytes cross the host
boundary at a quarter of the f32 volume), the accumulator holds device
sums, and the striped optimizer apply runs as jit-compiled device
programs per stripe (async_sgd/device_optimizer.ShardedDeviceOptimizer,
per "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training", arXiv:2004.13336).  Default OFF: every existing path is
byte-identical with the flag unset.

Bit-exactness contract (the numpy path is the oracle): XLA:CPU's LLVM
backend CONTRACTS an ``fmul`` feeding an ``fadd``/``fsub`` in the same
fused kernel into an FMA (under the emitter's instruction flags), which
differs from numpy's separately-rounded mul-then-add by 1 ulp — and
every HLO-level fence we tried (``optimization_barrier``, identity
``reduce_precision``) is either deleted by the CPU pipeline or emitted
as a no-op.  Ops in separate executables materialize their results and
are correctly rounded exactly like numpy ufuncs.  So the kernel library
below fuses AROUND that one hazard: a jit program may chain any mix of
mul/div/sqrt/compare/select ops, and may contain add/sub — but never an
add/sub consuming a product formed in the SAME program.  Under that
rule every op in a fused stage is individually correctly rounded, so a
stage is bit-identical to the equivalent numpy ufunc sequence while
sweeping memory once instead of once per op — the device apply runs
FEWER memory passes than the numpy path it reproduces bit for bit
(proven by tests/test_device_apply.py).

Dequant kernels are bit-compatible with the C++ host path by
construction: ``dequant_int8`` computes ``q.astype(f32) * scale`` — the
same two exact operations as ``native/psdt_native.cpp::psdt_dequant_int8``
and the numpy oracle in rpc/codec.py — and the top-k scatter writes the
identical bf16-upcast values at the identical indices.

Recompilation bound: kernels are elementwise over the tensor's natural
shape, so the compile count is O(distinct tensor shapes × stages per
rule) per process — a fixed, model-sized set; stripe partitioning never
introduces new shapes (a stripe is a subset of whole tensors).
"""

from __future__ import annotations

import os
from typing import Mapping

import numpy as np

ENV_DEVICE_APPLY = "PSDT_DEVICE_APPLY"


def enabled() -> bool:
    """The per-process selection knob.  Default off: the reference
    protocol, wire bytes, and every existing test see zero change."""
    return os.environ.get(ENV_DEVICE_APPLY, "") not in ("", "0")


_available: bool | None = None


def available(refresh: bool = False) -> bool:
    """True when the jax backend owns at least one device.  Cached: the
    check can cost a backend initialization.  Only code that was ASKED
    for a device path calls this (a ``device_*``/``pallas_*``/
    ``sharded_*`` optimizer, ``PSDT_DEVICE_APPLY``, ``PSDT_ARENA``), so
    a backend that fails to come up raises here, at PS start, instead
    of turning into a silent host path."""
    global _available
    if _available is None or refresh:
        import jax

        _available = len(jax.devices()) > 0
    return _available


def wants_device_fold(optimizer) -> bool:
    """True when the optimizer is device-resident (the sharded device
    family): its apply consumes jax Arrays natively, so folds should
    accumulate on device instead of round-tripping through numpy."""
    return bool(getattr(optimizer, "device_resident", False))


# Mean-tensor-size bound (bytes) under which the device apply/scale is
# dispatched stripe-parallel.  Small kernels are DISPATCH-bound: one
# python thread can't feed XLA fast enough, so a second dispatcher
# nearly doubles throughput.  Large kernels are BANDWIDTH-bound: the
# runtime data-parallelizes each sweep across the intra-op pool, and a
# second dispatcher only contends with it (both regimes seen on a CPU
# host; not measured on the chip).
ENV_STRIPE_DISPATCH_MAX = "PSDT_DEVICE_STRIPE_DISPATCH_MAX"


def stripe_dispatch(store: Mapping) -> bool:
    """True when a striped device close should fan dispatch across the
    stripe executor rather than issuing from the closing thread."""
    if not store:
        return False
    bound = int(os.environ.get(ENV_STRIPE_DISPATCH_MAX, str(16 << 20)))
    total = sum(getattr(v, "nbytes", 0) for v in store.values())
    return total // len(store) < bound


# Elements per sub-chunk of an arena stage program (0 = whole-slab
# stages, the default).  When set, the fused per-stripe update sweep
# runs as ceil(size/chunk) independent [lo, hi) range programs instead
# of one slab-sized program — the intra-host parallelization hook for
# one stripe-slice's sweep (every stage is elementwise, so the chunked
# program is bit-identical to the unchunked one; pinned by
# tests/test_sharded_update.py).  The same per-range programs are what
# the cross-replica sharded update runs over its owned slices.
ENV_STAGE_CHUNK = "PSDT_DEVICE_STAGE_CHUNK"


def stage_chunk_elems() -> int:
    """Arena stage sub-chunk size in ELEMENTS (0 = off)."""
    try:
        return max(0, int(os.environ.get(ENV_STAGE_CHUNK, "0")))
    except ValueError:
        return 0


# --------------------------------------------------------------- kernels
# One lazily-compiled jit program per stage name (jax caches compiled
# code per operand shape).  Donating variants are used ONLY on
# exclusively-owned temporaries and retired optimizer slot buffers;
# gradients and parameters are never donated (ps_core keeps serving
# previously-returned param dicts, and a failed close puts the
# accumulator back for retry).  Every stage obeys the no-product-into-
# add/sub-in-the-same-program rule from the module docstring — that is
# what makes each one bit-identical to its numpy ufunc sequence.
#
# SCRATCH RECYCLING (the device analogue of optimizer.py's retained
# thread-local scratch): a fresh store-sized XLA output above glibc's
# mmap threshold is mmap'd and munmap'd every close — thousands of page
# faults per 32 MB tensor, which is exactly where the host path's
# retained scratch wins.  jax's only buffer-reuse mechanism is
# donation, so stages whose outputs are short-lived intermediates take
# a RETAINED per-tensor scratch buffer as a donated operand and wrap
# the result as ``where(pred, scr, expr)`` with a RUNTIME-false pred:
# bitwise the expr (select never alters the taken branch and never
# fuses a product into an add), while XLA aliases the donated scratch
# buffer to the output — the sweep lands in place, and the caller
# stashes the output back as next close's scratch.  The one
# deliberately fresh buffer per tensor per close is the final update,
# whose buffer the last stage's donation turns into the new params.

_kernels: dict[str, object] = {}


def _build_kernel(name: str):
    import jax
    import jax.numpy as jnp

    # ---- single-op kernels (folds, casts, oracles) ----
    if name == "add_d0":
        return jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    if name == "mul_d0":
        return jax.jit(lambda a, b: a * b, donate_argnums=(0,))
    if name == "cast_f32":
        return jax.jit(lambda a: a.astype(jnp.float32))
    if name == "dequant_int8":
        # q * scale, both f32 — the exact arithmetic of
        # psdt_native.cpp::psdt_dequant_int8 and the numpy oracle
        return jax.jit(lambda q, scale: q.astype(jnp.float32) * scale)
    # ---- fused update stages (ShardedDeviceOptimizer) ----
    # Every stage is BATCHED over a shard's tensor list (the ISSUE's
    # "per-stripe compiled programs"): lists are pytrees, so one jit
    # object serves every stripe, recompiling once per distinct
    # shape-signature — shape-bucketed by construction, and a whole
    # shard's stage runs as ONE dispatch whose per-tensor sweeps execute
    # back to back inside the runtime instead of paying per-tensor
    # python dispatch.  Per-tensor arithmetic is untouched (no
    # cross-tensor op exists), so batching cannot change rounding.
    if name == "b_psub":
        # out = p - u: the one sub, alone (u is a materialized product);
        # u's donated buffer leaves the close as the new params
        return jax.jit(lambda ps, us: [p - u for p, u in zip(ps, us)],
                       donate_argnums=(1,))
    if name == "b_mul":
        # fresh products (sgd's u = g*lr, momentum's seed step): the
        # deliberate one-fresh-buffer-per-tensor allocation
        return jax.jit(lambda xs, s: [x * s for x in xs])
    if name == "b_mul_d0":
        return jax.jit(lambda xs, s: [x * s for x in xs],
                       donate_argnums=(0,))
    if name == "b_mom_pair":
        # v2 = t+g ; step = v2*lr in one sweep (fadd feeding fmul never
        # contracts; the t+g is CSE'd to one add)
        return jax.jit(lambda ts, gs, lr:
                       ([t + g for t, g in zip(ts, gs)],
                        [(t + g) * lr for t, g in zip(ts, gs)]),
                       donate_argnums=(0,))
    if name == "b_adam_mul4":
        # (m*b1, g*(1-b1), v*b2, (g*g)*(1-b2)): all products, the g*g
        # chain included (mul feeding mul never contracts).  m and v are
        # the retiring slot buffers — donated.  s2/s4 are the RETAINED
        # SCRATCH buffers for the two non-slot products (see the
        # scratch-recycling note above _build_kernel): where(pred=False,
        # scr, expr) is bitwise expr, and the donated scr buffer becomes
        # the output in place — no fresh store-sized allocation.
        return jax.jit(
            lambda ms, vs, gs, b1, w1, b2, w2, s2s, s4s, pred:
            ([m * b1 for m in ms],
             [jnp.where(pred, s2, g * w1)
              for s2, g in zip(s2s, gs)],
             [v * b2 for v in vs],
             [jnp.where(pred, s4, (g * g) * w2)
              for s4, g in zip(s4s, gs)]),
            donate_argnums=(0, 1, 7, 8))
    if name == "b_lion_mul4":
        # (m*b1, g*(1-b1), m*b2, g*(1-b2)): the interpolation AND the
        # EMA products off the same old slot, one read sweep of m/g.
        # t3 = m*b2 becomes the new slot via b_add_d0 (its buffer is
        # retained in the slot table); t2/t4 recycle scratch.
        return jax.jit(
            lambda ms, gs, b1, w1, b2, w2, s2s, s4s, pred:
            ([m * b1 for m in ms],
             [jnp.where(pred, s2, g * w1)
              for s2, g in zip(s2s, gs)],
             [m * b2 for m in ms],
             [jnp.where(pred, s4, g * w2)
              for s4, g in zip(s4s, gs)]),
            donate_argnums=(0, 6, 7))
    if name == "b_add2":
        # (t1+t2, t3+t4): pure adds — products all from prior programs.
        # Only t1/t3 are donated: two outputs can reuse two buffers.
        return jax.jit(lambda t1s, t2s, t3s, t4s:
                       ([a + b for a, b in zip(t1s, t2s)],
                        [a + b for a, b in zip(t3s, t4s)]),
                       donate_argnums=(0, 2))
    if name == "b_add_d0":
        return jax.jit(lambda xs, ys:
                       [a + b for a, b in zip(xs, ys)],
                       donate_argnums=(0,))
    if name == "b_adam_fin1":
        # plain adam's WHOLE tail in one sweep:
        #   out = p - ((m/bc1)*lr) / (sqrt(v/bc2)+eps)
        # Every hazard is dodged by construction: the outer divide's
        # numerator is a MUL (not a div — the lr multiply interposes,
        # so the a/b/c consecutive-divide rewrite cannot fire), the
        # final sub consumes a QUOTIENT (not a product — no FMA
        # contraction), and sqrt/add on the denominator chain are
        # product-free.  Saves the den/mh materialization sweeps; the
        # output is the per-tensor fresh params buffer.  (adamw cannot
        # fuse like this: its mh is UNSCALED, so mh/den would be a
        # div-of-div — it keeps the two-program tail.)
        return jax.jit(
            lambda ps, ms, vs, bc1, bc2, eps, lr:
            [p - ((m / bc1) * lr) / (jnp.sqrt(v / bc2) + eps)
             for p, m, v in zip(ps, ms, vs)])
    if name == "b_adamw_den_mh":
        # (sqrt(v/bc2)+eps, m/bc1): denominator and UNSCALED
        # bias-corrected moment in one sweep (lr multiplies LAST, after
        # the decay term joins — the host AdamW's evaluation order).
        # The two dataflow chains are independent — CRUCIALLY the final
        # u = mh/den divide lives in the NEXT program, because XLA's
        # algebraic simplifier rewrites consecutive divides a/b/c into
        # a/(b*c), which rounds differently from numpy's two divides
        # (mh here is a bare quotient, so it CANNOT fuse with the /den
        # the way plain adam's lr-scaled tail does — see b_adam_fin1).
        # div+sqrt+add chains are rewrite-free.  v2/m2 are live slots —
        # never donated; the denominator recycles scratch.
        return jax.jit(
            lambda vs, bc2, eps, ms, bc1, sds, pred:
            ([jnp.where(pred, sd, jnp.sqrt(v / bc2) + eps)
              for sd, v in zip(sds, vs)],
             [m / bc1 for m in ms]),
            donate_argnums=(5,))
    if name == "b_adamw_fin":
        # u = (mh/den)*lr — single divide, mul after (no-decay lane)
        return jax.jit(lambda mhs, dens, lr:
                       [(mh / den) * lr
                        for mh, den in zip(mhs, dens)],
                       donate_argnums=(0,))
    if name == "b_adamw_fin_wd":
        # u = ((mh/den)+t)*lr — the one divide feeds an add (quotient,
        # not product) and the trailing mul consumes the add: both
        # contraction-free; t = p*wd was formed in the PRIOR program
        return jax.jit(lambda mhs, dens, ts, lr:
                       [((mh / den) + t) * lr
                        for mh, den, t in zip(mhs, dens, ts)],
                       donate_argnums=(0,))
    if name == "b_wd_mul":
        # t = p*wd — the decoupled-decay product, alone (scratch-recycled)
        return jax.jit(lambda ps, wd, sws, pred:
                       [jnp.where(pred, sw, p * wd)
                        for sw, p in zip(sws, ps)],
                       donate_argnums=(2,))
    if name == "b_addmul":
        # (u+t)*lr: the add consumes two PRIOR products; the mul then
        # consumes the add (fadd feeding fmul never contracts)
        return jax.jit(lambda us, ts, lr:
                       [(u + t) * lr for u, t in zip(us, ts)],
                       donate_argnums=(0,))
    # ---- flat-arena stages (core/arena.py, ISSUE 15) ----
    # Per-stripe mega-array operands: one flat f32 slab per (stripe,
    # role) regardless of tensor count.  Same fusion rules as above —
    # flattening changes which buffer an element lives in, never the
    # operation sequence applied to it.  The AdamW/Lion matrices-only
    # decay mask becomes a per-element boolean operand and a branch
    # SELECT: both lanes are the existing per-tensor expressions, and a
    # select preserves the taken branch's bits (a wd=0 multiply-through
    # would not: `x + p*0` flips -0.0 to +0.0 and keeps NaN params in
    # the plain lane).
    if name == "a_copy":
        # momentum's copy-seed on a slab: select of identical branches
        # is a bit copy into a FRESH buffer (no donation) — the slot
        # must not alias the put-back-able sums slab
        return jax.jit(lambda x, pred: jnp.where(pred, x, x))
    if name == "a_wd_mul":
        # t = p*wd on the decay lane, 0 elsewhere — the product formed
        # ALONE (the next program consumes t as an operand, so no
        # product ever feeds an add in one program); scratch-recycled
        # via the outer runtime-false select like b_wd_mul
        return jax.jit(
            lambda p, wd, mask, s, pred:
            jnp.where(pred, s,
                      jnp.where(mask, p * wd, jnp.float32(0.0))),
            donate_argnums=(3,))
    if name == "a_adamw_fin":
        # u = ((mh/den)+t)*lr decayed / (mh/den)*lr plain, per element:
        # the divide is CSE'd once, the add consumes a QUOTIENT and an
        # operand (no contraction), the mul consumes the select.  mh is
        # a retiring intermediate — donated.
        return jax.jit(
            lambda mhs, dens, ts, mask, lr:
            jnp.where(mask, (mhs / dens) + ts, mhs / dens) * lr,
            donate_argnums=(0,))
    if name == "a_lion_fin":
        # u = (s+t)*lr decayed / s*lr plain — s is the sign result from
        # the prior program (donated), t the decay product operand
        return jax.jit(
            lambda ss, ts, mask, lr:
            jnp.where(mask, ss + ts, ss) * lr,
            donate_argnums=(0,))
    if name == "b_sign_add":
        # sign(t1+t2) with numpy sign semantics: ±0 -> +0.0, denormals
        # nonzero, NaN propagates (jnp.sign flushes denormals to 0 and
        # keeps -0's sign on XLA:CPU, so build it from compares —
        # adds/compares/selects only, no product in this program)
        def _one(t1, t2):
            x = t1 + t2
            s = jnp.where(x > 0, jnp.float32(1.0),
                          jnp.where(x < 0, jnp.float32(-1.0),
                                    jnp.float32(0.0)))
            return jnp.where(jnp.isnan(x), x, s)
        return jax.jit(lambda t1s, t2s:
                       [_one(a, b) for a, b in zip(t1s, t2s)],
                       donate_argnums=(0,))
    raise KeyError(f"unknown device kernel {name!r}")


def k(name: str):
    """The named exact kernel, compiled lazily (see module docstring for
    the fusion rule that keeps each one bit-identical to numpy)."""
    fn = _kernels.get(name)
    if fn is None:
        fn = _kernels[name] = _build_kernel(name)
    return fn


def slab_update(ranges: tuple, mode: str, flat: bool):
    """One jit program folding a chunk's tensors into a stripe slab at
    STATIC (offset, length) ranges (core/arena.py, ISSUE 15) — the one
    device op per (chunk, stripe, lane).  Static slices lower to plain
    slice/concat updates instead of gather-scatter over index arrays,
    so the fold runs at elementwise-add speed; compile count is one per
    distinct range tuple, and chunk boundaries are stable across
    iterations.  ``mode='set'`` is the exact BIT-COPY seed of a fresh
    name (the host oracle's first-touch ``np.array(g)`` — zeros+add
    would flip -0.0); ``mode='add'`` the correctly-rounded f32
    accumulate, elementwise ``np.add`` exactly.  ``flat=True`` takes ONE
    pre-concatenated host upload split by the static ranges inside the
    program (numpy payloads cross H2D once per lane); ``flat=False``
    takes the per-tensor device arrays as a pytree.  The slab is
    donated and updates land in place."""
    key = ("a_slab", mode, flat, ranges)
    fn = _kernels.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        segments = _merge_ranges(ranges)

        # The updated slab is rebuilt as ONE interleaved concatenation:
        # per merged segment, the folded values (plus the slab's own
        # elements on the add lane); between segments, the untouched
        # slab slices.  One read of the slab + one write of the result
        # — the same memory traffic as the per-tensor in-place adds —
        # where a chain of per-tensor dynamic-update-slices costs a
        # slab copy EACH on XLA:CPU.  adds are correctly-rounded f32
        # (elementwise np.add exactly); sets are bit copies.
        def run(slab, vals):
            total = slab.shape[0]
            pieces = []
            pos = voff = 0
            for dst, idxs, seglen in segments:
                if dst > pos:
                    pieces.append(slab[pos:dst])
                if flat:
                    v = vals[0][voff:voff + seglen]
                    voff += seglen
                else:
                    parts = [vals[i].astype(jnp.float32).reshape(-1)
                             for i in idxs]
                    v = parts[0] if len(parts) == 1 else \
                        jnp.concatenate(parts)
                pieces.append(v if mode == "set"
                              else slab[dst:dst + seglen] + v)
                pos = dst + seglen
            if pos < total:
                pieces.append(slab[pos:total])
            return (pieces[0] if len(pieces) == 1
                    else jnp.concatenate(pieces))

        fn = _kernels[key] = jax.jit(run, donate_argnums=(0,))
    return fn


def _merge_ranges(ranges: tuple) -> list:
    """Merge ABUTTING (offset, length) ranges (sorted by offset) into
    (offset, [input indices], total length) segments — a whole-store
    push over an unpadded stripe collapses to one segment."""
    segments: list[tuple[int, list[int], int]] = []
    for i, (off, ln) in enumerate(ranges):
        if segments and segments[-1][0] + segments[-1][2] == off:
            segments[-1] = (segments[-1][0], segments[-1][1] + [i],
                            segments[-1][2] + ln)
        else:
            segments.append((off, [i], ln))
    return segments


def slab_full_cover(ranges: tuple, size: int) -> bool:
    """True when ``ranges`` tile [0, size) exactly — a set-lane fold
    then needs no existing slab at all (the assembled values ARE the
    slab, skipping the zeros seed and its memset)."""
    merged = _merge_ranges(ranges)
    return len(merged) == 1 and merged[0][0] == 0 and merged[0][2] == size


def slab_assemble(ranges: tuple):
    """The no-prior-slab seed: concatenate the per-tensor device values
    into the stripe slab (bit copies, one kernel)."""
    key = ("a_assemble", ranges)
    fn = _kernels.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def run(vals):
            parts = [v.astype(jnp.float32).reshape(-1) for v in vals]
            return parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts)

        fn = _kernels[key] = jax.jit(run)
    return fn


def _topk_scatter(total: int):
    fn = _kernels.get(("topk", total))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def scatter(idx, vals):
            return jnp.zeros(total, jnp.float32).at[idx].set(
                vals.astype(jnp.float32))

        fn = jax.jit(scatter)
        _kernels[("topk", total)] = fn
    return fn


# ------------------------------------------------------------- dequant
def device_unpack(wire_dtype: int, raw, total: int):
    """Wire payload -> device f32 array, dequantizing ON DEVICE.

    Bit-compatible with ``Codec.unpack`` (the numpy oracle) and the
    native C++ kernels: the host-side work is only header parsing and the
    H2D copy of the PACKED bytes (int8 crosses at 1/4 the f32 volume,
    bf16 at 1/2, top-k at the kept-entry volume); the arithmetic — int8
    scale multiply, bf16 upcast, top-k scatter — runs as a jit kernel.
    """
    import jax.numpy as jnp

    from ..rpc.codec import (WIRE_BF16, WIRE_INT8, WIRE_RAW_F32, WIRE_TOPK,
                             bf16_dtype)

    raw = bytes(raw) if not isinstance(raw, (bytes, bytearray)) else raw
    if wire_dtype == WIRE_RAW_F32:
        return jnp.asarray(np.frombuffer(raw, dtype="<f4"))
    if wire_dtype == WIRE_BF16:
        host = np.frombuffer(raw, dtype=bf16_dtype())
        return k("cast_f32")(jnp.asarray(host))
    if wire_dtype == WIRE_INT8:
        scale = np.frombuffer(raw, dtype="<f4", count=1)[0]
        q = np.frombuffer(raw, dtype=np.int8, offset=4)
        return k("dequant_int8")(jnp.asarray(q), jnp.float32(scale))
    if wire_dtype == WIRE_TOPK:
        kept = int(np.frombuffer(raw, dtype="<u4", count=1)[0])
        if not kept:
            return jnp.zeros(total, jnp.float32)
        idx = np.frombuffer(raw, dtype="<u4", offset=4, count=kept)
        vals = np.frombuffer(raw, dtype=bf16_dtype(), offset=4 + 4 * kept,
                             count=kept)
        return _topk_scatter(total)(jnp.asarray(idx.astype(np.int32)),
                                    jnp.asarray(vals))
    raise ValueError(f"not a packed wire dtype: {wire_dtype}")


def tensor_to_device(t):
    """rpc Tensor -> device f32 array (the device-buffer fold target used
    by rpc/data_plane.decode_gradients).  Packed payloads dequantize on
    device; the legacy repeated-float encoding decodes host-side first
    (its wire format is already full f32 — nothing to win)."""
    import jax.numpy as jnp

    from ..rpc.codec import PACKED_WIRE_DTYPES
    from ..rpc.wire import ArrayPayload

    packed = t.packed
    if isinstance(packed, ArrayPayload):
        packed = packed.tobytes()
    if t.packed_dtype in PACKED_WIRE_DTYPES and packed:
        arr = device_unpack(t.packed_dtype, packed,
                            int(np.prod(t.shape)))
        if t.shape:
            arr = arr.reshape(t.shape)
        return arr
    return jnp.asarray(np.asarray(t.to_array(), np.float32))


# ---------------------------------------------------------------- folds
def is_device_array(a) -> bool:
    """POSITIVE jax-Array detection: the fold path must treat every
    other array-like (numpy, memoryviews, duck-typed test doubles with
    only ``__array__``) exactly like the pre-existing numpy code did,
    so "not an ndarray" is not enough."""
    return (not isinstance(a, np.ndarray)
            and hasattr(a, "block_until_ready") and hasattr(a, "dtype"))


def is_device_store(store: Mapping) -> bool:
    """True when any value is a device-resident jax Array."""
    return any(is_device_array(v) for v in store.values())


def owned_f32(g):
    """First-fold accumulator seed: an exclusively-owned device f32 array
    (the device analogue of ``np.array(g, np.float32)``).  A device input
    is adopted without copy — device arrays are immutable, and the
    decode dict that produced it is dropped right after the fold."""
    import jax.numpy as jnp

    if isinstance(g, np.ndarray):
        return jnp.asarray(np.ascontiguousarray(g, np.float32))
    return k("cast_f32")(g) if g.dtype != jnp.float32 else g


def owned_copy(g):
    """A freshly-ALLOCATED device f32 copy, never an adoption — for
    seeding a value into an optimizer slot that a later step will
    DONATE.  Adopting (``owned_f32``) would let the donation delete a
    buffer the original producer may still hold (the numpy path's
    ``np.array(g)`` first-touch copy exists for the same reason)."""
    import jax.numpy as jnp

    if isinstance(g, np.ndarray):
        return jnp.asarray(np.ascontiguousarray(g, np.float32))
    if g.dtype != jnp.float32:
        return k("cast_f32")(g)
    return jnp.array(g)  # copy=True: a distinct device buffer


def fold_add(acc, g):
    """acc + g on device (correctly-rounded f32, bit-identical to the
    numpy ``np.add``).  The old ``acc`` buffer is donated — its only
    reference is the accumulator slot the caller immediately overwrites.
    Raises on a shape mismatch BEFORE the donation is consumed,
    preserving the fold-retry contract.  The check reproduces
    ``np.add(acc, g, out=acc)`` exactly: g may broadcast UP to acc's
    shape, but a result shape differing from acc raises — jax's add
    would otherwise happily broadcast BOTH ways and silently rebind the
    accumulator to a wrong-shaped sum."""
    import jax.numpy as jnp

    try:
        result_shape = np.broadcast_shapes(acc.shape, g.shape)
    except ValueError as exc:
        raise ValueError(
            f"fold shape mismatch: accumulator {acc.shape} vs gradient "
            f"{g.shape}") from exc
    if tuple(result_shape) != tuple(acc.shape):
        raise ValueError(
            f"fold shape mismatch: gradient {g.shape} does not fold into "
            f"accumulator {acc.shape}")
    if isinstance(g, np.ndarray):
        g = jnp.asarray(np.ascontiguousarray(g, np.float32))
    elif g.dtype != jnp.float32:
        g = k("cast_f32")(g)
    return k("add_d0")(acc, g)


def scale_mean(acc, count: int):
    """acc * (1/count): the contributor-mean scale, same f32 scalar as
    the numpy path (``np.float32(1.0 / count)`` — the divide runs in f64
    and rounds once).  Donates ``acc``; the caller re-binds the slot."""
    import jax.numpy as jnp

    return k("mul_d0")(acc, jnp.float32(np.float32(1.0 / count)))


# ------------------------------------------------------------- readback
def readback_async(store: Mapping) -> None:
    """Start the device->host copy of every device-resident value WITHOUT
    blocking (jax ``copy_to_host_async``).  Called right after a device
    apply swaps the store in, so the D2H overlaps the barrier publish and
    a serve-side encode finds the host bytes already in flight instead of
    stalling on the transfer."""
    for v in store.values():
        start = getattr(v, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # noqa: BLE001 — prefetch only; the encode's
                pass           # blocking np.asarray still succeeds without it


def block_on_store(store: Mapping) -> None:
    """Wait until every device value is materialized (test/bench helper:
    makes a 'settled' close timing honest about the async dispatch)."""
    for v in store.values():
        wait = getattr(v, "block_until_ready", None)
        if wait is not None:
            wait()
