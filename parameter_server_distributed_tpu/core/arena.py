"""Flat arena apply (ISSUE 15): per-stripe mega-array layout for the
accelerator-resident barrier close.

PR 11 moved the close onto the accelerator but kept a per-TENSOR program
structure: a stripe's update stage is one jit dispatch over the tensor
LIST, so a transformer/moe store with hundreds of small params still
pays XLA dispatch per tensor per stage.  ``PSDT_ARENA=1`` flattens the
layout instead: one contiguous f32 device buffer per (stripe, role) —
params, mean-sums, and each optimizer slot — addressed through a
process-stable packing table (name -> offset/length/shape, rebuilt only
on a store-shape change and epoch-fenced like the shard map), so

- fold chunks scatter into the stripe's sum arena as ONE device op per
  chunk lane (index ranges precomputed from the table; the per-chunk
  dequantize kernels stay at ingress exactly as PR 11 left them),
- the contributor-mean scale and every optimizer stage run as ONE fused
  kernel per stage per stripe over the flat buffer, REGARDLESS of
  tensor count (the per-element arithmetic is byte-for-byte the host
  optimizers' ufunc sequences, so the numpy oracle still holds), and
- the post-swap D2H readback is ONE contiguous transfer per stripe
  whose host bytes every per-tensor consumer — serve-cache encode,
  delta build, checkpoint — slices by table offset as zero-copy numpy
  views instead of re-gathering tensor by tensor.

Bit-exactness is inherited from core/device_apply.py's kernel rules
(no product feeds an add/sub in the same program; selects preserve the
taken branch's bits): flattening only changes WHICH buffer an element
lives in, never the operation sequence applied to it.  The two
per-tensor behaviors that do not trivially flatten are handled exactly:

- the AdamW/Lion matrices-only weight-decay mask becomes a per-element
  boolean operand and a branch SELECT (``where(mask, decayed, plain)``)
  — both lanes are elementwise-exact, and a select never alters the
  taken branch — with the table packing decayed (ndim >= 2) tensors
  first so the mask is a monotone prefix per stripe;
- Momentum's copy-seed (``v = np.array(g)`` on first touch, not
  ``mu*0 + g`` — the latter flips ``-0.0`` to ``+0.0``) is preserved by
  an all-or-nothing per-table seeding rule; a MIXED velocity table
  (some names seeded, some not — reshard merges) downgrades that close
  to the per-tensor path.

Downgrade matrix (never fail the PS boot, never fail a close):
anything the flat layout cannot represent exactly — gradient coverage
short of the table (pass-through names), non-uniform per-name
contributor counts (quorum straggler folds, sharded disjoint pushes),
tombstoned names mid-iteration, a table epoch moving under an open
accumulator, mixed momentum seeding, or any packing failure — falls
back to the PR 11 per-tensor device path FOR THAT CLOSE, with an
``apply.arena.fallback`` flight code and the ``ps.apply.arena_fallback``
counter.  A packing EXCEPTION additionally latches the arena off for
the core (the per-tensor path is always correct).  Default off: every
PR 11 path is byte-identical with the flag unset.

Padding: ``PSDT_ARENA_ALIGN`` (elements, default 1) rounds each
tensor's slab offset up, trading padding bytes for aligned slices.
Padding elements are zero-initialized, never scattered into, masked
OUT of the decay lane, and provably fixed points of every update rule
at (p=0, g=0, slots=0) — they ride the fused sweeps and stay zero.
The ``ps.apply.arena_pad`` gauge reports the padding overhead.
"""

from __future__ import annotations

import os
import time
from typing import Iterable, Mapping

import numpy as np

from ..analysis.lock_order import checked_lock
from ..obs import flight
from ..obs import stats as obs_stats
from . import device_apply
from .stripes import stripe_of

ENV_ARENA = "PSDT_ARENA"
ENV_ALIGN = "PSDT_ARENA_ALIGN"
ENV_MAX_TENSOR = "PSDT_ARENA_MAX_TENSOR_BYTES"

# Regime bound (the stripe_dispatch discipline one level up): the arena
# exists for the DISPATCH floor — hundreds of small tensors paying
# per-tensor overhead per stage.  A store of big tensors is BANDWIDTH
# bound, and on XLA:CPU's thunk runtime one fused sweep is ONE thunk
# (one core) while the per-tensor batched stage parallelizes its
# independent per-tensor ops across the pool — so ON A CPU BACKEND
# stores above this MEAN tensor size keep the per-tensor path
# (byte-identical anyway).  On an accelerator a single fused sweep
# saturates the chip, so there the default is no bound.
DEFAULT_MAX_TENSOR_BYTES = 2 << 20


def enabled() -> bool:
    """The per-process layout knob.  Default off: the PR 11 per-tensor
    device path (and every host path) sees zero change."""
    return os.environ.get(ENV_ARENA, "") not in ("", "0")


def align_elems() -> int:
    n = int(os.environ.get(ENV_ALIGN, "1") or "1")
    if n < 1:
        raise ValueError(f"{ENV_ALIGN} must be >= 1, got {n}")
    return n


def max_tensor_bytes() -> int:
    """Mean-tensor-size regime bound; 0 disables the bound.  Unset, it
    follows the backend the slabs live on: DEFAULT_MAX_TENSOR_BYTES on
    a CPU backend, no bound on an accelerator."""
    value = os.environ.get(ENV_MAX_TENSOR)
    if value:
        return int(value)
    import jax

    on_cpu = jax.devices()[0].platform == "cpu"
    return DEFAULT_MAX_TENSOR_BYTES if on_cpu else 0


# Close-path device dispatches per stripe (contributor-mean scale
# included), per update rule — the "one kernel per stage per stripe"
# acceptance bound tests/test_arena.py asserts against.  Rules with
# a weight-decay mask pay two extra stages (the decay product and the
# select tail); everything else is the PR 11 stage list collapsed onto
# one flat operand.
STAGE_BUDGET: dict[str, int] = {
    "sgd": 3,        # scale, g*lr, p-u
    "momentum": 4,   # scale, v*mu (or seed copy), v2/step pair, p-u
    "adam": 4,       # scale, mul4, add2, fused tail
    "adamw": 7,      # scale, mul4, add2, den/mh, wd product, tail, p-u
    "lion": 7,       # scale, mul4, sign-add, slot EMA, wd product, tail
}


def close_dispatch_budget(rule: str, stripes: int) -> int:
    """Max device kernels a flat close may dispatch: stages x stripes."""
    return STAGE_BUDGET[rule] * stripes


class TableEntry:
    __slots__ = ("name", "stripe", "offset", "length", "shape", "decayed")

    def __init__(self, name: str, stripe: int, offset: int, length: int,
                 shape: tuple, decayed: bool):
        self.name = name
        self.stripe = stripe
        self.offset = offset      # elements into the stripe slab
        self.length = length      # elements
        self.shape = shape
        self.decayed = decayed    # ndim >= 2: the AdamW/Lion decay mask


def store_signature(store: Mapping) -> tuple:
    """The (name, shape) signature a table is built against — the table
    is rebuilt ONLY when this changes (the shard-map epoch discipline:
    value changes never invalidate the layout, shape changes always
    do)."""
    return tuple(sorted(
        (name, tuple(int(d) for d in np.shape(v)))
        for name, v in store.items()))


class PackingTable:
    """The process-stable name -> (stripe, offset, length, shape) map.

    Packing order per stripe is deterministic — decayed (ndim >= 2)
    names sorted, then the rest sorted — so every process, checkpoint
    era, and test agrees on the layout for a given store signature, and
    the decay mask is a per-stripe prefix."""

    __slots__ = ("stripes", "epoch", "signature", "entries",
                 "stripe_names", "stripe_sizes", "payload_elems",
                 "_masks", "_idx")

    def __init__(self, store: Mapping, stripes: int, epoch: int):
        self.stripes = int(stripes)
        self.epoch = int(epoch)
        self.signature = store_signature(store)
        self.entries: dict[str, TableEntry] = {}
        self.stripe_names: list[list[str]] = [[] for _ in range(stripes)]
        self.stripe_sizes: list[int] = [0] * stripes
        self.payload_elems = 0
        align = align_elems()
        by_stripe: dict[int, list[str]] = {}
        shapes = {name: tuple(int(d) for d in np.shape(v))
                  for name, v in store.items()}
        for name in store:
            by_stripe.setdefault(stripe_of(name, stripes), []).append(name)
        for stripe in range(stripes):
            names = by_stripe.get(stripe, [])
            ordered = (sorted(n for n in names if len(shapes[n]) >= 2)
                       + sorted(n for n in names if len(shapes[n]) < 2))
            offset = 0
            for name in ordered:
                shape = shapes[name]
                length = int(np.prod(shape)) if shape else 1
                self.entries[name] = TableEntry(
                    name, stripe, offset, length, shape,
                    decayed=len(shape) >= 2)
                self.stripe_names[stripe].append(name)
                offset += -(-length // align) * align
                self.payload_elems += length
            self.stripe_sizes[stripe] = offset
        # lazy per-stripe device cache of the decay-mask operand.  dict
        # setdefault is GIL-atomic, so no lock is needed here.
        self._masks: dict[int, object] = {}

    @property
    def total_elems(self) -> int:
        return sum(self.stripe_sizes)

    @property
    def padding_elems(self) -> int:
        return self.total_elems - self.payload_elems

    def covers(self, names: Iterable[str]) -> bool:
        entries = self.entries
        return all(name in entries for name in names)

    def compatible(self, name: str, g) -> bool:
        """True when ``g`` scatters exactly into ``name``'s slab range —
        identical shape, no broadcasting.  Anything else (including the
        host fold's legal broadcast-up) rides the per-tensor overflow
        path, which keeps the exact pre-existing semantics."""
        e = self.entries.get(name)
        return (e is not None
                and tuple(int(d) for d in np.shape(g)) == e.shape)

    def decay_mask(self, stripe: int):
        """Device bool mask of the decayed (ndim >= 2) elements of one
        stripe slab — padding and sub-2D tensors are False."""
        cached = self._masks.get(stripe)
        if cached is None:
            import jax.numpy as jnp

            host = np.zeros(self.stripe_sizes[stripe], bool)
            for name in self.stripe_names[stripe]:
                e = self.entries[name]
                if e.decayed:
                    host[e.offset:e.offset + e.length] = True
            cached = self._masks.setdefault(stripe, jnp.asarray(host))
        return cached

    def views(self, stripe: int, host_slab: np.ndarray) -> dict:
        """Zero-copy per-tensor numpy views of one stripe's host slab,
        sliced by table offset — what every per-tensor consumer (serve
        encode, delta build, checkpoint) reads instead of re-gathering
        device buffers."""
        out = {}
        for name in self.stripe_names[stripe]:
            e = self.entries[name]
            out[name] = host_slab[e.offset:e.offset + e.length].reshape(
                e.shape)
        return out


class ArenaStore(dict):
    """The post-close parameter store: an ordinary ``{name: np.ndarray}``
    dict (every existing consumer is untouched) whose values are views
    into ``slabs`` — one contiguous host f32 buffer per stripe, the
    product of the single per-stripe D2H readback.  ``layout`` carries
    the packing table so slab-aware consumers (delta/chain.py) can diff
    whole slabs instead of tensors."""

    __slots__ = ("layout", "slabs")

    def __init__(self, values: Mapping, layout: PackingTable,
                 slabs: Mapping[int, np.ndarray]):
        super().__init__(values)
        self.layout = layout
        self.slabs = dict(slabs)


class _PoppedShim:
    """Stand-in for a popped accumulator entry — callers only read
    ``.nbytes`` for the buffer accounting."""

    __slots__ = ("nbytes",)

    def __init__(self, nbytes: int):
        self.nbytes = nbytes


class ArenaAccum:
    """A streaming iteration's running sums as per-stripe flat device
    buffers.  Fold chunks scatter in as one device op per (chunk,
    stripe, lane): fresh names take the SET lane (the exact bit-copy
    seed ``np.array(g)`` is on the host path — zeros+add would flip
    ``-0.0``), repeat names the correctly-rounded ADD lane, with host
    (numpy) payloads concatenated once and crossing H2D as one upload.
    Names the table cannot represent exactly (unknown, shape-mismatched
    — the host fold's broadcast-up) fold per-tensor into ``overflow``
    via the caller's pre-existing ``_fold_one`` path, which forces the
    per-tensor fallback close.

    Thread-safety matches the per-tensor accumulator: different stripes
    fold under different stripe locks (disjoint slabs), one stripe's
    folds are serialized by its lock, and the barrier close drains
    in-flight folds before taking the accumulator."""

    __slots__ = ("table", "slabs", "covered", "popped", "overflow",
                 "scaled")

    def __init__(self, table: PackingTable):
        self.table = table
        self.slabs: dict[int, object] = {}
        self.covered: dict[int, set[str]] = {}
        self.popped: set[str] = set()
        self.overflow: dict = {}       # name -> per-tensor accumulator
        self.scaled = False

    # ------------------------------------------------------------- fold
    def fold_group(self, stripe: int, items: list, counts: dict,
                   weight: int) -> int:
        """Scatter one chunk's tensors for one stripe into the slab.
        ``items`` must be table-compatible (caller pre-validated).
        Returns bytes newly resident.  Caller holds the stripe lock (or
        ``_state_lock`` on the serial path) and updates the per-worker
        folded set from the items afterwards."""
        import jax.numpy as jnp

        table = self.table
        cov = self.covered.setdefault(stripe, set())
        fresh = [(n, g) for n, g in items if n not in cov]
        repeat = [(n, g) for n, g in items if n in cov]
        slab = self.slabs.get(stripe)
        size = table.stripe_sizes[stripe]
        added = 0
        for mode, group in (("set", fresh), ("add", repeat)):
            if not group:
                continue
            group.sort(key=lambda kv: table.entries[kv[0]].offset)
            # one lane per payload residence: device payloads ride the
            # jit pytree; host payloads concatenate once (an O(bytes)
            # memcpy) and cross H2D as one upload, split back by the
            # STATIC ranges inside the compiled program
            lanes: list[list] = [[], []]
            for name, g in group:
                lanes[0 if device_apply.is_device_array(g) else 1].append(
                    (name, g))
            for lane in lanes:
                if not lane:
                    continue
                ranges = tuple(
                    (table.entries[n].offset, table.entries[n].length)
                    for n, _ in lane)
                host = not device_apply.is_device_array(lane[0][1])
                if host:
                    vals = [jnp.asarray(np.concatenate(
                        [np.asarray(g, np.float32).reshape(-1)
                         for _, g in lane]))
                            if len(lane) > 1 else
                            jnp.asarray(np.asarray(
                                lane[0][1], np.float32).reshape(-1))]
                else:
                    vals = [g for _, g in lane]
                if slab is None and mode == "set" \
                        and device_apply.slab_full_cover(ranges, size):
                    # whole-stripe seed: the assembled values ARE the
                    # slab — no zeros memset, and a host lane's upload
                    # lands as the slab with zero kernels
                    slab = (vals[0] if host
                            else device_apply.slab_assemble(ranges)(
                                vals))
                    continue
                if slab is None:
                    slab = jnp.zeros(size, jnp.float32)
                slab = device_apply.slab_update(ranges, mode, host)(
                    slab, vals)
        for name, _ in fresh:
            cov.add(name)
            added += 4 * table.entries[name].length
        for name, _ in items:
            counts[name] = counts.get(name, 0) + weight
        self.slabs[stripe] = slab
        return added

    # ------------------------------------------------------------ close
    def names(self) -> set[str]:
        out: set[str] = set()
        for cov in self.covered.values():
            out |= cov
        out |= set(self.overflow)
        return out - self.popped

    def full_coverage(self) -> bool:
        """True when the sums cover EXACTLY the table: every name folded,
        none popped (retired), nothing in per-tensor overflow — the
        precondition for the flat close."""
        if self.overflow or self.popped:
            return False
        covered = sum(len(c) for c in self.covered.values())
        return covered == len(self.table.entries)

    def scale_uniform(self, count: int) -> None:
        """The contributor-mean scale as one kernel per stripe — the
        same f32 scalar multiply as the per-tensor paths (caller proved
        the per-name counts uniform).  Donates each slab and rebinds."""
        for stripe, slab in self.slabs.items():
            self.slabs[stripe] = device_apply.scale_mean(slab, count)
        self.scaled = True

    def to_tensor_dict(self) -> dict:
        """Per-tensor DEVICE views of the sums — the per-tensor fallback
        close's input (and the put-back accumulator on a failed apply:
        jax slices are their own buffers, safe for later donation)."""
        out = dict(self.overflow)
        for stripe, cov in self.covered.items():
            slab = self.slabs.get(stripe)
            if slab is None:
                continue
            for name in cov:
                if name in self.popped:
                    continue
                e = self.table.entries[name]
                out[name] = slab[e.offset:e.offset + e.length].reshape(
                    e.shape)
        return out

    def to_host_dict(self) -> dict:
        """Writable host numpy sums (one readback per stripe) — the leaf
        barrier relay's input; put back on a relay failure, they must
        stay foldable in place."""
        device_apply.readback_async({i: s for i, s in self.slabs.items()})
        out = {}
        for stripe, cov in self.covered.items():
            slab = self.slabs.get(stripe)
            if slab is None:
                continue
            host = np.asarray(slab)
            for name in cov:
                if name in self.popped:
                    continue
                e = self.table.entries[name]
                out[name] = np.array(
                    host[e.offset:e.offset + e.length],
                    np.float32).reshape(e.shape)
        for name, acc in self.overflow.items():
            out[name] = np.array(np.asarray(acc), np.float32)
        return out

    # ------------------------------------------- mapping-protocol shims
    def __iter__(self):
        return iter(self.names())

    def __len__(self) -> int:
        return len(self.names())

    def __contains__(self, name) -> bool:
        return name in self.names()

    def values(self):
        """The live device buffers (slabs + overflow) — what settle
        helpers (``block_on_store``) and residence probes walk."""
        return list(self.slabs.values()) + list(self.overflow.values())

    def in_slab(self, name: str) -> bool:
        """True when ``name``'s running sum lives in a stripe slab (and
        was not evicted/popped)."""
        if name in self.popped:
            return False
        e = self.table.entries.get(name)
        return (e is not None
                and name in self.covered.get(e.stripe, ()))

    def evict_to_overflow(self, name: str) -> None:
        """Move a slab-resident sum into the per-tensor overflow dict
        (one range readback, a WRITABLE host copy) — the convergence
        point when a later fold for the same name cannot scatter (the
        host fold's legal broadcast-up): the partial sum must keep
        accumulating in ONE place, or the fallback close would divide
        by a count covering contributions it cannot see.  Caller holds
        the lock covering the name's stripe."""
        if not self.in_slab(name):
            return
        e = self.table.entries[name]
        slab = self.slabs[e.stripe]
        self.overflow[name] = np.array(
            np.asarray(slab[e.offset:e.offset + e.length]),
            np.float32).reshape(e.shape)
        self.popped.add(name)

    def pop(self, name, default=None):
        """Retire-purge hook (reshard tombstones): the name's range is
        vacated from the close's coverage — which forces the per-tensor
        fallback for this iteration — and the returned shim carries the
        freed byte count for the buffer gauge."""
        if name in self.overflow:
            return self.overflow.pop(name)
        e = self.table.entries.get(name)
        if e is None or name in self.popped:
            return default
        if not any(name in cov for cov in self.covered.values()):
            return default
        self.popped.add(name)
        return _PoppedShim(4 * e.length)


class ArenaManager:
    """Per-core owner of the packing table and the device param slabs.

    The table is rebuilt ONLY when the store signature changes (epoch
    bumped — the shard-map fence discipline); param slabs are adopted
    from the previous close's output (zero H2D in steady state) and
    repacked from whatever store is live otherwise.  ``_lock``
    serializes builds/packs (device dispatch under it is its purpose —
    BLOCKING_ALLOWED, rank 49 in analysis/lock_order.py); the fold hot
    path only reads the published ``table`` reference, which is a
    GIL-atomic attribute load."""

    def __init__(self, stripes: int):
        self._stripes = int(stripes)
        self._lock = checked_lock("ArenaManager._lock")
        self.table: PackingTable | None = None
        self._table_ref: object = None       # store identity the table
        self._epoch = 0                      # was last validated against
        self._param_slabs: dict[int, object] | None = None
        self._adopted_ref: object = None
        self._slab_epoch = -1
        self._latched_off = False
        # regime gate (see DEFAULT_MAX_TENSOR_BYTES): True when the
        # current store's mean tensor size keeps it on the per-tensor
        # path — re-evaluated whenever the table rebuilds
        self.gated = False
        # resolved here, where the core has just brought the backend up:
        # ensure_table runs under the core's locks and must not reach
        # jax.devices() (a backend init can block)
        self._max_tensor_bytes = max_tensor_bytes()
        self._obs_closes = obs_stats.counter("ps.apply.arena")
        self._obs_fallbacks = obs_stats.counter("ps.apply.arena_fallback")
        self._obs_pad = obs_stats.gauge("ps.apply.arena_pad")

    @property
    def active(self) -> bool:
        return not self._latched_off

    def note_close(self) -> None:
        self._obs_closes.add()

    def fallback(self, reason: str, iteration: int = -1) -> None:
        """Per-close downgrade to the per-tensor device path (counter +
        flight code; the close itself still succeeds)."""
        self._obs_fallbacks.add()
        flight.record("apply.arena.fallback", iteration=iteration,
                      note=reason[:48])

    def latch_off(self, reason: str) -> None:
        """A packing EXCEPTION latches the arena off for this core —
        the per-tensor path is always correct, and a persistent packing
        failure must not re-raise on every close."""
        self._latched_off = True
        self.fallback(f"latched: {reason}")

    # ------------------------------------------------------------ table
    def ensure_table(self, store: Mapping,
                     iteration: int = -1) -> PackingTable | None:
        """The current packing table, rebuilt on a store-shape change.
        ``store`` is the live params reference (callers read it under
        ``_params_lock`` first); identity short-circuits the signature
        scan on the hot path.  Returns None (and latches) on a build
        failure."""
        if self._latched_off or not store:
            return None
        if self.table is not None and self._table_ref is store:
            return None if self.gated else self.table
        try:
            with self._lock:
                if self.table is not None and self._table_ref is store:
                    return None if self.gated else self.table
                sig = store_signature(store)
                if self.table is None or self.table.signature != sig:
                    t0 = time.perf_counter()
                    self._epoch += 1
                    self.table = PackingTable(store, self._stripes,
                                              self._epoch)
                    self._param_slabs = None
                    self._adopted_ref = None
                    pad = self.table.padding_elems
                    total = max(1, self.table.total_elems)
                    self._obs_pad.set(round(pad / total, 4))
                    bound = self._max_tensor_bytes
                    mean = (4 * self.table.payload_elems
                            // max(1, len(self.table.entries)))
                    was_gated = self.gated
                    self.gated = bool(bound) and mean > bound
                    if self.gated and not was_gated:
                        # once per table, not per close: this store is
                        # bandwidth-bound — the per-tensor path is the
                        # right regime for it (see DEFAULT_MAX_TENSOR_
                        # BYTES); byte-identical either way
                        self.fallback(f"regime: mean {mean}B > {bound}B")
                    flight.record(
                        "apply.arena.pack" if self._epoch == 1
                        else "apply.arena.repack",
                        iteration=iteration,
                        a=int(1e6 * (time.perf_counter() - t0)),
                        b=self._stripes)
                self._table_ref = store
                return None if self.gated else self.table
        except Exception as exc:  # noqa: BLE001 — never fail a fold/boot
            self.latch_off(f"{type(exc).__name__}: {exc}")
            return None

    def new_accum(self, table: PackingTable) -> ArenaAccum:
        return ArenaAccum(table)

    # ------------------------------------------------------------ slabs
    def ensure_param_slabs(self, store: Mapping, table: PackingTable,
                           iteration: int = -1) -> dict[int, object]:
        """The device param slabs for ``store`` under ``table`` — the
        previous close's output is ADOPTED by identity (zero H2D); any
        other store (init, restore, install) packs per stripe: one host
        concatenation + one upload each.  Raises on failure (the caller
        latches + falls back)."""
        import jax.numpy as jnp

        with self._lock:
            if (self._param_slabs is not None
                    and self._adopted_ref is store
                    and self._slab_epoch == table.epoch):
                return self._param_slabs
            t0 = time.perf_counter()
            slabs: dict[int, object] = {}
            for stripe in range(table.stripes):
                size = table.stripe_sizes[stripe]
                if not size:
                    continue
                host = np.zeros(size, np.float32)
                for name in table.stripe_names[stripe]:
                    e = table.entries[name]
                    host[e.offset:e.offset + e.length] = np.asarray(
                        np.asarray(store[name]), np.float32).reshape(-1)
                slabs[stripe] = jnp.asarray(host)
            self._param_slabs = slabs
            self._adopted_ref = store
            self._slab_epoch = table.epoch
            flight.record("apply.arena.pack", iteration=iteration,
                          a=int(1e6 * (time.perf_counter() - t0)),
                          b=table.stripes)
            return slabs

    def adopt(self, store: ArenaStore, slabs: dict[int, object]) -> None:
        """Retain a close's output as the next close's input (the host
        views in ``store`` alias the readback, the device ``slabs`` stay
        live for the next apply — params are never donated)."""
        with self._lock:
            self._param_slabs = dict(slabs)
            self._adopted_ref = store
            self._slab_epoch = store.layout.epoch

    def invalidate(self) -> None:
        """Store-mutation fence (restore / replication install / reshard
        retire): the adopted slabs no longer describe the live store and
        the table signature must be re-proven at next use."""
        with self._lock:
            self._param_slabs = None
            self._adopted_ref = None
            self._table_ref = None
