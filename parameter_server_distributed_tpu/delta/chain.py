"""Bounded wire-space delta chain between consecutive store versions.

Built by the PS right after the striped optimizer apply (the core's
delta sink hook — core/ps_core.py ``set_delta_sink``): the new store is
encoded to the configured delta wire dtype (``PSDT_DELTA_DTYPE``,
default bf16) and diffed ELEMENTWISE IN WIRE SPACE against the retained
encoding of the previous version.  That is the whole trick: a small
optimizer step moves most weights by less than a bf16 ulp, so the wire
bytes a full pull would ship are mostly UNCHANGED between versions —
the changed slice is genuinely sparse even though every f32 value
moved.  A receiver holding version ``v``'s decode gets exactly version
``v+1``'s decode by scattering the changed elements' wire values into
its cached arrays:

- unchanged element => unchanged wire bytes => the receiver's cached
  decode is already bit-identical to a fresh full pull's;
- changed element => the delta carries exactly the bytes the full pull
  would, decoded by the same codec path.

So chain-applied deltas are bit-for-bit equal to a full pull by
construction, for every elementwise wire encoding (f32/raw/bf16 — the
lossy int8/topk encodings are never used for SERVED parameters,
server/ps_service.py ``_serve_wire_dtype``).

The chain is bounded (``PSDT_DELTA_DEPTH`` pairs) and value-based: it
does not care WHY the store changed, only that the retained previous
encoding matches the named version.  Any version bump the sink was not
told about (checkpoint restore, replication install, reshard retire —
each also calls :meth:`DeltaChain.reset`) leaves a version gap, the
pair is not built, and receivers behind the gap are served full.

Checksum contract (the receiver's base-mismatch detector): per tensor,
crc32 over the DECODED little-endian f32 bytes of the full tensor at
``to_version``; the store checksum folds the per-tensor crcs as
crc32 over their ``<u4`` concatenation in sorted-name order (so both
ends can compute per-tensor crcs in parallel and fold cheaply).
"""

from __future__ import annotations

import logging
import os
import struct
import threading
import time
import zlib
from collections import OrderedDict
from typing import Mapping

import numpy as np

from ..analysis.lock_order import checked_lock
from ..core.stripes import partition_names, run_striped, stripe_count
from ..obs import flight
from ..rpc.codec import (WIRE_BF16, WIRE_DTYPE_NAMES, WIRE_F32,
                         WIRE_RAW_F32, bf16_dtype)
from ..rpc.wire import ArrayPayload
from .messages import DEFAULT_DTYPE, ENV_DTYPE, delta_depth

log = logging.getLogger("pst.delta")

# wire encodings the chain supports: elementwise, fixed bytes/element
_ELEMENTWISE = {WIRE_F32: 4, WIRE_RAW_F32: 4, WIRE_BF16: 2}

# Publication coalescing under continuous versions (free-running mode,
# freerun/engine.py, ISSUE 16): with barriers gone, EVERY push bumps the
# raw store version, and notifying the chain per push would rebuild a
# delta pair, wake every SubscribeWeights parker, and churn the
# encode-once serve cache on every single push — while exhausting
# PSDT_DELTA_DEPTH in one barrier-width's worth of pushes.  The free-run
# engine therefore PUBLISHES (snapshots + notes a new served version) at
# most once per PSDT_PUBLISH_MIN_VERSIONS applies, with
# PSDT_PUBLISH_MAX_LAG_MS bounding how long an apply may sit
# unpublished.  Barriered and async modes never coalesce — their apply
# cadence IS the version cadence, byte-identical with these unset.
ENV_PUBLISH_MIN_VERSIONS = "PSDT_PUBLISH_MIN_VERSIONS"
ENV_PUBLISH_MAX_LAG_MS = "PSDT_PUBLISH_MAX_LAG_MS"
DEFAULT_PUBLISH_MAX_LAG_MS = 100.0


def publish_min_versions(override: int | None = None) -> int:
    """Applies coalesced per publication.  0 (the default) = auto: the
    free-run engine substitutes its current worker-fleet size, so one
    publication lands per fleet-wide round of pushes — the barriered
    modes' natural version cadence."""
    raw = (override if override is not None
           else os.environ.get(ENV_PUBLISH_MIN_VERSIONS, "0"))
    value = int(raw)
    if value < 0:
        raise ValueError(
            f"{ENV_PUBLISH_MIN_VERSIONS} must be >= 0 (0 = auto), "
            f"got {value}")
    return value


def publish_max_lag_s(override_ms: float | None = None) -> float:
    """Upper bound (seconds) an applied update may wait unpublished —
    the coalescing window's freshness backstop."""
    raw = (override_ms if override_ms is not None
           else os.environ.get(ENV_PUBLISH_MAX_LAG_MS, ""))
    ms = float(raw) if raw != "" else DEFAULT_PUBLISH_MAX_LAG_MS
    if ms < 0:
        raise ValueError(
            f"{ENV_PUBLISH_MAX_LAG_MS} must be >= 0, got {ms}")
    return ms / 1e3


def delta_wire_dtype() -> int:
    name = os.environ.get(ENV_DTYPE, DEFAULT_DTYPE)
    dtype = WIRE_DTYPE_NAMES.get(name)
    if dtype is None or dtype not in _ELEMENTWISE:
        raise ValueError(
            f"{ENV_DTYPE}={name!r} is not an elementwise serve encoding; "
            f"options: f32, raw, bf16")
    return dtype


def wire_dtype_compatible(dtype: int, chain_dtype: int) -> bool:
    """A pull's effective encoding matches the chain when the DECODED f32
    values are identical: f32 and raw-f32 are the same value space."""
    if dtype == chain_dtype:
        return True
    return {dtype, chain_dtype} <= {WIRE_F32, WIRE_RAW_F32}


def encode_wire(flat: np.ndarray, wire_dtype: int) -> np.ndarray:
    """A tensor's flat wire-space image: the exact elementwise payload a
    full pull would carry, as a numpy array (``<u2`` per bf16 element,
    ``<f4`` per f32 element) so versions diff with one vector compare."""
    if wire_dtype == WIRE_BF16:
        raw = ArrayPayload(flat, WIRE_BF16).tobytes()  # active codec path
        return np.frombuffer(raw, dtype="<u2")
    # owned copy, never a view: the retained image must survive the
    # optimizer's in-place ufuncs mutating the live store next apply
    return np.array(flat, dtype="<f4", copy=True).reshape(-1)


def decode_wire_values(raw: bytes, wire_dtype: int) -> np.ndarray:
    """Wire-space element bytes -> f32 values, the codec's decode for a
    (possibly sparse) element subset."""
    if wire_dtype == WIRE_BF16:
        return np.frombuffer(raw, dtype=bf16_dtype()).astype(np.float32)
    return np.frombuffer(raw, dtype="<f4").astype(np.float32, copy=False)


def decoded_f32(wire: np.ndarray, wire_dtype: int) -> np.ndarray:
    """Whole wire-space image -> the f32 array a receiver holds."""
    if wire_dtype == WIRE_BF16:
        return wire.view(bf16_dtype()).astype(np.float32)
    return wire.view("<f4")


def tensor_crc(decoded: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(decoded, "<f4"))


def fold_crcs(named_crcs: Mapping[str, int]) -> int:
    """The store checksum: crc32 over the per-tensor crcs' ``<u4``
    concatenation in sorted-name order (see module doc)."""
    return zlib.crc32(b"".join(
        struct.pack("<I", named_crcs[name] & 0xFFFFFFFF)
        for name in sorted(named_crcs)))


def store_crc(store: Mapping[str, np.ndarray]) -> int:
    """Checksum of a receiver-side f32 store — what the last frame of a
    delta pair must match after the chain applies."""
    return fold_crcs({name: tensor_crc(np.ascontiguousarray(arr, "<f4"))
                      for name, arr in store.items()})


class DeltaPair:
    """One built ``from_version -> to_version`` transition."""

    __slots__ = ("from_version", "to_version", "entries", "nbytes", "crc",
                 "changed", "total")

    def __init__(self, from_version: int, to_version: int,
                 entries: list, nbytes: int, crc: int,
                 changed: int, total: int):
        self.from_version = from_version
        self.to_version = to_version
        # [(name, idx_bytes | b"", value_bytes, dense)], sorted by name
        self.entries = entries
        self.nbytes = nbytes          # wire payload bytes of the entries
        self.crc = crc                # store checksum at to_version
        self.changed = changed        # changed elements (diagnostics)
        self.total = total


class DeltaChain:
    """The bounded pair store + the retained previous wire image.

    ``note_apply`` is the core's post-apply hook: it runs inside the
    barrier close (under ``_apply_lock`` on the streaming path), never
    raises (a build failure logs, drops the chain, and the next serve
    falls back to full — serve correctness over delta coverage), and
    does its O(model) encode/diff OUTSIDE ``_lock`` (applies are
    serialized by the caller, so the retained image has exactly one
    writer; ``_lock`` guards only the published pair map and the
    subscriber condition variable)."""

    def __init__(self, depth: int | None = None,
                 wire_dtype: int | None = None,
                 stripes: int | None = None):
        self.depth = delta_depth() if depth is None else int(depth)
        self.wire_dtype = (delta_wire_dtype() if wire_dtype is None
                           else int(wire_dtype))
        if self.wire_dtype not in _ELEMENTWISE:
            raise ValueError(f"unsupported delta wire dtype "
                             f"{self.wire_dtype}")
        self._stripes = stripe_count(stripes)
        self._lock = checked_lock("DeltaChain._lock")
        self._cv = threading.Condition(self._lock)
        # keyed by from_version; consecutive keys form servable chains
        self._pairs: "OrderedDict[int, DeltaPair]" = OrderedDict()
        # previous version's wire image (one writer: the serialized
        # apply hook) + its generation fence against a concurrent reset
        self._wire_prev: dict[str, np.ndarray] | None = None
        # flat-arena stores (core/arena.py ArenaStore, ISSUE 15) also
        # retain the previous image as whole per-stripe wire SLABS —
        # (packing table, {stripe: wire slab}) — so the next build's
        # bitwise diff is one vector compare per stripe slab split per
        # tensor by table offset, instead of a compare per tensor.  The
        # per-name views above stay populated (they alias the slabs),
        # so a residency flip mid-chain degrades to the per-name diff,
        # never to a missed pair.
        self._prev_slabs: tuple | None = None
        self._prev_version = -1
        self._gen = 0

    # ------------------------------------------------------------- build
    def note_apply(self, store: Mapping[str, np.ndarray],
                   version: int) -> None:
        """Record that the serialized apply produced ``version`` with
        ``store``'s values.  Builds the ``prev -> version`` pair when the
        retained image is exactly one version behind; otherwise reseeds.
        MUST NOT raise (core hook contract)."""
        try:
            self._note_apply(store, int(version))
        except Exception:  # noqa: BLE001 — a delta build failure must
            # never fail the barrier close; full serves remain correct
            log.exception("delta build failed at version %d; chain reset",
                          version)
            self.reset()

    @staticmethod
    def _diff_entry(name: str, prev_bits, new_bits, wire: np.ndarray,
                    itemsize: int) -> tuple:
        """One tensor's pair entry from its (bitwise) changed-index set
        — shared by the per-name and slab diffs, so their bytes are
        identical by construction."""
        idx_changed = np.flatnonzero(prev_bits != new_bits)
        n, total = int(idx_changed.size), int(wire.size)
        if n * (4 + itemsize) < total * itemsize:
            return (name, idx_changed.astype("<u4").tobytes(),
                    wire[idx_changed].tobytes(), False, n)
        return (name, b"", wire.tobytes(), True, n)

    def _note_apply(self, store: Mapping[str, np.ndarray],
                    version: int) -> None:
        with self._lock:
            gen = self._gen
            prev = self._wire_prev
            prev_version = self._prev_version
            prev_slabs = self._prev_slabs
        diffable = (prev is not None and version == prev_version + 1
                    and set(prev) == set(store))
        itemsize = _ELEMENTWISE[self.wire_dtype]
        names = sorted(store)
        layout = getattr(store, "layout", None)
        slabs = getattr(store, "slabs", None)
        new_slabs: tuple | None = None
        if layout is not None and slabs is not None:
            # flat-arena store (ISSUE 15): encode + diff whole stripe
            # SLABS — the contiguous layout makes the bitwise diff a
            # straight vector compare over each slab, split per tensor
            # by table offset; entry bytes are identical to the
            # per-name path's by construction (_diff_entry)
            merged, wire_slabs = self._build_arena(
                store, layout, slabs, diffable, prev, prev_slabs,
                itemsize)
            new_slabs = (layout, wire_slabs)
        else:
            merged = self._build_per_name(store, names, diffable, prev,
                                          itemsize)
        wires = {name: merged[name][0] for name in names}
        crc = fold_crcs({name: merged[name][1] for name in names})
        pair = None
        if diffable and all(merged[n][2] is not None for n in names):
            entries = [merged[n][2][:4] for n in names]
            nbytes = sum(len(e[1]) + len(e[2]) for e in entries)
            changed = sum(merged[n][2][4] for n in names)
            total = sum(int(w.size) for w in wires.values())
            pair = DeltaPair(prev_version, version, entries, nbytes, crc,
                             changed, total)
        with self._lock:
            if self._gen != gen:
                return  # a reset landed mid-build: this image is stale
            self._wire_prev = wires
            self._prev_slabs = new_slabs
            self._prev_version = version
            if pair is not None:
                self._pairs[pair.from_version] = pair
                while len(self._pairs) > self.depth:
                    self._pairs.popitem(last=False)
                flight.record("serve.delta.build", a=pair.nbytes,
                              b=version)
            else:
                # version gap / shape change: older pairs can no longer
                # chain to the current version — drop them
                self._pairs.clear()
            self._cv.notify_all()

    def _build_per_name(self, store: Mapping[str, np.ndarray],
                        names: list[str], diffable: bool,
                        prev: dict | None, itemsize: int) -> dict:
        """The per-tensor encode + diff (the pre-arena path): one wire
        encode and one bitwise compare per tensor, stripe-parallel."""
        groups = (partition_names(names, self._stripes)
                  if len(names) > 1 else [list(names)])
        results: list[dict] = [{} for _ in groups]

        def build_group(idx: int, group: list[str]) -> None:
            out = results[idx]
            for name in group:
                flat = np.asarray(store[name], np.float32).reshape(-1)
                wire = encode_wire(flat, self.wire_dtype)
                crc = tensor_crc(decoded_f32(wire, self.wire_dtype))
                entry = None
                if diffable and prev[name].size == wire.size:
                    # BITWISE compare (u2/u4 views), not float compare:
                    # 0.0 -> -0.0 changes the wire bytes a full pull
                    # would ship, and NaNs must patch deterministically
                    if self.wire_dtype == WIRE_BF16:
                        prev_bits, new_bits = prev[name], wire
                    else:
                        prev_bits = prev[name].view("<u4")
                        new_bits = wire.view("<u4")
                    entry = self._diff_entry(name, prev_bits, new_bits,
                                             wire, itemsize)
                out[name] = (wire, crc, entry)

        run_striped([(lambda i=i, g=g: build_group(i, g))
                     for i, g in enumerate(groups)])
        merged: dict[str, tuple] = {}
        for out in results:
            merged.update(out)
        return merged

    def _build_arena(self, store: Mapping[str, np.ndarray], layout,
                     slabs: Mapping[int, np.ndarray], diffable: bool,
                     prev: dict | None, prev_slabs: tuple | None,
                     itemsize: int) -> tuple[dict, dict]:
        """The slab encode + diff for a flat-arena store: per stripe,
        ONE wire-space encode of the whole host slab and — when the
        previous image was retained under the SAME packing-table epoch —
        ONE bitwise vector compare over it, with the changed-index set
        split per tensor by table offset (searchsorted).  Per-tensor
        wire views slice the slab encoding, so entry bytes, crcs, and
        the sparse/dense decision are identical to the per-name path's.
        Falls to the per-name diff per tensor when the previous image
        predates the arena (a residency flip mid-chain)."""
        slab_prev = None
        if (diffable and prev_slabs is not None
                and prev_slabs[0].epoch == layout.epoch):
            slab_prev = prev_slabs[1]
        merged: dict[str, tuple] = {}
        wire_slabs: dict[int, np.ndarray] = {}
        stripes = sorted(slabs)
        results: list[tuple] = [None] * len(stripes)

        def build_stripe(idx: int, stripe: int) -> None:
            host = slabs[stripe]
            wire_slab = encode_wire(
                np.asarray(host, np.float32).reshape(-1),
                self.wire_dtype)
            changed = None
            if slab_prev is not None and stripe in slab_prev \
                    and slab_prev[stripe].size == wire_slab.size:
                if self.wire_dtype == WIRE_BF16:
                    prev_bits, new_bits = slab_prev[stripe], wire_slab
                else:
                    prev_bits = slab_prev[stripe].view("<u4")
                    new_bits = wire_slab.view("<u4")
                # the slab diff: one vector compare over the whole
                # contiguous stripe (padding elements never change)
                changed = np.flatnonzero(prev_bits != new_bits)
            out: dict[str, tuple] = {}
            for name in layout.stripe_names[stripe]:
                e = layout.entries[name]
                wire = wire_slab[e.offset:e.offset + e.length]
                crc = tensor_crc(decoded_f32(wire, self.wire_dtype))
                entry = None
                if changed is not None:
                    lo, hi = np.searchsorted(
                        changed, (e.offset, e.offset + e.length))
                    local = (changed[lo:hi] - e.offset).astype("<u4")
                    n, total = int(local.size), int(wire.size)
                    if n * (4 + itemsize) < total * itemsize:
                        entry = (name, local.tobytes(),
                                 wire_slab[changed[lo:hi]].tobytes(),
                                 False, n)
                    else:
                        entry = (name, b"", wire.tobytes(), True, n)
                elif diffable and prev is not None \
                        and prev[name].size == wire.size:
                    # previous image predates the arena: per-name diff
                    if self.wire_dtype == WIRE_BF16:
                        prev_bits, new_bits = prev[name], wire
                    else:
                        prev_bits = prev[name].view("<u4")
                        new_bits = wire.view("<u4")
                    entry = self._diff_entry(name, prev_bits, new_bits,
                                             wire, itemsize)
                out[name] = (wire, crc, entry)
            results[idx] = (wire_slab, out)

        run_striped([(lambda i=i, s=s: build_stripe(i, s))
                     for i, s in enumerate(stripes)])
        for idx, stripe in enumerate(stripes):
            wire_slab, out = results[idx]
            wire_slabs[stripe] = wire_slab
            merged.update(out)
        # names outside the slabs cannot occur (an ArenaStore's views
        # cover exactly the table), but stay defensive: encode any
        # stragglers per name so the image is complete
        for name in store:
            if name not in merged:
                flat = np.asarray(store[name], np.float32).reshape(-1)
                wire = encode_wire(flat, self.wire_dtype)
                merged[name] = (wire, tensor_crc(
                    decoded_f32(wire, self.wire_dtype)), None)
        return merged, wire_slabs

    def reset(self) -> None:
        """Invalidate everything (restore / replication install /
        reshard retire): the retained image no longer describes the
        store, and serving a stale pair would patch a wrong base."""
        with self._lock:
            self._gen += 1
            self._pairs.clear()
            self._wire_prev = None
            self._prev_slabs = None
            self._prev_version = -1
            self._cv.notify_all()

    # ------------------------------------------------------------- serve
    @property
    def version(self) -> int:
        with self._lock:
            return self._prev_version

    def pairs_between(self, held: int, current: int
                      ) -> list[DeltaPair] | None:
        """The consecutive pair chain ``held -> current``, or None when
        any hop is missing (past the depth budget, across a reset, or a
        version the sink never saw)."""
        if held < 0 or current <= held:
            return None
        with self._lock:
            chain: list[DeltaPair] = []
            v = held
            while v < current:
                pair = self._pairs.get(v)
                if pair is None:
                    return None
                chain.append(pair)
                v = pair.to_version
            return chain

    def wait_for_newer(self, version: int, timeout: float) -> bool:
        """Park until the chain records a version newer than ``version``
        (the subscription handler's wakeup; bounded wait — callers
        re-probe the core's serve version on every wake regardless)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._prev_version <= version:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cv.wait(remaining)
            return True
