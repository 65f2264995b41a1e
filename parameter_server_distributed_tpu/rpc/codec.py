"""The wire payload codec: one narrow interface, two implementations.

Every packed tensor payload on the wire (the ``Tensor.packed`` extension
field — rpc/messages.py) is produced and consumed through the
:class:`Codec` interface below:

- :class:`PythonCodec` — the pure-numpy reference implementation.  It is
  the BYTE-IDENTITY ORACLE: the payload layouts are defined by what this
  class emits, and every other implementation must match it bit for bit
  (fuzz-checked across dtypes/shapes in tests/test_codec.py).
- :class:`NativeCodec` — the C++ fast path (native/psdt_native.cpp, built
  by the existing ``native.lib()`` g++ machinery).  Encode/decode/
  quantize/dequantize run as single fused passes over zero-copy pointers
  into the caller's arrays and the encoder's preallocated message buffer;
  ctypes releases the GIL, so stripe-parallel encodes really occupy
  multiple cores.  Any operation the native library cannot take falls
  back to the inherited numpy path per call — never a different answer,
  at worst a slower one.

Selection is per-process: :func:`active_codec` resolves to the native
codec whenever ``native.lib()`` is available and enabled (``PSDT_NATIVE=0``
or ``native.set_enabled(False)`` forces the Python path — the tests' A/B
switch).  The resolved choice is exported as the ``rpc.codec.native`` gauge.

Payload layouts (little-endian, pinned by the Python oracle):

- ``WIRE_RAW_F32``:  n * f32
- ``WIRE_BF16``:     n * bf16 (round-to-nearest-even)
- ``WIRE_INT8``:     f32 max-abs scale | n * int8
- ``WIRE_TOPK``:     u32 k | k * u32 ascending indices | k * bf16 values

Top-k selection is part of the codec contract: elements with |v| strictly
above the k-th largest |v|, threshold ties filled in ascending index
order (:func:`topk_indices`) — deterministic, so native and Python emit
identical bytes even on tied inputs.
"""

from __future__ import annotations

import numpy as np

from .. import native
from ..obs import flight
from ..obs import stats as obs_stats

# Wire encodings for Tensor payloads.  WIRE_F32 is the reference encoding
# (packed `repeated float`, field 3) and never reaches the codec; the
# packed encodings are a framework extension carried in fields 5/6, which
# reference peers skip per proto3 unknown-field rules.
WIRE_F32 = 0       # repeated float field 3 (reference-compatible, default)
WIRE_RAW_F32 = 1   # raw little-endian float32 bytes in field 5
WIRE_BF16 = 2      # raw bfloat16 bytes in field 5 — half the payload
WIRE_INT8 = 3      # f32 max-abs scale + int8 bytes in field 5 — quarter
                   # the payload (EQuARX-style quantized transport; pair
                   # with error feedback for gradients — worker/worker.py)
WIRE_TOPK = 4      # top-k sparsified: u32 k | k*u32 indices | k*bf16
                   # values in field 5 (Deep-Gradient-Compression-style
                   # transport; pair with error feedback so unsent mass
                   # is carried, not dropped — worker/worker.py)

# CLI/config name -> wire dtype.  Single definition; rpc/messages.py
# re-exports it (the analyzer manifest pins its VALUES through there).
WIRE_DTYPE_NAMES = {"f32": WIRE_F32, "raw": WIRE_RAW_F32, "bf16": WIRE_BF16,
                    "int8": WIRE_INT8, "topk": WIRE_TOPK}

# The packed encodings the codec handles (everything but repeated-float).
PACKED_WIRE_DTYPES = (WIRE_RAW_F32, WIRE_BF16, WIRE_INT8, WIRE_TOPK)

# Bytes an element takes in the encodings that are a cast of each element
# alone, so that a slice of the source packs to the same slice of the
# payload (int8's scale and top-k's selection read the whole source).
ELEMENT_BYTES = {WIRE_RAW_F32: 4, WIRE_BF16: 2}

TOPK_DEFAULT_DENSITY = 0.01  # fraction of entries a topk tensor keeps


_BF16 = None


def bf16_dtype():
    global _BF16
    if _BF16 is None:
        import ml_dtypes  # ships with jax
        _BF16 = ml_dtypes.bfloat16
    return _BF16


def topk_k(size: int, density: float) -> int:
    """Kept-entry count for a WIRE_TOPK payload of ``size`` elements."""
    if not size:
        return 0
    return min(size, max(1, int(round(size * density))))


def payload_nbytes(wire_dtype: int, size: int, k: int = 0) -> int:
    """Exact payload byte count — known BEFORE any encode runs, which is
    what lets the two-pass exactly-sized encoder (wire.py) budget packed
    payloads lazily."""
    if wire_dtype == WIRE_RAW_F32:
        return 4 * size
    if wire_dtype == WIRE_BF16:
        return 2 * size
    if wire_dtype == WIRE_INT8:
        return 4 + size
    if wire_dtype == WIRE_TOPK:
        return 4 + 6 * k
    raise ValueError(f"not a packed wire dtype: {wire_dtype}")


def topk_indices(flat: np.ndarray, k: int) -> np.ndarray:
    """Deterministic top-k-|value| selection (ascending u32 indices).

    Threshold = the k-th largest |v| (``np.partition`` — value-defined, so
    every implementation agrees); everything strictly above it is kept,
    ties AT the threshold fill the remaining slots in ascending index
    order, and NaN entries (which compare false both ways but sort as
    the LARGEST values, numpy convention) fill any slots still left,
    ascending — so a diverging run's NaN gradients still encode exactly
    k entries instead of crashing the push.  The tie-break is part of
    the codec contract — it is what makes native and Python
    byte-identical on inputs like all-equal gradients, where an
    argpartition's arbitrary tie choice would diverge between
    implementations (and numpy versions)."""
    n = int(flat.size)
    if k >= n:
        return np.arange(n, dtype="<u4")
    ab = np.abs(flat)
    thr = np.partition(ab, n - k)[n - k]
    above = np.nonzero(ab > thr)[0]
    at = np.nonzero(ab == thr)[0][:k - above.size]
    short = k - above.size - at.size
    if short > 0:  # NaNs in the top-k range (possibly thr itself)
        at = np.concatenate([at, np.nonzero(np.isnan(ab))[0][:short]])
    return np.sort(np.concatenate([above, at])).astype("<u4")


class Codec:
    """Narrow payload codec interface: flat f32 array <-> packed payload
    bytes, for the packed WIRE_* encodings.

    ``pack_into`` writes the exact ``payload_nbytes`` payload of ``src``
    (flat contiguous float32) into the writable buffer ``dst`` — encode,
    quantize, and sparsify are all this one call, running straight into
    the outgoing message buffer (no intermediate copies).  ``unpack``
    inverts it: payload bytes -> flat f32 array (``total`` is the dense
    element count, needed by WIRE_TOPK's scatter).  Implementations MUST
    be byte-identical to :class:`PythonCodec` — it is the oracle.
    """

    name = "abstract"

    def pack_into(self, wire_dtype: int, src: np.ndarray, dst,
                  k: int = 0) -> None:
        raise NotImplementedError

    def unpack(self, wire_dtype: int, raw, total: int) -> np.ndarray:
        raise NotImplementedError


class PythonCodec(Codec):
    """Pure-numpy reference implementation — the byte-identity oracle and
    the always-available fallback (no compiler required)."""

    name = "python"

    def pack_into(self, wire_dtype: int, src: np.ndarray, dst,
                  k: int = 0) -> None:
        if wire_dtype == WIRE_RAW_F32:
            np.copyto(np.frombuffer(dst, dtype="<f4"), src,
                      casting="unsafe")
        elif wire_dtype == WIRE_BF16:
            # fused convert-and-store: the f32->bf16 cast writes straight
            # into the message buffer
            np.copyto(np.frombuffer(dst, dtype=bf16_dtype()), src,
                      casting="unsafe")
        elif wire_dtype == WIRE_INT8:
            out = np.frombuffer(dst, np.uint8)
            max_abs = float(np.max(np.abs(src))) if src.size else 0.0
            scale = max_abs / 127.0 if max_abs > 0 else 1.0
            out[:4] = np.frombuffer(np.float32(scale).tobytes(), np.uint8)
            q = np.clip(np.rint(src / np.float32(scale)),
                        -127, 127).astype(np.int8)
            out[4:] = q.view(np.uint8)
        elif wire_dtype == WIRE_TOPK:
            out = np.frombuffer(dst, np.uint8)
            out[:4] = np.frombuffer(np.uint32(k).tobytes(), np.uint8)
            if k:
                idx = topk_indices(src, k)
                vals = src[idx.astype(np.int64)].astype(bf16_dtype())
                out[4:4 + 4 * k] = idx.view(np.uint8)
                out[4 + 4 * k:] = vals.view(np.uint8)
        else:
            raise ValueError(f"not a packed wire dtype: {wire_dtype}")

    def unpack(self, wire_dtype: int, raw, total: int) -> np.ndarray:
        if wire_dtype == WIRE_BF16:
            return np.frombuffer(raw, dtype=bf16_dtype()).astype(np.float32)
        if wire_dtype == WIRE_RAW_F32:
            # zero-copy view; to_array() copies iff a writable array is
            # needed (the read-only view is the cost this codec avoids)
            return np.frombuffer(raw, dtype="<f4").astype(np.float32,
                                                          copy=False)
        if wire_dtype == WIRE_INT8:
            scale = np.frombuffer(raw, dtype="<f4", count=1)[0]
            return np.frombuffer(raw, dtype=np.int8,
                                 offset=4).astype(np.float32) * scale
        if wire_dtype == WIRE_TOPK:
            k = int(np.frombuffer(raw, dtype="<u4", count=1)[0])
            arr = np.zeros(total, np.float32)
            if k:
                idx = np.frombuffer(raw, dtype="<u4", offset=4, count=k)
                vals = np.frombuffer(raw, dtype=bf16_dtype(),
                                     offset=4 + 4 * k, count=k)
                arr[idx.astype(np.int64)] = vals.astype(np.float32)
            return arr
        raise ValueError(f"not a packed wire dtype: {wire_dtype}")


class NativeCodec(PythonCodec):
    """C++ fast path over zero-copy memoryviews (native/psdt_native.cpp).

    Each operation tries the native kernel and inherits the numpy path
    when it declines (library unavailable, unsuitable layout, or a
    malformed payload the Python path should reject loudly) — so a
    process that loses the native library mid-run degrades per call, not
    catastrophically."""

    name = "native"

    def pack_into(self, wire_dtype: int, src: np.ndarray, dst,
                  k: int = 0) -> None:
        if wire_dtype == WIRE_BF16:
            if native.pack_bf16_native(src, dst):
                return
        elif wire_dtype == WIRE_INT8:
            if native.quant_int8_native(src, dst):
                return
        elif wire_dtype == WIRE_TOPK:
            if native.topk_pack_native(src, k, dst):
                return
        # WIRE_RAW_F32 is a memcpy either way — numpy is already optimal
        super().pack_into(wire_dtype, src, dst, k)

    def unpack(self, wire_dtype: int, raw, total: int) -> np.ndarray:
        if wire_dtype == WIRE_BF16:
            out = np.empty(len(raw) // 2, np.float32)
            if native.unpack_bf16_native(raw, out):
                return out
        elif wire_dtype == WIRE_INT8:
            out = np.empty(max(0, len(raw) - 4), np.float32)
            if native.dequant_int8_native(raw, out):
                return out
        elif wire_dtype == WIRE_TOPK:
            out = np.empty(total, np.float32)
            if native.topk_unpack_native(raw, out):
                return out
        return super().unpack(wire_dtype, raw, total)


_PYTHON = PythonCodec()
_NATIVE = NativeCodec()
_gauge = obs_stats.gauge("rpc.codec.native")
_last: Codec | None = None


def active_codec() -> Codec:
    """The process-wide codec: native when the library is available and
    enabled (``PSDT_NATIVE``), the Python oracle otherwise.  Resolved per
    call — a few attribute reads — so ``native.set_enabled`` flips take
    effect immediately; the ``rpc.codec.native`` gauge records the
    resolved choice (1 = native)."""
    global _last
    codec: Codec = _NATIVE if native.lib() is not None else _PYTHON
    if codec is not _last:
        _gauge.set(1.0 if codec is _NATIVE else 0.0)
        # flight evidence: which codec this process resolved (and every
        # flip — a mid-run native failure downgrade is a postmortem clue)
        flight.record("codec.select", a=1 if codec is _NATIVE else 0)
        _last = codec
    return codec
