"""Native C++ host kernels with automatic build + Python fallback.

`lib()` returns the ctypes-bound shared library, compiling it with g++ on
first use (cached under native/build/<host ISA key>/).  Every consumer must handle
``lib() is None`` (no compiler available) by falling back to numpy — the
framework is fully functional without the native path, just slower on the
host-side PS hot loops.

Production callers (reference analogue: the C++ aggregation + SGD hot loop
at src/parameter_server.cpp:40-91):

- core/optimizer.py — SGD / Momentum / Adam host optimizers
- core/ps_core.py — fused barrier mean+SGD (`psdt_mean_sgd`)

Set ``PSDT_NATIVE=0`` (or call :func:`set_enabled`) to force the numpy
fallback — the tests' A/B switch.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess

import numpy as np

from ..analysis.lock_order import checked_lock

log = logging.getLogger("pst.native")

_SRC = os.path.join(os.path.dirname(__file__), "psdt_native.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "build")


def build_key() -> str:
    """Short hash of the host's ISA: ``platform.machine()`` plus the
    ``flags`` line of /proc/cpuinfo.  The library is compiled with
    ``-march=native``, so it is only valid on a CPU with the builder's
    instruction set; keying the build directory by it means a checkout
    copied to another machine (build/ and all) compiles its own library
    instead of executing this host's, while one host keeps reusing its
    own.  A fixed function of the host: no pid, time or path in it."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.partition(":")[2].strip()
                    break
    except OSError:
        pass  # no procfs: the machine name alone keys the build
    text = f"{platform.machine()}|{flags}"
    return hashlib.sha256(text.encode()).hexdigest()[:12]


def _so_path() -> str:
    return os.path.join(_BUILD_DIR, build_key(), "libpsdt_native.so")


_lock = checked_lock("native._lock")
_lib: ctypes.CDLL | None = None
_tried = False

_F32P = ctypes.POINTER(ctypes.c_float)


def _build() -> str | None:
    so_path = _so_path()
    try:
        # makedirs inside the guard: a root-installed package run by an
        # unprivileged user has a read-only site-packages — that must mean
        # numpy fallback, not a crash on the PS hot loop
        os.makedirs(os.path.dirname(so_path), exist_ok=True)
        if (os.path.exists(so_path)
                and os.path.getmtime(so_path) >= os.path.getmtime(_SRC)):
            return so_path
        base = ["g++", "-O3", "-ffp-contract=off", "-shared", "-fPIC",
                "-pthread", "-std=c++17", "-o", so_path, _SRC]
        try:
            # -march=native lets the codec loops vectorize (the .so is
            # built on the machine that runs it — build_key() — so the
            # ISA is known); IEEE semantics are untouched — no
            # -ffast-math, ever, and -ffp-contract=off keeps -march from
            # FMA-contracting the optimizer kernels away from numpy's
            # separate mul+add rounding: the wire codec must stay
            # bit-identical to the numpy oracle and the optimizers
            # numpy-trajectory-equal
            cmd = base[:1] + ["-march=native"] + base[1:]
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
        except subprocess.SubprocessError:
            # cross/exotic toolchains may reject -march=native
            subprocess.run(base, check=True, capture_output=True,
                           timeout=120)
        return so_path
    except (OSError, subprocess.SubprocessError) as exc:
        log.warning("native build failed (%s); using numpy fallback", exc)
        return None


_U8P = ctypes.POINTER(ctypes.c_uint8)


def _bind(path: str) -> ctypes.CDLL:
    lib = ctypes.CDLL(path)
    i64, i32, f32 = ctypes.c_int64, ctypes.c_int32, ctypes.c_float
    pp = ctypes.POINTER(_F32P)
    lib.psdt_mean.argtypes = [pp, i32, i64, _F32P]
    lib.psdt_mean_sgd.argtypes = [_F32P, pp, i32, i64, f32]
    # the optimizers' out-of-place sweeps (param, grad, slots..., out, n,
    # scalars...).  A library built before they existed (an old build on
    # a read-only install, which _build cannot replace) lacks them: that
    # optimizer then runs its numpy rule (_sweep), the rest stays bound.
    for name, argtypes in (
            ("psdt_sgd_out", [_F32P] * 3 + [i64, f32]),
            ("psdt_momentum_out", [_F32P] * 4 + [i64, f32, f32]),
            ("psdt_adam_out", [_F32P] * 5 + [i64] + [f32] * 6),
            ("psdt_adamw_out", [_F32P] * 5 + [i64] + [f32] * 7)):
        try:
            getattr(lib, name).argtypes = argtypes
        except AttributeError:
            log.warning("native library %s has no %s; that optimizer "
                        "uses numpy", path, name)
    # wire-codec kernels (rpc/codec.py NativeCodec)
    try:
        # the shm ring's span mover (copy_fn); a library built before it
        # (an old build on a read-only install) lacks it, and the rings
        # then move their spans through memoryviews
        lib.psdt_ring_move.argtypes = [ctypes.c_void_p, i64, i64,
                                       ctypes.c_void_p, i64, i32, i32]
        lib.psdt_ring_move.restype = None
    except AttributeError:
        log.warning("native library %s has no psdt_ring_move; shm rings "
                    "copy under the GIL", path)
    lib.psdt_pack_bf16.argtypes = [_F32P, i64, _U8P]
    lib.psdt_unpack_bf16.argtypes = [_U8P, i64, _F32P]
    lib.psdt_quant_int8.argtypes = [_F32P, i64, _U8P]
    lib.psdt_dequant_int8.argtypes = [_U8P, i64, _F32P]
    lib.psdt_topk_pack.argtypes = [_F32P, i64, i64, _U8P]
    lib.psdt_topk_unpack.argtypes = [_U8P, i64, _F32P]
    lib.psdt_topk_unpack.restype = ctypes.c_int32
    return lib


_enabled = os.environ.get("PSDT_NATIVE", "1").lower() not in ("0", "false")


def set_enabled(value: bool) -> None:
    """Enable/disable the native path at runtime (the tests' A/B switch).

    Re-enabling also clears the build-attempted latch when no library was
    bound, so a failure (e.g. a transiently missing compiler) is retried
    instead of sticking for the process lifetime."""
    global _enabled, _tried
    _enabled = bool(value)
    if _enabled and _lib is None and _tried:
        with _lock:
            if _lib is None:
                _tried = False


def is_enabled() -> bool:
    """Whether the native path is currently requested (it may still be
    unavailable — ``lib()`` is the authoritative probe)."""
    return _enabled


def reset_for_retry() -> None:
    """Drop the bound library and the build-attempted latch so the next
    ``lib()`` call rebuilds/rebinds from scratch (test hook; also the
    escape hatch after fixing a broken toolchain in a live process)."""
    global _lib, _tried
    with _lock:
        _lib = None
        _tried = False


def lib() -> ctypes.CDLL | None:
    """The bound native library, or None if unavailable/disabled."""
    global _lib, _tried
    if not _enabled:
        return None
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is None and not _tried:
            _tried = True
            path = _build()
            if path is not None:
                try:
                    _lib = _bind(path)
                except OSError as exc:
                    log.warning("native load failed: %s", exc)
    return _lib


def _fptr(arr: np.ndarray) -> _F32P:
    return arr.ctypes.data_as(_F32P)


def mean_over_workers_native(arrays: list[np.ndarray]) -> np.ndarray | None:
    """Fused mean of equally-shaped float32 arrays; None if no native lib or
    arrays unsuitable."""
    native = lib()
    if native is None or not arrays:
        return None
    first = arrays[0]
    if np.asarray(first).dtype != np.float32:
        return None
    contig = [np.ascontiguousarray(a, np.float32) for a in arrays]
    if any(c.shape != contig[0].shape for c in contig):
        return None
    out = np.empty_like(contig[0])
    ptrs = (_F32P * len(contig))(*[_fptr(c) for c in contig])
    native.psdt_mean(ptrs, len(contig), contig[0].size, _fptr(out))
    return out


def _sweep(name: str, out: np.ndarray, *arrays: np.ndarray):
    """The optimizer entry point ``name``, or None when the library lacks
    it or the arrays do not suit it: all float32, C-contiguous and of
    ``out``'s shape, ``out`` writable and overlapping none of the others
    (the kernels declare it ``__restrict__``).  None means the caller's
    numpy rule."""
    native = lib()
    fn = getattr(native, name, None) if native is not None else None
    if fn is None or not out.flags.writeable:
        return None
    for a in (out,) + arrays:
        if (a.dtype != np.float32 or not a.flags.c_contiguous
                or a.shape != out.shape):
            return None
    if any(np.may_share_memory(out, a) for a in arrays):
        return None
    return fn


def sgd_native(param: np.ndarray, grad: np.ndarray, out: np.ndarray,
               lr: float) -> bool:
    """out = param - lr*grad; returns False if native path unavailable."""
    fn = _sweep("psdt_sgd_out", out, param, grad)
    if fn is None:
        return False
    fn(_fptr(param), _fptr(grad), _fptr(out), out.size, ctypes.c_float(lr))
    return True


def mean_sgd_native(param: np.ndarray, grads: list[np.ndarray],
                    lr: float) -> bool:
    """In-place fused param -= lr*mean(grads)."""
    native = lib()
    if (native is None or not grads or param.dtype != np.float32
            or not param.flags.c_contiguous):
        return False
    contig = [np.ascontiguousarray(g, np.float32) for g in grads]
    if any(c.shape != param.shape for c in contig):
        return False
    ptrs = (_F32P * len(contig))(*[_fptr(c) for c in contig])
    native.psdt_mean_sgd(_fptr(param), ptrs, len(contig), param.size,
                         ctypes.c_float(lr))
    return True


def momentum_native(param: np.ndarray, grad: np.ndarray,
                    velocity: np.ndarray, out: np.ndarray, lr: float,
                    mu: float) -> bool:
    """Fused velocity = mu*velocity + grad (in place);
    out = param - lr*velocity."""
    fn = _sweep("psdt_momentum_out", out, param, grad, velocity)
    if fn is None:
        return False
    fn(_fptr(param), _fptr(grad), _fptr(velocity), _fptr(out), out.size,
       ctypes.c_float(lr), ctypes.c_float(mu))
    return True


def adam_native(param: np.ndarray, grad: np.ndarray, m: np.ndarray,
                v: np.ndarray, out: np.ndarray, lr: float, b1: float,
                b2: float, eps: float, step: int,
                wd: float | None = None) -> bool:
    """Fused Adam pass: m and v update in place, ``out`` takes the new
    parameters; ``step`` is the 1-based update count used for bias
    correction.  With ``wd`` the AdamW kernel (Adam + decoupled decay in
    one sweep; 0 for tensors excluded from decay)."""
    fn = _sweep("psdt_adam_out" if wd is None else "psdt_adamw_out",
                out, param, grad, m, v)
    if fn is None or step < 1:
        return False
    scalars = [lr, b1, b2, eps, 1.0 - b1 ** step, 1.0 - b2 ** step]
    if wd is not None:
        scalars.append(wd)
    fn(_fptr(param), _fptr(grad), _fptr(m), _fptr(v), _fptr(out), out.size,
       *map(ctypes.c_float, scalars))
    return True


# ---------------------------------------------------------------------------
# Wire-codec wrappers (rpc/codec.py NativeCodec).  All of them are zero-copy:
# sources/destinations are pointers into the caller's numpy arrays and the
# encoder's preallocated message buffer; ctypes releases the GIL around the
# call, so stripe-parallel encodes (core/stripes.py) really run multicore.
# Every wrapper returns False when the native path is unavailable or the
# inputs are unsuitable — the caller falls back to the numpy reference.


def _u8ptr(arr: np.ndarray) -> "ctypes.POINTER":
    return arr.ctypes.data_as(_U8P)


def _as_u8(buf) -> np.ndarray:
    """Zero-copy uint8 view of a bytes/memoryview/ndarray buffer."""
    if isinstance(buf, np.ndarray):
        return buf.view(np.uint8) if buf.dtype != np.uint8 else buf
    return np.frombuffer(buf, np.uint8)


def copy_fn():
    """GIL-free move of one span between a byte ring and the caller's
    memory, ``fn(ring_addr, capacity, pos, mem_addr, nbytes, flags,
    width)`` (raw addresses; ``ring_addr`` the ring's first payload byte;
    ``pos + nbytes`` may pass ``capacity``: the span wraps; ``flags`` 1:
    into the ring, else out of it, 2: with stores that go past the
    cache), or None without the native lib.  The span is cut over
    ``width`` threads, the caller and helpers the library keeps, and the
    call returns when every piece is done; ``width`` 1 is one ``memcpy``
    (two at the wrap) on the caller's thread.  The shm ring transport moves every span with it, so
    the two ends of a ring copy beside each other."""
    native = lib()
    return getattr(native, "psdt_ring_move", None)


def pack_bf16_native(src: np.ndarray, dst) -> bool:
    """f32 -> bf16 (RNE) straight into ``dst`` (2*n bytes)."""
    native = lib()
    if native is None or src.dtype != np.float32 \
            or not src.flags.c_contiguous:
        return False
    native.psdt_pack_bf16(_fptr(src), src.size, _u8ptr(_as_u8(dst)))
    return True


def unpack_bf16_native(raw, out: np.ndarray) -> bool:
    """bf16 payload -> f32 ``out`` (len(raw)//2 elements)."""
    native = lib()
    if native is None or out.dtype != np.float32 \
            or not out.flags.c_contiguous:
        return False
    native.psdt_unpack_bf16(_u8ptr(_as_u8(raw)), out.size, _fptr(out))
    return True


def quant_int8_native(src: np.ndarray, dst) -> bool:
    """f32 -> [f32 max-abs scale | int8 * n] payload into ``dst``."""
    native = lib()
    if native is None or src.dtype != np.float32 \
            or not src.flags.c_contiguous:
        return False
    native.psdt_quant_int8(_fptr(src), src.size, _u8ptr(_as_u8(dst)))
    return True


def dequant_int8_native(raw, out: np.ndarray) -> bool:
    native = lib()
    if native is None or out.dtype != np.float32 \
            or not out.flags.c_contiguous:
        return False
    native.psdt_dequant_int8(_u8ptr(_as_u8(raw)), out.size, _fptr(out))
    return True


def topk_pack_native(src: np.ndarray, k: int, dst) -> bool:
    """f32 -> [u32 k | k*u32 idx | k*bf16 vals] payload into ``dst``
    (deterministic threshold + ascending-index tie-break — the shared
    codec contract, see psdt_native.cpp)."""
    native = lib()
    if native is None or src.dtype != np.float32 \
            or not src.flags.c_contiguous:
        return False
    native.psdt_topk_pack(_fptr(src), src.size, int(k), _u8ptr(_as_u8(dst)))
    return True


def topk_unpack_native(raw, out: np.ndarray) -> bool:
    """topk payload -> dense f32 ``out`` (zero-filled + scatter).  False on
    a malformed payload — truncated header, a k claiming more entries
    than the payload carries (the C++ would read past the buffer), or an
    out-of-range index — so the Python path raises loudly instead."""
    native = lib()
    if native is None or out.dtype != np.float32 \
            or not out.flags.c_contiguous:
        return False
    u8 = _as_u8(raw)
    if u8.size < 4:
        return False
    k = int(np.frombuffer(u8[:4].tobytes(), "<u4")[0])
    if u8.size < 4 + 6 * k:  # wire-facing input: never trust the header
        return False
    rc = native.psdt_topk_unpack(_u8ptr(u8), out.size, _fptr(out))
    return rc == 0
