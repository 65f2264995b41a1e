"""Buffers a component keeps and writes again, and who still reads them.

At the sizes a parameter store is chunked into (far above malloc's mmap
threshold) a new buffer is new address space and every 4 KB of it a page
fault, which costs more than the copy or the sweep that fills it.  So the
ring's frame pool (rpc/shm_transport.py), the serve cache
(server/ps_service.py), the barrier close (core/close_buffers.py) and the
fold's accumulator (core/fold_buffers.py) each keep ``bytearray``s, hand out views of them, and write one again only when
:func:`exported` finds no view of it alive: a holder of a view keeps that
buffer, and the keeper allocates in its place.  That one rule is what keeps
served memory from being written, and it lives here.

The allocators are for destinations that are overwritten whole before
anything reads them: :func:`untouched_bytearray` for a kept buffer whose
writers should first-touch their own ranges, :func:`uninit_bytes` for the
immutable ``bytes`` gRPC's serializer wants.
"""

from __future__ import annotations

import ctypes

import numpy as np

_pyapi = ctypes.pythonapi
_pyapi.PyByteArray_Resize.restype = ctypes.c_int
_pyapi.PyByteArray_Resize.argtypes = [ctypes.py_object, ctypes.c_ssize_t]
_pyapi.PyBytes_FromStringAndSize.restype = ctypes.py_object
_pyapi.PyBytes_FromStringAndSize.argtypes = [ctypes.c_char_p, ctypes.c_ssize_t]
_pyapi.PyBytes_AsString.restype = ctypes.c_void_p
_pyapi.PyBytes_AsString.argtypes = [ctypes.py_object]


def exported(buf: bytearray) -> bool:
    """Whether any view of ``buf`` is alive, however derived (slices of
    the memoryview handed out, ``np.frombuffer`` arrays of those, a
    device transfer still reading one).  The interpreter keeps that count
    for a bytearray and refuses to resize one under an export, so ask it:
    a pop/append pair changes nothing when it is allowed (the allocation
    has room for the byte it just gave up) and raises before touching
    anything when it is not."""
    try:
        buf.append(buf.pop())
    except BufferError:
        return True
    return False


def untouched_bytearray(nbytes: int) -> bytearray:
    """A bytearray of ``nbytes`` whose pages nothing has touched:
    ``bytearray(n)`` zeroes them on the calling thread, while the tasks
    that fill this one first-touch each range on the thread that writes
    it.  Its bytes mean nothing until written."""
    buf = bytearray()
    _pyapi.PyByteArray_Resize(buf, nbytes)   # raises MemoryError itself
    return buf


def float32_over(buf: bytearray | None, shape: tuple, fresh
                 ) -> tuple[bytearray | None, np.ndarray]:
    """A float32 array of ``shape`` to be overwritten whole, and the
    buffer it lies in: ``buf`` when it has exactly the size and no view
    of it is alive, a new :func:`untouched_bytearray` otherwise (its
    bytes added to the counter ``fresh``).  An empty shape needs no
    buffer: ``(None, empty array)``."""
    nbytes = 4 * int(np.prod(shape, dtype=np.int64))
    if not nbytes:
        return None, np.empty(shape, np.float32)
    if buf is None or len(buf) != nbytes or exported(buf):
        fresh.add(nbytes)
        buf = untouched_bytearray(nbytes)
    return buf, np.frombuffer(buf, np.float32).reshape(shape)


def uninit_bytes(size: int) -> tuple[bytes, np.ndarray]:
    """Return (bytes_of_len_size, writable uint8 view into it); the view
    is for whoever fills the object before anyone else sees it."""
    obj = _pyapi.PyBytes_FromStringAndSize(None, size)
    addr = _pyapi.PyBytes_AsString(obj)
    view = np.frombuffer((ctypes.c_ubyte * size).from_address(addr), np.uint8)
    return obj, view
