"""Named-tensor store: the framework's parameter/gradient value type.

The reference models parameters and gradients as a list of named flat float
vectors (`tensor` at include/parameter_server.h:9-14, `TensorLite` at
include/worker.h:14-19).  The TPU-native equivalent is an ordered
``dict[str, np.ndarray | jax.Array]`` — a pytree, so the same store flows
through jitted update steps, shardings, and checkpointing without
conversion.  Host-side (RPC) code uses numpy float32; device-side code uses
jax Arrays; both satisfy this interface.
"""

from __future__ import annotations

from typing import Iterable, Mapping

import numpy as np

from ..rpc.messages import TOPK_DEFAULT_DENSITY, Tensor

# A parameter/gradient store is just an ordered mapping name -> array.
TensorStore = dict[str, np.ndarray]


def to_wire(store: Mapping[str, np.ndarray], wire_dtype: int = 0,
            topk_density: float = TOPK_DEFAULT_DENSITY) -> list[Tensor]:
    """Store -> wire messages (reference: src/worker.cpp:40-52 to_proto).
    `wire_dtype` selects the payload encoding (messages.WIRE_*); the default
    is the reference-compatible packed repeated-float.  ``topk_density``
    applies to the WIRE_TOPK encoding only (fraction of entries kept)."""
    return [Tensor.from_array(name, np.asarray(arr), wire_dtype=wire_dtype,
                              topk_density=topk_density)
            for name, arr in store.items()]


def from_wire(tensors: Iterable[Tensor]) -> TensorStore:
    """Wire messages -> store (reference: src/worker.cpp:54-66 from_proto)."""
    return {t.name: t.to_array() for t in tensors}


def tree_like(store: Mapping[str, np.ndarray]) -> TensorStore:
    return {k: np.asarray(v, np.float32) for k, v in store.items()}


def num_params(store: Mapping[str, np.ndarray]) -> int:
    return sum(int(np.asarray(v).size) for v in store.values())


def store_nbytes(store: Mapping[str, np.ndarray]) -> int:
    """Total payload bytes of a store WITHOUT copying device-resident
    arrays to host (``.size``/``.itemsize`` are metadata on numpy and jax
    arrays alike).  Used for the PS gradient-buffer accounting
    (core/ps_core.py)."""
    total = 0
    for v in store.values():
        itemsize = getattr(v, "itemsize", None)
        if itemsize is None:
            itemsize = np.dtype(getattr(v, "dtype", np.float32)).itemsize
        total += int(v.size) * int(itemsize)
    return total


def flat_concat(store: Mapping[str, np.ndarray]) -> np.ndarray:
    """Concatenate all tensors into one flat float32 vector (stable order)."""
    if not store:
        return np.zeros((0,), np.float32)
    return np.concatenate([np.asarray(v, np.float32).reshape(-1)
                           for v in store.values()])


def unflatten_like(flat: np.ndarray, template: Mapping[str, np.ndarray]) -> TensorStore:
    """Inverse of :func:`flat_concat` given a template of shapes."""
    out: TensorStore = {}
    offset = 0
    for name, arr in template.items():
        arr = np.asarray(arr)
        n = int(arr.size)
        out[name] = np.asarray(flat[offset:offset + n], np.float32).reshape(arr.shape)
        offset += n
    return out
