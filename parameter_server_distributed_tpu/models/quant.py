"""Weight-only int8 quantization for the serving path.

Decode is weight-bandwidth-bound: every generated token streams the full
parameter set from HBM while the MXU sits mostly idle, so halving the
weight bytes (bf16 -> int8) is worth up to 2x tokens/s before any compute
speedup.  This module quantizes a trained parameter store offline
(:func:`quantize_params`) into :class:`QTensor` leaves — symmetric int8
with a per-output-channel f32 scale — that flow through the existing
model code transparently:

- ``QTensor`` is a registered JAX pytree, so quantized stores pass through
  ``jit``/``lax.scan`` (the ``scan_layers`` stacked layout) unchanged, and
  ``layer_view``'s per-layer ``value[layer]`` slicing works via
  ``__getitem__``.
- The transformer's matmul sites call :func:`wdot`, which contracts
  activations against the int8 matrix (the int8->bf16 convert fuses into
  the matmul, so only int8 bytes leave HBM) and applies the channel scale
  to the product.

Scope: the dense transformer serving path (attention + MLP + LM head).
Embeddings stay bf16 (a gather, not a matmul: int8 would add a dequant
pass without saving matmul bandwidth), norms/biases stay f32, and MoE
expert banks are out of scope for now (their einsum paths live in
models/moe.py; the router is a tiny f32 matmul either way).  Training on
quantized weights is deliberately unsupported — this is a post-training
serving transform.

The reference has no quantized path (its tensors are ``repeated float``
f32 end to end — reference proto/parameter_server.proto:19-24); this is
TPU-native added capability (``pst-serve --quant=int8``); its speed
against the bf16 decoder is not measured on the chip.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

Array = Any

# Matmul-weight key suffixes eligible for quantization, in both layouts
# (unrolled "layer<i>/attn/wq" and scan_layers' stacked "blocks/attn/wq").
_WEIGHT_SUFFIXES = ("/attn/wq", "/attn/wk", "/attn/wv", "/attn/wo",
                    "/mlp/w1", "/mlp/w2", "/mlp/w3")


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Symmetric weight-only int8 matrix.

    ``q``: int8, shape [..., d_in, d_out] (leading axes = stacked layers).
    ``scale``: f32, shape [..., d_out] — per-output-channel absmax/127 over
    the contracted (d_in) axis, so dequant is ``q * scale`` broadcast over
    d_in and a matmul against q can apply the scale to its product instead.
    """

    __slots__ = ("q", "scale")

    def __init__(self, q: Array, scale: Array):
        self.q = q
        self.scale = scale

    @property
    def shape(self) -> tuple:
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim

    def __getitem__(self, idx) -> "QTensor":
        # layer_view slices stacked [L, ...] params per layer; slice the
        # scale with the same leading index.
        return QTensor(self.q[idx], self.scale[idx])

    def dequant(self, dtype=jnp.float32) -> Array:
        return (self.q.astype(dtype)
                * self.scale[..., None, :].astype(dtype))

    # --- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self) -> str:
        return f"QTensor(int8 {tuple(self.q.shape)})"


def quantize(w: Array) -> QTensor:
    """Symmetric per-output-channel int8 quantization of a weight matrix
    [..., d_in, d_out] (absmax over the contracted d_in axis)."""
    w32 = jnp.asarray(w, jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2)              # [..., d_out]
    scale = absmax / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)          # all-zero channel
    q = jnp.round(w32 / scale[..., None, :])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return QTensor(q, scale.astype(jnp.float32))


def wdot(x: Array, w: Array | QTensor, *,
         preferred_element_type=jnp.float32) -> Array:
    """``jnp.dot`` that understands QTensor weights: contracts against the
    int8 matrix (the convert-to-activation-dtype fuses into the matmul, so
    HBM streams int8 bytes) and scales the f32 product per channel."""
    if isinstance(w, QTensor):
        y = jnp.dot(x, w.q.astype(x.dtype),
                    preferred_element_type=preferred_element_type)
        return y * w.scale.astype(y.dtype)
    return jnp.dot(x, w, preferred_element_type=preferred_element_type)


def _eligible(name: str, value: Array) -> bool:
    if name == "lm_head/w":
        return True
    return (any(name.endswith(suffix) for suffix in _WEIGHT_SUFFIXES)
            and getattr(value, "ndim", 0) >= 2)


def quantize_params(params: Mapping[str, Array]) -> dict[str, Array]:
    """Quantize a trained store for serving: matmul weights (attention,
    MLP, LM head — both layer layouts) become QTensor; embeddings, norm
    scales, and MoE tensors pass through unchanged."""
    return {name: quantize(value) if _eligible(name, value) else value
            for name, value in params.items()}


def store_bytes(params: Mapping[str, Array],
                unquantized_itemsize: int = 2) -> tuple[int, int]:
    """(bytes_as_is, bytes_had_nothing_been_quantized) for a store that may
    hold QTensor leaves — the decode-bandwidth story in one pair of
    numbers.  ``unquantized_itemsize`` is what a QTensor's weight would
    have weighed per element unquantized (2 = bf16 serving weights)."""
    as_is = dense = 0
    for value in params.values():
        if isinstance(value, QTensor):
            nq = int(value.q.size)
            as_is += nq + int(value.scale.size) * 4
            dense += nq * unquantized_itemsize
        else:
            b = int(value.size) * value.dtype.itemsize
            as_is += b
            dense += b
    return as_is, dense
