"""Fused optimizer-update pallas kernels.

The PS update path (reference: the aggregation loop + `param -= avg_grad`
at src/parameter_server.cpp:40-91, single-threaded C++ over every element)
becomes one pallas pass per tensor: read param/grad (and slots), write the
updated values, all in VMEM-resident tiles — no intermediate HBM
round-trips between optimizer sub-ops.

Production caller: async_sgd.PallasOptimizer (the device-resident PS
optimizer selected via ``optimizer=pallas_sgd|pallas_momentum|pallas_adam``)
— see async_sgd/device_optimizer.py.

Hyperparameters that are constant for a run (lr, betas, eps) are
compile-time constants baked into the kernel; Adam's per-step bias
corrections change every update, so they enter as SMEM scalars — zero
recompiles across steps.

Arrays are processed as (rows, 128) tiles (padded as needed), streamed
through VMEM over a 1-D grid of ``BLOCK_ROWS``-row blocks so a tensor of any
size compiles.  On a CPU backend the kernels run in interpret mode
(ops/pallas.interpret_mode) so the same code path is tested there.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

LANE = 128
SUBLANE = 8
# Rows per grid step: a (1024, 128) f32 block is 512 KiB.  Adam streams
# four inputs and three outputs, each double-buffered by the pipeline:
# 7 x 2 x 512 KiB = 7 MiB, inside the 16 MiB of VMEM a kernel gets by
# default on every TPU generation.
BLOCK_ROWS = 1024


def _sgd_kernel(p_ref, g_ref, out_ref, *, lr: float):
    out_ref[:] = p_ref[:] - lr * g_ref[:]


def _momentum_kernel(p_ref, g_ref, vel_ref, p_out, vel_out, *, lr: float,
                     mu: float):
    v_new = mu * vel_ref[:] + g_ref[:]
    vel_out[:] = v_new
    p_out[:] = p_ref[:] - lr * v_new


def _adam_kernel(bc_ref, p_ref, g_ref, m_ref, v_ref, p_out, m_out, v_out, *,
                 lr: float, b1: float, b2: float, eps: float):
    # bc_ref (SMEM) holds the per-step bias corrections [1-b1^t, 1-b2^t] so
    # the kernel compiles once per shape, not once per step.
    bc1, bc2 = bc_ref[0], bc_ref[1]
    g = g_ref[:]
    m_new = b1 * m_ref[:] + (1.0 - b1) * g
    v_new = b2 * v_ref[:] + (1.0 - b2) * g * g
    m_out[:] = m_new
    v_out[:] = v_new
    p_out[:] = p_ref[:] - lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)


def _as_tiles(arr: jax.Array) -> tuple[jax.Array, int]:
    """Flatten + pad to a (rows, LANE) float32 tile layout whose row count
    is a sublane multiple and, past one block, a BLOCK_ROWS multiple."""
    flat = arr.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    rows = -(-n // LANE)
    rows = -(-rows // SUBLANE) * SUBLANE  # round rows to sublane multiple
    if rows > BLOCK_ROWS:
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    if rows * LANE != n:
        flat = jnp.pad(flat, (0, rows * LANE - n))
    return flat.reshape(rows, LANE), n


def _from_tiles(tiles: jax.Array, n: int, shape, dtype) -> jax.Array:
    return tiles.reshape(-1)[:n].reshape(shape).astype(dtype)


def _run(kernel, arrays: list[jax.Array], num_outputs: int,
         interpret: bool, scalars: jax.Array | None = None) -> list[jax.Array]:
    rows = arrays[0].shape[0]
    block_rows = min(rows, BLOCK_ROWS)
    block = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    in_specs = [block] * len(arrays)
    operands = list(arrays)
    if scalars is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        operands = [scalars] + operands
    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, LANE), jnp.float32)] * num_outputs,
        grid=(rows // block_rows,),
        in_specs=in_specs,
        out_specs=[block] * num_outputs,
        interpret=interpret,
    )(*operands)
    return list(out)


def fused_sgd(params: Mapping[str, jax.Array],
              grads: Mapping[str, jax.Array], lr: float,
              interpret: bool | None = None) -> dict[str, jax.Array]:
    """param <- param - lr * grad, one fused pass per tensor."""
    interpret = (interpret_mode(*params.values()) if interpret is None
                 else interpret)
    kernel = functools.partial(_sgd_kernel, lr=float(lr))
    out = {}
    for name, p in params.items():
        if name not in grads:
            out[name] = p
            continue
        tiles_p, n = _as_tiles(p)
        tiles_g, _ = _as_tiles(grads[name])
        (res,) = _run(kernel, [tiles_p, tiles_g], 1, interpret)
        out[name] = _from_tiles(res, n, np.shape(p), p.dtype)
    return out


def fused_momentum(params: Mapping[str, jax.Array],
                   grads: Mapping[str, jax.Array],
                   velocity: Mapping[str, jax.Array], lr: float,
                   mu: float = 0.9, interpret: bool | None = None):
    """Fused momentum SGD: returns (new_params, new_velocity)."""
    interpret = (interpret_mode(*params.values()) if interpret is None
                 else interpret)
    kernel = functools.partial(_momentum_kernel, lr=float(lr), mu=float(mu))
    new_p, new_v = {}, {}
    for name, p in params.items():
        if name not in grads:
            new_p[name], new_v[name] = p, velocity.get(name)
            continue
        tiles = [_as_tiles(x) for x in (p, grads[name], velocity[name])]
        n = tiles[0][1]
        res = _run(kernel, [t for t, _ in tiles], 2, interpret)
        new_p[name] = _from_tiles(res[0], n, np.shape(p), p.dtype)
        new_v[name] = _from_tiles(res[1], n, np.shape(p), jnp.float32)
    return new_p, new_v


def fused_adam(params: Mapping[str, jax.Array],
               grads: Mapping[str, jax.Array],
               m: Mapping[str, jax.Array], v: Mapping[str, jax.Array],
               step: int | jax.Array, lr: float = 1e-3, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               interpret: bool | None = None):
    """Fused Adam: returns (new_params, new_m, new_v).  ``step`` (1-based)
    may be a Python int or a traced scalar — bias corrections enter the
    kernel as SMEM data, so stepping never recompiles."""
    interpret = (interpret_mode(*params.values()) if interpret is None
                 else interpret)
    kernel = functools.partial(_adam_kernel, lr=float(lr), b1=float(b1),
                               b2=float(b2), eps=float(eps))
    step_f = jnp.asarray(step, jnp.float32)
    bc = jnp.stack([1.0 - jnp.float32(b1) ** step_f,
                    1.0 - jnp.float32(b2) ** step_f])
    new_p, new_m, new_v = {}, {}, {}
    for name, p in params.items():
        if name not in grads:
            new_p[name], new_m[name], new_v[name] = p, m.get(name), v.get(name)
            continue
        tiles = [_as_tiles(x) for x in (p, grads[name], m[name], v[name])]
        n = tiles[0][1]
        res = _run(kernel, [t for t, _ in tiles], 3, interpret, scalars=bc)
        new_p[name] = _from_tiles(res[0], n, np.shape(p), p.dtype)
        new_m[name] = _from_tiles(res[1], n, np.shape(p), jnp.float32)
        new_v[name] = _from_tiles(res[2], n, np.shape(p), jnp.float32)
    return new_p, new_m, new_v
