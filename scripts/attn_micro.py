#!/usr/bin/env python3
"""Micro-programs for the training attention, run by hand on the chip
(``chiprun -- python3 scripts/attn_micro.py``): LAYERS layers of causal
attention, forward + backward under ``jax.checkpoint``, for each arm a
device can take (``models/transformer.device_arm``: ``kernel``,
``blockwise``, ``dense``) at the cells' shapes; forward only at the serving
shapes; the kernel's output and gradients against the einsum's, computed
on the chip; and, on several chips (``chiprun --chips 4 -- python3
scripts/attn_micro.py seq``), ``ring`` against ``ulysses`` over a ``seq``
axis of all of them.  One JSON line per reading.  PERF.md (PR 30) has what
it read of the arms and of the contenders that went in PR 46.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_distributed_tpu.config import MeshConfig
from parameter_server_distributed_tpu.models.transformer import (
    attend_by, causal_attention, select_attention)
from parameter_server_distributed_tpu.ops.pallas.fused_attention import (
    fused_causal_attention)
from parameter_server_distributed_tpu.parallel.mesh import build_mesh

LAYERS = 24


def arm(name: str):
    return lambda q, k, v: attend_by(name, q, k, v)


def stack(attend, train: bool, groups: int):
    """LAYERS layers, each fed the last one's output as q (and its first
    heads as k and v), so nothing can be hoisted."""
    def layer(h, _):
        kv = h[:, :, ::groups]
        return attend(h, kv, kv * 0.5).astype(h.dtype), None

    def forward(h):
        body = jax.checkpoint(layer) if train else layer
        out, _ = jax.lax.scan(body, h, None, length=LAYERS)
        return jnp.sum(out.astype(jnp.float32))

    return jax.jit(jax.value_and_grad(forward) if train else forward)


def timed(fn, *args, repeats: int = 5):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return float(np.median(times)), float(min(times))


def say(**fields):
    print(json.dumps(fields), flush=True)


def main():
    device = jax.devices()[0]
    say(platform=device.platform, device_kind=device.device_kind,
        layers=LAYERS)
    # arguments: the sections to run (all of one chip's where none is
    # given), and after "--" the arms of the training section
    argv = sys.argv[1:]
    cut = argv.index("--") if "--" in argv else len(argv)
    sections = set(argv[:cut]) or {"train", "small", "against", "prefill"}
    only = set(argv[cut + 1:])
    train_shapes = [(64, 1024, 16, 16, 64), (32, 1024, 10, 10, 64),
                    (2, 256, 16, 16, 64)]
    contenders = {name: arm(name) for name in ("dense", "kernel",
                                               "blockwise")}
    # where the kernel overtakes the einsum as the step shrinks
    small = [(b, s, 16, 16, 64) for b, s in (
        (4, 256), (16, 256), (64, 256), (4, 512), (16, 512), (2, 1024),
        (8, 1024))]
    shapes = ((train_shapes if "train" in sections else [])
              + (small if "small" in sections else []))
    for b, s, h, kv, d in shapes:
        key = jax.random.key(b)
        x = jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
        for name, attend in contenders.items():
            if only and name not in only:
                continue
            if (b, s, h, kv, d) in small and name == "blockwise":
                continue
            try:
                median, best = timed(stack(attend, True, h // kv), x)
                say(kind="train", shape=[b, s, h, kv, d], contender=name,
                    median_ms=1e3 * median, min_ms=1e3 * best)
            except Exception as exc:      # a contender that does not lower
                say(kind="train", shape=[b, s, h, kv, d], contender=name,
                    error=str(exc)[:300])
    # the kernel against the einsum on the chip: output and gradients
    for b, s, h, kv, d in [(2, 256, 16, 16, 64), (8, 1024, 16, 16, 64),
                           (2, 512, 28, 4, 128), (2, 256, 8, 4, 64)
                           ] if "against" in sections else []:
        keys = jax.random.split(jax.random.key(s + h), 4)
        q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, s, kv, d), jnp.bfloat16)
        w = jax.random.normal(keys[3], (b, s, h, d), jnp.float32)

        def both(attend):
            def loss(q, k, v):
                out = attend(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out
            (_, out), grads = jax.jit(jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True))(q, k, v)
            return (out, *grads)

        exact = both(lambda q, k, v: causal_attention(
            *(x.astype(jnp.float32) for x in (q, k, v))))
        for name, attend in (("dense", causal_attention),
                             ("kernel", fused_causal_attention)):
            got = both(attend)
            say(kind="against_float32", shape=[b, s, h, kv, d],
                contender=name, **{
                    part: float(jnp.linalg.norm(
                        (a.astype(jnp.float32) - e).ravel())
                        / jnp.linalg.norm(e.ravel()))
                    for part, a, e in zip(("out", "dq", "dk", "dv"), got,
                                          exact)})
    # serving prefill: forward only, batch 1, what the default runs today
    for b, s, h, kv, d in [(1, 256, 16, 16, 64), (1, 512, 16, 16, 64),
                           (1, 1024, 16, 16, 64), (1, 256, 28, 4, 128),
                           (1, 1024, 28, 4, 128), (1, 2048, 28, 4, 128),
                           (1, 4096, 28, 4, 128), (1, 16384, 28, 4, 128)
                           ] if "prefill" in sections else []:
        x = jax.random.normal(jax.random.key(s), (b, s, h, d), jnp.bfloat16)
        variants = {"today": arm("dense" if s < 2048 else "blockwise"),
                    "kernel": arm("kernel")}
        for name, attend in variants.items():
            try:
                median, best = timed(stack(attend, False, h // kv), x)
                say(kind="prefill", shape=[b, s, h, kv, d], contender=name,
                    median_ms=1e3 * median, min_ms=1e3 * best)
            except Exception as exc:
                say(kind="prefill", shape=[b, s, h, kv, d], contender=name,
                    error=str(exc)[:300])
    # the two ways over a seq axis (ROADMAP R3): every chip of the host
    # on it, LAYERS layers forward + backward, the whole arrays' shapes
    if "seq" in sections and jax.device_count() > 1:
        mesh = build_mesh(MeshConfig(sequence=jax.device_count()))
        for b, s, h, kv, d in [(4, 4096, 16, 16, 64), (1, 16384, 16, 16, 64),
                               (1, 16384, 28, 4, 128)]:
            x = jax.random.normal(jax.random.key(s), (b, s, h, d),
                                  jnp.bfloat16)
            for name in ("ring", "ulysses"):
                try:
                    with mesh:
                        median, best = timed(stack(
                            select_attention(name, mesh), True, h // kv), x)
                    say(kind="seq", shape=[b, s, h, kv, d], contender=name,
                        median_ms=1e3 * median, min_ms=1e3 * best)
                except Exception as exc:
                    say(kind="seq", shape=[b, s, h, kv, d], contender=name,
                        error=str(exc)[:300])


if __name__ == "__main__":
    main()
