#!/usr/bin/env python3
"""A decode round's full attention alone, run by hand on the chip
(``chiprun -- python3 scripts/full_decode_micro.py [cell ...] [-- BLOCK
...]``): one layer's single token a lane against K and V parts at the
serving cells' shapes, the lanes filled as the cells' traffic fills them,
through each arm of ``models/transformer.round_arm``'s ``softmax`` kind: ``dense`` (the
two einsums over the whole parts) and ``kernel``
(ops/pallas/full_decode.py), the kernel at each block size named after
``--`` (default, and 0: the module's own rule, ``block_positions``;
``256:2097152`` also sets ``STEP_BYTES``).  REPS calls chained inside one program (the
result feeds the next query), so a reading is device time.  One JSON line a
reading: ms a call, the bytes the kernel fetches and what they make of the
HBM peak, the largest difference between the arms.  ``--rehearse`` tries
the script itself on the CPU at a tiny size.  PERF.md (PR 55) has what it
read.
"""

from __future__ import annotations

import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_distributed_tpu.models import generation
from parameter_server_distributed_tpu.ops.pallas import full_decode

REPS = 20
HBM_PEAK = 819e9     # bytes/s of one v5e chip (perfbench/peaks.py)
# cell: (lanes, max_len, kv_heads, head_dim, query heads a K/V head, the
# lanes' lengths as (low, high) a share of the lanes each)
CELLS = {
    "olmo": (12, 4096, 30, 128, 1, [(600, 2600, 1.0)]),
    "exaone": (32, 4096, 8, 128, 8, [(200, 1800, 1.0)]),
    "smallthinker": (16, 16384, 4, 128, 7, [(12300, 12900, 0.4),
                                            (300, 1200, 0.6)]),
    "lfm2": (64, 4096, 8, 64, 4, [(200, 1500, 1.0)]),
    "gpt2": (32, 1024, 16, 64, 1, [(100, 400, 1.0)]),
}


def lengths_of(lanes: int, mix, rng) -> np.ndarray:
    out = []
    for low, high, share in mix:
        out += list(rng.integers(low, high, max(1, round(share * lanes))))
    return np.asarray((out * 2)[:lanes], np.int32)


def chained(attend):
    """REPS calls, each one's result the next one's query."""
    def run(q, k, v, n):
        def body(_, q):
            return (q + 1e-3 * attend(q, k, v, n)).astype(q.dtype)
        return jax.lax.fori_loop(0, REPS, body, q)
    return jax.jit(run)


def timed(fn, *args, repeats: int = 3) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times) / REPS * 1e3


def main() -> None:
    args = sys.argv[1:]
    rehearse = "--rehearse" in args
    args = [a for a in args if a != "--rehearse"]
    blocks = ["0"]
    if "--" in args:
        blocks = args[args.index("--") + 1:]
        args = args[:args.index("--")]
    rule = full_decode.block_positions
    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        raise SystemExit("the micro-program times the chip: no TPU here")
    rng = np.random.default_rng(0)
    for name in args or list(CELLS):
        lanes, max_len, kv_heads, head_dim, groups, mix = CELLS[name]
        if rehearse:
            lanes, max_len = 2, 1024
            mix = [(1, max_len, 1.0)]
        pack = generation.heads_per_row(kv_heads, head_dim)
        c = types.SimpleNamespace(kv_heads=kv_heads, kv_groups=groups,
                                  head_dim=head_dim,
                                  n_heads=kv_heads * groups,
                                  dtype=jnp.bfloat16)
        keys = jax.random.split(jax.random.key(1), 3)
        shape = (lanes, max_len, kv_heads // pack, pack * head_dim)
        q = jax.random.normal(keys[0], (lanes, 1, c.n_heads, head_dim),
                              c.dtype)
        k = jax.random.normal(keys[1], shape, c.dtype)
        v = jax.random.normal(keys[2], shape, c.dtype)
        n = jnp.asarray(lengths_of(lanes, mix, rng))
        row = 2 * shape[2] * shape[3] * 2          # K and V of a position
        base = {"cell": name, "part": list(shape), "pack": pack,
                "by_head": generation._lies_by_head(shape[2]),
                "live_pct": round(float(n.sum()) / lanes / max_len * 100, 2),
                "device": device.device_kind}

        def dense(q, k, v, n):
            mask = (jnp.arange(max_len)[None, :]
                    < n[:, None])[:, None, None, None, :]
            return generation._dense_cache_attention(c, q, k, v, mask, None)

        def kernel(q, k, v, n):
            return generation._kernel_cache_attention(c, q, k, v, n)

        want = jax.jit(dense)(q, k, v, n)
        ms = timed(chained(dense), q, k, v, n)
        print(json.dumps(dict(base, arm="dense", ms=round(ms, 4), gb_s=round(
            lanes * max_len * row / ms / 1e6, 1))), flush=True)
        for named in blocks:
            # (0: the module's own rule; 256:2097152 also sets STEP_BYTES)
            block, _, step_bytes = named.partition(":")
            block = int(block)
            full_decode.STEP_BYTES = int(step_bytes or 1 << 20)
            full_decode.block_positions = (
                rule if not block else lambda *_, block=block: block)
            block = block or rule(shape, 2, base["by_head"],
                                  c.n_heads // shape[2])
            if max_len % block:
                continue
            got = jax.jit(kernel)(q, k, v, n)
            ms = timed(chained(kernel), q, k, v, n)
            fetched = int((-(-np.asarray(n) // block) * block).sum()) * row
            print(json.dumps(dict(
                base, arm="kernel", block=named, ms=round(ms, 4),
                fetched_mb=round(fetched / 1e6, 1),
                read_pct=round(fetched / (lanes * max_len * row) * 100, 2),
                fetched_gb_s=round(fetched / ms / 1e6, 1),
                hbm_peak_pct=round(fetched / ms / 1e-3 / HBM_PEAK * 100, 1),
                max_diff=float(jnp.max(jnp.abs(
                    got.astype(jnp.float32) - want.astype(jnp.float32)))))),
                flush=True)


if __name__ == "__main__":
    main()
