#!/usr/bin/env python3
"""An experts layer's three grouped matmuls alone, run by hand on the chip
(``chiprun -- python3 scripts/experts_groups_micro.py [SHAPE ...]``): what a
TOUCHED expert costs and what a call costs whatever it touches, at the
shapes the serving cells' rounds run.  A SHAPE is a name of ``SHAPES``
(default: all): the experts' weights ``[E, D, F]`` / ``[E, F, D]`` in
bfloat16, the static rows of a round (slots x top_k, or slots x min(top_k,
held)) and the numbers of experts touched to time.  The rows that belong to
a touched expert lie first, sorted by expert, one row an expert and the
rest dealt at random among the touched; the rows behind them are zeros and
belong to no group (``moe.dropless_experts`` under ``live`` / ``held``).

REPS layers chained inside one program (gate and up from the rows, down
from their product, the result the next layer's rows), so a reading is
device time; the weights are one layer's, read again by every call (a v5e
keeps nothing of 0.75 GB between calls).  One JSON line a reading: ms a
layer (three calls), us a call, the bytes of the touched experts' matrices
and what they make of the HBM peak; last a line a shape with the least
squares fit ``us a call = floor + per_expert x touched``.  ``--rehearse``
tries the script itself on the CPU at a tiny size.  PERF.md (PR 60) has what
it read.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

REPS = 20
HBM_PEAK = 819e9     # bytes/s of one v5e chip (perfbench/peaks.py)
# name: (experts, d_model, expert width, static rows, experts touched)
SHAPES = {
    # serve_docs_chat_smallthinker: 16 slots x top-6 of 64
    "smallthinker": (64, 2560, 768, 96, (51, 39, 25, 12, 0)),
    # serve_manychat_lfm2_24b_a2b: 64 slots x top-4 of 64
    "lfm2": (64, 2048, 1536, 256, (60, 55, 40, 0)),
    # serve_chat_k_exaone_ep8: 32 lanes x top-8, 16 of 128 held
    "k_exaone": (16, 6144, 2048, 256, (16, 12, 8, 4, 0)),
}


def layer(rows, sizes, w1, w3, w2):
    """One gated experts layer over sorted ``rows`` [A, D]: the three
    grouped matmuls of ``moe.dropless_experts`` and nothing else."""
    dot = partial(jax.lax.ragged_dot, group_sizes=sizes,
                  preferred_element_type=jnp.float32)
    hidden = jax.nn.silu(dot(rows, w1).astype(rows.dtype)) * dot(
        rows, w3).astype(rows.dtype)
    return dot(hidden, w2)


@jax.jit
def chained(rows, sizes, w1, w3, w2):
    """REPS layers, each one's result the next one's rows (rows of no group
    stay zeros: what a grouped matmul leaves there is masked as the model
    masks it)."""
    mine = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]

    def body(_, rows):
        out = layer(rows, sizes, w1, w3, w2)
        return jnp.where(mine, rows + (1e-3 * out).astype(rows.dtype), 0)
    return jax.lax.fori_loop(0, REPS, body, rows)


def timed(fn, args, repeats: int = 5) -> float:
    """ms a layer."""
    times = []
    for _ in range(repeats + 1):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times[1:]) / REPS * 1e3


def group_sizes(rng, experts: int, rows: int, touched: int,
                live_rows: int) -> np.ndarray:
    """``live_rows`` rows over ``touched`` of the experts: one each, the
    rest at random among them."""
    sizes = np.zeros(experts, np.int32)
    if touched:
        chosen = rng.permutation(experts)[:touched]
        sizes[chosen] = 1
        extra = rng.integers(0, touched, max(live_rows - touched, 0))
        np.add.at(sizes, chosen[extra], 1)
    assert sizes.sum() <= rows
    return sizes


def main() -> None:
    args = sys.argv[1:]
    rehearse = "--rehearse" in args
    names = [a for a in args if a != "--rehearse"] or list(SHAPES)
    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        raise SystemExit("the micro-program times the chip: no TPU here")
    rng = np.random.default_rng(0)
    for name in names:
        experts, d, f, rows, touches = SHAPES[name]
        if rehearse:
            d, f = 128, 64
        keys = jax.random.split(jax.random.key(1), 4)
        w1, w3 = (jax.random.normal(k, (experts, d, f), jnp.bfloat16)
                  * d ** -0.5 for k in keys[:2])
        w2 = jax.random.normal(keys[2], (experts, f, d),
                               jnp.bfloat16) * f ** -0.5
        x = jax.random.normal(keys[3], (rows, d), jnp.bfloat16)
        expert_bytes = 3 * d * f * 2
        readings = []
        for touched in touches:
            # as many rows as touch that many of E at random (E (1 - (1 -
            # 1/E)^n) = touched), at most all and at least one an expert
            share = min(touched / experts, 1 - 1e-9)
            live_rows = 0 if not touched else max(touched, min(rows, round(
                np.log1p(-share) / np.log1p(-1 / experts))))
            if touched == max(touches):
                live_rows = rows
            sizes = group_sizes(rng, experts, rows, touched, live_rows)
            mine = np.arange(rows) < sizes.sum()
            inputs = (jnp.where(jnp.asarray(mine)[:, None], x, 0),
                      jnp.asarray(sizes), w1, w3, w2)
            ms = timed(chained, inputs)
            moved = touched * expert_bytes
            readings.append((touched, ms * 1e3 / 3))
            print(json.dumps({
                "shape": name, "weights": [experts, d, f], "rows": rows,
                "live_rows": int(sizes.sum()), "touched": touched,
                "ms_a_layer": round(ms, 4),
                "us_a_call": round(ms * 1e3 / 3, 1),
                "touched_mb": round(moved / 1e6, 1),
                "hbm_peak_pct": round(moved / ms / 1e-3 / HBM_PEAK * 100, 1),
                "device": device.device_kind}), flush=True)
        t, us = np.array(readings).T
        per, floor = np.polyfit(t, us, 1)
        print(json.dumps({
            "shape": name, "fit": "us_a_call = floor + per_expert * touched",
            "floor_us": round(float(floor), 1),
            "per_expert_us": round(float(per), 2),
            "matrix_mb": round(d * f * 2 / 1e6, 2),
            "per_expert_at_hbm_peak_us": round(d * f * 2 / HBM_PEAK * 1e6, 2),
            "worst_residual_us": round(float(np.max(np.abs(
                floor + per * t - us))), 1)}), flush=True)


if __name__ == "__main__":
    main()
