#!/usr/bin/env python3
"""An ssm layer's decode round alone, run by hand on the chip (``chiprun --
python3 scripts/ssd_decode_micro.py [SHARE ...] [-- HEADS ...]``): one
layer's single position a lane over the matrix ``[64, 64, 64, 128]`` float32
of ``serve_manychat_granite_4_h_micro`` (134 MB), the lanes that hold a
request scattered over the slots at each live SHARE (per cent; default 100
59 37 0), through each arm of ``models/transformer.round_arm``'s ``ssm``
kind: ``plain`` (``ops/ssd._one_position``: XLA's one fusion over every
lane's matrix, idle lanes' steps zero) and ``kernel``
(ops/pallas/ssd_decode.py), the kernel at each number of heads a grid step
named after ``--`` (default, and 0: the module's own rule,
``heads_a_step``; ``64:16`` also sets ``UNROLL``, the heads of a step the
body writes out).  REPS calls chained inside one program (the state is
carried where it lies and a call's result feeds the next one's values), so
a reading is device time.  One JSON line a reading: ms a layer, the bytes
of the matrices the arm moves (both ways) and what they make of the HBM
peak, the largest difference between the arms.  ``--rehearse`` tries the
script itself on the CPU at a tiny size.  PERF.md (PR 58) has what it read.
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

from parameter_server_distributed_tpu.ops import ssd
from parameter_server_distributed_tpu.ops.pallas import ssd_decode

REPS = 20
UNROLL = ssd_decode.UNROLL
HBM_PEAK = 819e9     # bytes/s of one v5e chip (perfbench/peaks.py)
SHAPE = (64, 64, 64, 128)     # lanes, heads, a head's values, the state
GROUPS = 1


def plain(written, fall, b, c, state, live):
    """``ops/ssd.py``'s single position: an idle lane's step is zero."""
    batch, heads, dim, width = state.shape
    by_group = (GROUPS, heads // GROUPS)
    y, state = ssd._one_position(
        jnp.where(live[:, None, None], written, 0.0).reshape(
            batch, *by_group, dim),
        jnp.where(live[:, None], fall, 0.0).reshape(batch, *by_group), b, c,
        state.reshape(batch, *by_group, dim, width))
    return y.reshape(batch, heads, dim), state.reshape(batch, heads, dim,
                                                       width)


def kernel(heads):
    def run(written, fall, b, c, state, live):
        return ssd_decode.ssd_decode(written, jnp.exp(fall), b, c, state,
                                     live, heads=heads or None)
    return run


def chained(step):
    """REPS calls: the state carried, each y the next call's values."""
    def run(written, fall, b, c, state, live):
        def body(_, carry):
            written, state = carry
            y, state = step(written, fall, b, c, state, live)
            return written + 1e-3 * y, state
        return jax.lax.fori_loop(0, REPS, body, (written, state))
    return jax.jit(run, donate_argnums=(4,))


def timed(fn, args, repeats: int = 3) -> float:
    """ms a call of ``fn``, whose state (argument 4) is donated and comes
    back as the second result."""
    args = list(args)
    times = []
    for _ in range(repeats + 1):
        t0 = time.perf_counter()
        _, args[4] = jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times[1:]) / REPS * 1e3


def main() -> None:
    args = sys.argv[1:]
    rehearse = "--rehearse" in args
    args = [a for a in args if a != "--rehearse"]
    blocks = ["0"]
    if "--" in args:
        blocks = args[args.index("--") + 1:]
        args = args[:args.index("--")]
    device = jax.devices()[0]
    if device.platform != "tpu" and not rehearse:
        raise SystemExit("the micro-program times the chip: no TPU here")
    shape = (4, 8, 8, 128) if rehearse else SHAPE
    lanes, heads, dim, width = shape
    keys = jax.random.split(jax.random.key(1), 5)
    written = jax.random.normal(keys[0], shape[:3], jnp.float32)
    fall = -jnp.abs(jax.random.normal(keys[1], shape[:2], jnp.float32))
    b, c = (jax.random.normal(key, (lanes, GROUPS, width), jnp.float32)
            for key in keys[2:4])
    lane_bytes = 2 * heads * dim * width * 4          # in and out
    rng = np.random.default_rng(0)
    for share in [float(a) for a in args] or [100.0, 59.0, 37.0, 0.0]:
        n_live = round(share / 100 * lanes)
        live = np.zeros(lanes, bool)
        live[rng.permutation(lanes)[:n_live]] = True
        live = jnp.asarray(live)

        def state():
            return jax.random.normal(keys[4], shape, jnp.float32)

        inputs = (written, fall, b, c)
        base = {"matrix": list(shape), "live_lanes": n_live,
                "device": device.device_kind}
        want_y, want = jax.jit(plain)(*inputs, state(), live)
        ms = timed(chained(plain), (*inputs, state(), live))
        print(json.dumps(dict(
            base, arm="plain", ms=round(ms, 4),
            moved_mb=round(lanes * lane_bytes / 1e6, 1),
            moved_gb_s=round(lanes * lane_bytes / ms / 1e6, 1),
            hbm_peak_pct=round(
                lanes * lane_bytes / ms / 1e-3 / HBM_PEAK * 100, 1))),
            flush=True)
        for named in blocks:
            step, _, unroll = named.partition(":")
            step = int(step)
            if step and heads % step:
                continue
            ssd_decode.UNROLL = int(unroll or UNROLL)
            jax.clear_caches()
            got_y, got = jax.jit(kernel(step))(*inputs, state(), live)
            idle = ~np.asarray(live)
            ms = timed(chained(kernel(step)), (*inputs, state(), live))
            moved = n_live * lane_bytes
            print(json.dumps(dict(
                base, arm="kernel", unroll=ssd_decode.UNROLL,
                heads=step or ssd_decode.heads_a_step(heads,
                                                      dim * width * 4),
                ms=round(ms, 4), moved_mb=round(moved / 1e6, 1),
                moved_gb_s=round(moved / ms / 1e6, 1),
                hbm_peak_pct=round(moved / ms / 1e-3 / HBM_PEAK * 100, 1),
                max_diff_state=float(jnp.max(jnp.abs(got - want))),
                max_diff_y=float(jnp.max(jnp.abs(
                    jnp.where(live[:, None, None], got_y - want_y, 0.0)))),
                idle_unchanged=bool(np.array_equal(
                    np.asarray(got)[idle], np.asarray(state())[idle])),
                idle_y_zero=bool(np.all(np.asarray(got_y)[idle] == 0)))),
                flush=True)


if __name__ == "__main__":
    main()
