#!/usr/bin/env python3
"""Start-up proof on the accelerator: one PS round, one SPMD training run,
one decode-server session and the pallas kernels, each driven once through
the entry points a user calls.

    python chip_smoke.py
        on the chip: `lm_350m_hd128` at its full width and depth (24 layers,
        d_model 1024, 8 heads of 128, d_ff 4096, vocab 32000, seq 1024,
        bf16, scan + remat), random weights from a seed.
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny
        the same phases on `small_lm` with pallas in interpret mode, to debug
        the control flow off the chip.

One process, which owns the chip: coordinator, parameter server and worker
run in it as threads.  The script sets no platform.  It takes the devices
JAX gives it and exits non-zero at once when they are not a TPU (only
``--tiny`` lifts that).  Each phase prints one JSON line; the first phase
that fails ends the run with its exception and a non-zero exit.  The last
line of standard output is ``{"ok": true, "device": {...}}``.

``--phases a,b`` runs a subset (for debugging one phase on the chip).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import unittest.mock

import numpy as np

SEED = 0


@dataclasses.dataclass(frozen=True)
class Sizes:
    model: str
    batch: int            # PS worker and SPMD global batch
    serve_max_len: int
    prompt_len: int
    shared_len: int       # tokens the last prompt shares with the first
    max_new: int
    attn_batch: int
    attn_seq: int
    attn_dtype: str       # interpret mode on the CPU checks float32
    fused_shape: tuple    # the tensor the fused updates run on


FULL = Sizes(model="lm_350m_hd128", batch=8, serve_max_len=1024,
             prompt_len=128, shared_len=96, max_new=32,
             attn_batch=4, attn_seq=1024, attn_dtype="bfloat16",
             fused_shape=(32000, 1024))
# fused_shape stays above one BLOCK_ROWS x 128 block so the grid runs
TINY = Sizes(model="small_lm", batch=8, serve_max_len=256,
             prompt_len=32, shared_len=24, max_new=8,
             attn_batch=1, attn_seq=256, attn_dtype="float32",
             fused_shape=(300, 1024))

# [heads, kv_heads, head_dim]: lm_350m_hd128, lm_350m, and the GQA fold
ATTENTION_SHAPES = ((8, 8, 128), (16, 16, 64), (16, 4, 64))

# Stated tolerances.  Attention is compared as max|x - ref| / max|ref|
# against a float32 dense reference: bf16 keeps 8 bits of mantissa and the
# kernels accumulate in f32, so 3e-2 leaves room for a few roundings.
ATTN_TOL = {"bfloat16": 3e-2, "float32": 1e-4}
# Fused updates are f32 elementwise with operands of order one:
# max|x - ref| within a few f32 ulps.
FUSED_ATOL = 2e-6
# One device Adam close against core/optimizer.py's numpy Adam: one
# thousandth of an lr-sized step (divide and sqrt are not correctly
# rounded on the chip, so bit equality is not asked for).
ADAM_LR = 1e-3
ADAM_CLOSE_ATOL = 1e-3 * ADAM_LR
# A served token that differs from `generate` must be a near-tie: within
# this many standard deviations of that position's logits from the best
# logit of a teacher-forced dense forward.  Random weights make greedy
# decoding hover on near-ties, and in bf16 the server's programs (prefix
# extension, 8-slot batch, 1024-long cache) round differently from
# generate's; on the CPU in float32 the two are token-exact.
SERVE_NEAR_TIE = 0.02


class Run:
    """What every phase needs: sizes, the device description that goes into
    every output line, and the compile log."""

    def __init__(self, sizes: Sizes):
        import jax

        self.sizes = sizes
        self.devices = jax.devices()
        first = self.devices[0]
        self.device = {"platform": first.platform,
                       "device_kind": first.device_kind,
                       "device_count": len(self.devices)}
        self._compiles: list[tuple[float, float]] = []   # (wall time, secs)
        self._cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles.append((time.time(), seconds))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._cache_hits += 1

    def compile_mark(self) -> tuple[int, int]:
        return len(self._compiles), self._cache_hits

    def compiles_since(self, mark: tuple[int, int]) -> dict:
        events = self._compiles[mark[0]:]
        return {"compile_s": round(sum(s for _, s in events), 3),
                "compiles": len(events),
                "cache_hits": self._cache_hits - mark[1]}

    def compiles_after(self, wall_time: float) -> int:
        return sum(1 for t, _ in self._compiles if t > wall_time)

    def emit(self, **fields) -> None:
        print(json.dumps({**fields, **self.device}), flush=True)

    def meshes(self):
        """(worker --mesh spec, SPMD MeshConfig, serving MeshConfig or None)
        covering every visible device."""
        from parameter_server_distributed_tpu.config import MeshConfig

        n = len(self.devices)
        if n == 1:
            return "", MeshConfig(), None
        tensor = 2 if n % 2 == 0 else 1
        return (f"fsdp:{n}", MeshConfig(fsdp=n // tensor, tensor=tensor),
                MeshConfig(data=n // tensor, tensor=tensor))


class MemorySampler:
    """Largest ``bytes_in_use`` each device reports while a phase runs
    (``peak_bytes_in_use`` covers the whole process, so it cannot show that
    THIS phase put bytes on every device)."""

    def __init__(self, devices):
        self._devices = devices
        self._max = [0] * len(devices)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True,
                                        name="chip-smoke-memory")

    def _poll(self) -> None:
        while not self._stop.wait(0.05):
            self._sample()

    def _sample(self) -> None:
        for i, device in enumerate(self._devices):
            stats = device.memory_stats()
            if stats:
                self._max[i] = max(self._max[i], stats["bytes_in_use"])

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self._sample()

    def report(self) -> dict:
        """Per-device bytes, and the check that each device held a share
        (skipped where the backend reports no memory statistics)."""
        lifetime = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in self._devices]
        out = {"peak_bytes_in_use": lifetime}
        if all(v is not None for v in lifetime):
            out["phase_max_bytes_in_use"] = self._max
            if min(self._max) <= 0:
                raise AssertionError(
                    f"a device held no bytes during the phase: {self._max}")
        return out


COUNTERS = ("rpc.shm.bytes", "rpc.shm.fallback", "ps.apply.device",
            "ps.apply.arena", "ps.apply.arena_fallback")


def _counters() -> dict:
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    counters = obs_stats.REGISTRY.snapshot()["counters"]
    return {name: counters.get(name, 0) for name in COUNTERS}


def _axes(mesh_config) -> dict | str:
    """The mesh axes wider than one, for the output line."""
    axes = mesh_config.axis_sizes if mesh_config else {}
    return {k: v for k, v in axes.items() if v > 1} or "one device"


def _max_abs_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


# ------------------------------------------------------------ PS rounds
def _ps_round(run: Run, *, optimizer: str, learning_rate: float,
              grad_iterations: int, device_close: bool) -> dict:
    """Coordinator + parameter server + one worker in this process, as
    pst-coordinator / pst-parameter-server / pst-worker assemble them."""
    from parameter_server_distributed_tpu import native
    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.config import (
        CoordinatorConfig, ParameterServerConfig, WorkerConfig)
    from parameter_server_distributed_tpu.obs import stats as obs_stats
    from parameter_server_distributed_tpu.rpc.codec import active_codec
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    mark = run.compile_mark()
    checkpoint_dir = tempfile.mkdtemp(prefix="chip-smoke-ps-")
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=checkpoint_dir, optimizer=optimizer,
        learning_rate=learning_rate, autosave_period_s=3600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    coordinator_port = coordinator.start()
    worker_mesh, _, _ = run.meshes()
    worker = build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{coordinator_port}", worker_id=0,
        iterations=1 + grad_iterations, model=run.sizes.model,
        batch_size=run.sizes.batch, scan_layers=True, fused_step=True,
        heartbeat_period_s=3600.0, mesh=worker_mesh))
    checked: dict = {"optimizer": optimizer, "learning_rate": learning_rate,
                     "worker_mesh": worker_mesh or "none"}
    try:
        with MemorySampler(run.devices) as memory:
            worker.initialize()
            versions = [ps.core.params_version]
            worker.run_iteration(0)                     # bootstrap seed
            versions.append(ps.core.params_version)
            t0 = time.perf_counter()
            losses = [worker.run_iteration(1)]          # compiles the step
            first_s = time.perf_counter() - t0
            versions.append(ps.core.params_version)
            before = _counters()
            t0 = time.perf_counter()
            for iteration in range(2, 1 + grad_iterations):
                losses.append(worker.run_iteration(iteration))
                versions.append(ps.core.params_version)
            steady_s = time.perf_counter() - t0
            after = _counters()

            if not all(math.isfinite(loss) for loss in losses):
                raise AssertionError(f"loss not finite: {losses}")
            if any(b - a != 1 for a, b in zip(versions, versions[1:])):
                raise AssertionError(
                    f"store version did not advance once per close: "
                    f"{versions}")
            init = worker.trainer.init_params(seed=0)
            _, pulled = worker.pull_parameters(1 + grad_iterations)
            if set(pulled) != set(init):
                raise AssertionError("pulled store misses tensors")
            moved = max(_max_abs_diff(pulled[name], init[name])
                        for name in init)
            if not moved > 0:
                raise AssertionError("pulled store equals the init")
            store_bytes = int(sum(v.nbytes for v in init.values()))
            del init

            if device_close:
                del pulled      # the comparison below needs the host RAM
                checked.update(_check_device_close(
                    ps, before, after, grad_iterations - 1,
                    1 + grad_iterations))
            else:
                checked["step_output"] = _check_step_output(
                    run, worker, pulled, bool(worker_mesh))
        # the f32 wire never asks for the codec; resolve it as the first
        # packed push would, so the gauge says which one this host has
        active_codec()
        gauges = obs_stats.REGISTRY.snapshot()["gauges"]
        checked.update({
            "iterations": 1 + grad_iterations,
            "losses": [round(loss, 4) for loss in losses],
            "store_versions": versions,
            "store_moved_max_abs": moved,
            "store_bytes_f32": store_bytes,
            "transport": ("shm" if after["rpc.shm.bytes"]
                          > before["rpc.shm.bytes"] else "tcp"),
            "rpc.shm.fallback": after["rpc.shm.fallback"],
            "rpc.codec.native": gauges.get("rpc.codec.native"),
            "native_lib": native.lib() is not None,
            "memory": memory.report(),
        })
    finally:
        worker.shutdown()
        coordinator.stop()
        ps.stop()
        shutil.rmtree(checkpoint_dir, ignore_errors=True)
    return {**run.compiles_since(mark), "first_step_s": round(first_s, 3),
            "steady_s": round(steady_s, 3),
            "steady_steps": grad_iterations - 1, "checked": checked}


def _check_step_output(run: Run, worker, params, meshed: bool) -> dict:
    """The jitted step's packed output (loss + gradients, padded to the
    mesh) stays on device in the bucket object: it must sit on the platform
    under test and, with a worker mesh, spread over every device."""
    import jax

    config = worker.trainer.model.config
    tokens = np.random.default_rng(SEED).integers(
        0, config.vocab, (run.sizes.batch, config.max_seq), dtype=np.int32)
    buckets = worker.trainer.compute_gradient_buckets(params, tokens)
    shards = len(run.devices) if meshed else 1
    packed_size = -(-(1 + sum(v.size for v in params.values()))
                    // shards) * shards
    (packed,) = [a for a in jax.live_arrays() if a.size == packed_size]
    platforms = sorted({d.platform for d in packed.devices()})
    if platforms != [run.device["platform"]]:
        raise AssertionError(f"step output on {platforms}, expected "
                             f"{run.device['platform']}")
    if meshed and len(packed.devices()) != len(run.devices):
        raise AssertionError(
            f"packed step output on {len(packed.devices())} of "
            f"{len(run.devices)} devices")
    if not math.isfinite(buckets.loss):
        raise AssertionError(f"loss not finite: {buckets.loss}")
    return {"platforms": platforms, "elements": int(packed.size),
            "devices": len(packed.devices()),
            "shard_elements": [int(shard.data.size)
                               for shard in packed.addressable_shards]}


def _check_device_close(ps, before: dict, after: dict,
                        steady_closes: int, next_iteration: int) -> dict:
    """Phase 2's own checks: every close ran on the device through the flat
    arena, and one more close — pushed straight into the PS core — agrees
    with the numpy Adam of core/optimizer.py.

    Under PSDT_ARENA the parameters live on the device as the arena's flat
    slabs, and the published store is host views of the one readback per
    stripe.  ``device_apply.is_device_store`` describes the per-tensor
    device close (PSDT_DEVICE_APPLY alone) and is False here by design, so
    ``ps.apply.arena`` — counted only where a flat close publishes — is
    what is checked."""
    from parameter_server_distributed_tpu.core.optimizer import Adam

    counted = {name: after[name] - before[name]
               for name in ("ps.apply.device", "ps.apply.arena")}
    if any(n != steady_closes for n in counted.values()):
        raise AssertionError(
            f"{steady_closes} closes, but the counters moved by {counted}")
    if after["ps.apply.arena_fallback"]:
        raise AssertionError(
            f"ps.apply.arena_fallback = {after['ps.apply.arena_fallback']}")

    params = {name: np.array(value, np.float32)
              for name, value in ps.core.get_parameters().items()}
    version = ps.core.params_version
    reference = Adam(ADAM_LR)
    reference.load_state_dict(ps.core.optimizer_state())
    rng = np.random.default_rng(SEED)
    grads = {name: (1e-2 * rng.standard_normal(value.shape))
             .astype(np.float32) for name, value in params.items()}
    result = ps.core.receive_gradients(0, next_iteration, grads)
    if not (result.success and result.aggregation_complete
            and ps.core.params_version == version + 1):
        raise AssertionError(f"direct close did not apply: {result.message}")
    closed = ps.core.get_parameters()
    expected = reference.apply(params, grads)
    worst = max(_max_abs_diff(closed[name], expected[name])
                for name in expected)
    if not worst <= ADAM_CLOSE_ATOL:
        raise AssertionError(
            f"device Adam close differs from numpy Adam by {worst} "
            f"(> {ADAM_CLOSE_ATOL})")
    fallbacks = _counters()["ps.apply.arena_fallback"]
    if fallbacks:
        raise AssertionError(f"direct close fell back {fallbacks} times")
    return {"device_closes": steady_closes + 1,
            "ps.apply.arena_fallback": fallbacks,
            "adam_vs_numpy_max_abs": worst,
            "adam_vs_numpy_atol": ADAM_CLOSE_ATOL,
            # core/arena.py and async_sgd/device_optimizer.py place with a
            # bare jnp.asarray, so the PS state (parameter, moment and
            # scratch slabs: all that is live on the devices right now)
            # sits on the first device however many the host has; a
            # multi-chip close is not built yet
            "ps_state_bytes_by_device": _live_bytes_by_device()}


def _live_bytes_by_device() -> dict:
    import jax

    held: dict = {}
    for array in jax.live_arrays():
        for shard in array.addressable_shards:
            name = str(shard.device)
            held[name] = held.get(name, 0) + int(shard.data.nbytes)
    return held


def phase_ps_round(run: Run) -> dict:
    return _ps_round(run, optimizer="sgd", learning_rate=0.05,
                     grad_iterations=4, device_close=False)


def phase_ps_round_device_close(run: Run) -> dict:
    flags = {"PSDT_DEVICE_APPLY": "1", "PSDT_ARENA": "1"}
    with unittest.mock.patch.dict(os.environ, flags):
        return _ps_round(run, optimizer="device_adam",
                         learning_rate=ADAM_LR, grad_iterations=3,
                         device_close=True)


# ------------------------------------------------------------ SPMD step
def phase_spmd_train(run: Run) -> dict:
    """parallel.train_loop.run_training, the function pst-train calls."""
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    steps = 5
    _, mesh, _ = run.meshes()
    mark = run.compile_mark()
    workdir = tempfile.mkdtemp(prefix="chip-smoke-train-")
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    try:
        with MemorySampler(run.devices) as memory:
            summary = run_training(TrainLoopConfig(
                model=run.sizes.model, batch_size=run.sizes.batch,
                steps=steps, log_every=1, scan_layers=True,
                attention="dense", mesh=mesh, seed=SEED,
                metrics_path=metrics_path))
        with open(metrics_path) as f:
            records = [json.loads(line) for line in f]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    losses = [r["loss"] for r in records]
    if [r["step"] for r in records] != list(range(1, steps + 1)):
        raise AssertionError(f"steps logged: {[r['step'] for r in records]}")
    if summary["steps"] != steps:
        raise AssertionError(f"summary: {summary}")
    if not all(math.isfinite(loss) for loss in losses):
        raise AssertionError(f"loss not finite: {losses}")
    late = run.compiles_after(records[1]["t"])
    if late:
        raise AssertionError(f"{late} compilations after step 2")
    return {**run.compiles_since(mark),
            "steady_s": round(sum(r["step_time_s"] for r in records[2:]), 3),
            "steady_steps": steps - 2,
            "checked": {"mesh": _axes(mesh), "steps": steps,
                        "losses": [round(loss, 4) for loss in losses],
                        "compiles_after_step_2": late,
                        "memory": memory.report()}}


# --------------------------------------------------------------- serving
def phase_serve(run: Run) -> dict:
    """DecodeServer with the radix prefix cache: seven requests at once, an
    eighth after the first finished that shares its leading tokens."""
    from parameter_server_distributed_tpu.models.registry import (
        get_model_and_batches)
    from parameter_server_distributed_tpu.models.serving import DecodeServer
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh

    sizes = run.sizes
    _, _, mesh_config = run.meshes()
    mark = run.compile_mark()
    model, _ = get_model_and_batches(sizes.model, 1, scan=True)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.config.vocab, sizes.prompt_len)
               for _ in range(8)]
    prompts[7][:sizes.shared_len] = prompts[0][:sizes.shared_len]
    with MemorySampler(run.devices) as memory:
        server = DecodeServer(
            model, model.init_params(SEED), slots=8,
            max_len=sizes.serve_max_len,
            mesh=build_mesh(mesh_config) if mesh_config else None,
            # 128 MiB: the eight full-size prompts' K/V rows are 12.6 MB
            # each, and the first must still be cached when the last comes
            prompt_cache=8, prefix_cache_bytes=1 << 27)
        request_ids = [server.submit(p, max_new_tokens=sizes.max_new)
                       for p in prompts[:7]]
        while request_ids[0] not in server.finished():
            server.step()
        request_ids.append(server.submit(prompts[7],
                                         max_new_tokens=sizes.max_new))
        server.step()                        # past any first-use compile
        steps_before = server.stats["steps"]
        t0 = time.perf_counter()
        results = server.run_to_completion()
        steady_s = time.perf_counter() - t0
        stats = server.stats
        agreement = {
            f"request_{i}": _agreement_with_reference(
                model, server.params, prompts[i], results[request_ids[i]])
            for i in (0, 7)}
    if sorted(results) != sorted(request_ids):
        raise AssertionError(f"finished {sorted(results)} of {request_ids}")
    if any(len(results[r]) != sizes.max_new for r in request_ids):
        raise AssertionError("a request stopped short of max_new")
    if not stats["prefill_tokens"] < stats["prompt_tokens"]:
        raise AssertionError(f"no prefix reuse: {stats}")
    return {**run.compiles_since(mark), "steady_s": round(steady_s, 3),
            "steady_steps": stats["steps"] - steps_before,
            "checked": {"mesh": _axes(mesh_config),
                        "requests_finished": len(results),
                        "vs_generate": agreement,
                        "near_tie_tolerance_std": SERVE_NEAR_TIE,
                        "prefill_tokens": stats["prefill_tokens"],
                        "prompt_tokens": stats["prompt_tokens"],
                        "prefix_hits": stats["prefix_hits"],
                        "memory": memory.report()}}


def _agreement_with_reference(model, params, prompt, served) -> dict:
    """Token-exact against ``models.generation.generate`` (the contract
    tests/test_serving.py holds on the CPU), or — where bf16 rounding
    flipped a near-tie — every served token within SERVE_NEAR_TIE of the
    best logit of a dense forward over the served sequence."""
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.generation import generate

    reference = np.asarray(generate(
        model, params, jnp.asarray(prompt[None], jnp.int32), len(served)))[0]
    differ = np.flatnonzero(reference != np.asarray(served))
    if not differ.size:
        return {"token_exact": True}
    sequence = jnp.asarray(np.concatenate([prompt, served])[None], jnp.int32)
    logits = np.asarray(jax.jit(model.apply)(params, sequence))[0]
    # position p predicts token p + 1
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
    chosen = rows[np.arange(len(served)), np.asarray(served)]
    margins = (rows.max(axis=-1) - chosen) / rows.std(axis=-1)
    worst = float(margins.max())
    if not worst <= SERVE_NEAR_TIE:
        raise AssertionError(
            f"served tokens {[int(t) for t in served]} differ from generate "
            f"{[int(t) for t in reference]} "
            f"and are {worst} logit std from the dense forward's best "
            f"(> {SERVE_NEAR_TIE})")
    return {"token_exact": False, "first_difference": int(differ[0]),
            "tokens_differing": int(differ.size),
            "max_margin_std": float(f"{worst:.3g}")}


# --------------------------------------------------------------- kernels
def _compile_and_run(fn, *args):
    """Lower, compile, run twice.  Returns (output, compiled text, steady
    seconds of the second call)."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    out = jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compiled.as_text(), time.perf_counter() - t0


def _require_mosaic(run: Run, name: str, text: str, at_least: int) -> int:
    """A real Mosaic kernel shows as a tpu_custom_call in the compiled
    program; on the CPU (--tiny) the kernels are interpreted instead."""
    calls = text.count("tpu_custom_call")
    if run.device["platform"] == "tpu" and calls < at_least:
        raise AssertionError(
            f"{name}: {calls} tpu_custom_call in the compiled program, "
            f"expected at least {at_least}")
    return calls


def phase_kernels(run: Run) -> dict:
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.transformer import (
        causal_attention)
    from parameter_server_distributed_tpu.ops.pallas.fused_attention import (
        fused_causal_attention)
    from parameter_server_distributed_tpu.ops.pallas.fused_update import (
        fused_adam, fused_momentum, fused_sgd)

    sizes = run.sizes
    dtype = jnp.dtype(sizes.attn_dtype)
    tol = ATTN_TOL[sizes.attn_dtype]
    mark = run.compile_mark()
    steady = 0.0
    checked: dict = {"attention_tolerance": tol, "fused_atol": FUSED_ATOL}
    rng = np.random.default_rng(SEED)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def rel_err(x, ref) -> float:
        ref = np.asarray(ref, np.float32)
        return _max_abs_diff(x, ref) / float(np.max(np.abs(ref)))

    for heads, kv_heads, head_dim in ATTENTION_SHAPES:
        shape = f"h{heads}_kv{kv_heads}_d{head_dim}"
        q = normal(sizes.attn_batch, sizes.attn_seq, heads, head_dim)
        k = normal(sizes.attn_batch, sizes.attn_seq, kv_heads, head_dim)
        v = normal(sizes.attn_batch, sizes.attn_seq, kv_heads, head_dim)
        cotangent = normal(*q.shape)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * cotangent)

        # float32 dense reference on the values the kernels see
        low = [x.astype(dtype) for x in (q, k, v)]
        exact = [x.astype(jnp.float32) for x in low]
        ref_out = causal_attention(*exact)
        ref_grads = jax.grad(lambda *a: loss(causal_attention, *a),
                             argnums=(0, 1, 2))(*exact)
        # the default path's kernel (models/transformer.device_arm)
        out, fwd_text, s1 = _compile_and_run(fused_causal_attention, *low)
        grads, bwd_text, s2 = _compile_and_run(
            jax.grad(lambda *a: loss(fused_causal_attention, *a),
                     argnums=(0, 1, 2)), *low)
        steady += s1 + s2
        errors = {"out": rel_err(out, ref_out),
                  **{name: rel_err(g, r) for name, g, r
                     in zip(("dq", "dk", "dv"), grads, ref_grads)}}
        checked[f"kernel_{shape}"] = {
            **{k_: float(f"{e:.3g}") for k_, e in errors.items()},
            "tpu_custom_calls": {
                "forward": _require_mosaic(
                    run, f"kernel {shape} forward", fwd_text, 1),
                # forward + dQ + dK/dV kernels
                "backward": _require_mosaic(
                    run, f"kernel {shape} backward", bwd_text, 3)}}
        if not max(errors.values()) <= tol:
            raise AssertionError(f"kernel {shape}: {errors} exceeds {tol}")

    p = {"w": normal(*sizes.fused_shape)}
    g = {"w": normal(*sizes.fused_shape)}
    slot_m = {"w": 0.1 * normal(*sizes.fused_shape)}
    slot_v = {"w": 1e-3 + 0.01 * jnp.square(normal(*sizes.fused_shape))}
    lr, mu, b1, b2, eps, step = 0.05, 0.9, 0.9, 0.999, 1e-8, 3

    def ref_sgd(p, g):
        return p - lr * g

    def ref_momentum(p, g, vel):
        vel = mu * vel + g
        return p - lr * vel, vel

    def ref_adam(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        # bias corrections in float32, as the kernel's SMEM scalars are
        m_hat = m / (1 - jnp.float32(b1) ** jnp.float32(step))
        v_hat = v / (1 - jnp.float32(b2) ** jnp.float32(step))
        return p - lr * m_hat / (jnp.sqrt(v_hat) + eps), m, v

    # the kernels take and return {name: tensor} stores; the references
    # take the bare tensors
    fused = (
        ("fused_sgd", lambda p, g: fused_sgd(p, g, lr), ref_sgd, (p, g)),
        ("fused_momentum",
         lambda p, g, vel: fused_momentum(p, g, vel, lr, mu),
         ref_momentum, (p, g, slot_m)),
        ("fused_adam",
         lambda p, g, m, v: fused_adam(p, g, m, v, step, lr, b1, b2, eps),
         ref_adam, (p, g, slot_m, slot_v)),
    )
    for name, kernel, reference, args in fused:
        outs, text, seconds = _compile_and_run(kernel, *args)
        steady += seconds
        expected = jax.jit(reference)(*(store["w"] for store in args))
        worst = max(_max_abs_diff(a, b) for a, b in zip(
            jax.tree.leaves(outs), jax.tree.leaves(expected)))
        checked[name] = {
            "elements": int(np.prod(sizes.fused_shape)),
            "max_abs": worst,
            "tpu_custom_calls": _require_mosaic(run, name, text, 1)}
        if not worst <= FUSED_ATOL:
            raise AssertionError(f"{name}: max|x - ref| = {worst} exceeds "
                                 f"{FUSED_ATOL}")
    return {**run.compiles_since(mark), "steady_s": round(steady, 3),
            "checked": checked}


PHASES = {
    "ps_round": phase_ps_round,
    "ps_round_device_close": phase_ps_round_device_close,
    "spmd_train": phase_spmd_train,
    "serve": phase_serve,
    "kernels": phase_kernels,
}


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def _version(package: str) -> str:
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tiny", action="store_true",
                        help="small_lm sizes; the only way to run off a TPU")
    parser.add_argument("--phases", default=",".join(PHASES),
                        help="comma-separated subset, in the given order")
    args = parser.parse_args(argv)
    names = [name for name in args.phases.split(",") if name]
    unknown = [name for name in names if name not in PHASES]
    if unknown:
        parser.error(f"unknown phase(s) {unknown}; have {list(PHASES)}")

    import jax

    from parameter_server_distributed_tpu.utils.compile_cache import (
        enable_compile_cache)

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    cache_dir = enable_compile_cache()
    run = Run(TINY if args.tiny else FULL)
    if run.device["platform"] != "tpu" and not args.tiny:
        print(f"chip_smoke: JAX gave {run.device}, not a TPU; only --tiny "
              f"runs elsewhere", file=sys.stderr)
        return 1
    run.emit(phase="start", tiny=args.tiny, model=run.sizes.model,
             jax=jax.__version__, jaxlib=_version("jaxlib"),
             libtpu=_version("libtpu"), compile_cache_dir=cache_dir,
             compile_cache_entries=_cache_entries(cache_dir))
    for name in names:
        t0 = time.perf_counter()
        try:
            result = PHASES[name](run)
        except BaseException as exc:
            run.emit(phase=name, ok=False,
                     wall_s=round(time.perf_counter() - t0, 3),
                     error=f"{type(exc).__name__}: {exc}"[:2000])
            raise
        run.emit(phase=name, ok=True,
                 wall_s=round(time.perf_counter() - t0, 3), **result)
        gc.collect()
    run.emit(phase="done", phases=names,
             compile_cache_entries=_cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": run.device["platform"],
        "kind": run.device["device_kind"],
        "count": run.device["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
