"""One parameter-server round after another: coordinator, parameter server
and one worker in this process, assembled as ``pst-coordinator``,
``pst-parameter-server`` and ``pst-worker`` assemble them (the assembly of
``chip_smoke._ps_round``; one process holds the chip).

A round is ``Worker.run_iteration``: pull, the worker's jitted step, the
fused push that closes the barrier, the server's fold and optimizer close,
and the next parameters coming back.  Every round ends in a fetch of its
loss, so the window is whole rounds over their own elapsed time.

The store is made from ``--seed`` and handed to the server before the first
round, so the first round is the first Adam update from zero moments: after
it the server's first moment is (1 - b1) times the gradient it folded, which
lets ``round_checks`` hold that one real round (the cell's own compiled
step, wire, fold and close) to exact arithmetic.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from .. import correct, program, traffic_gen
from ..harness import CHECKOUT, say, tiny


def relative_rms(got: dict, want: dict) -> float:
    """|got - want| / |want| over every tensor of two stores at once."""
    diff = sum(float(np.sum((got[k] - want[k]) ** 2, dtype=np.float64))
               for k in want)
    norm = sum(float(np.sum(want[k] ** 2, dtype=np.float64)) for k in want)
    return (diff / norm) ** 0.5


def round_checks(trainer, batches, before: dict, after: dict, state: dict,
                 loss: float, optimizer: dict, tolerance: dict) -> dict:
    """The first round, held to what it must have computed.  ``before``
    and ``after`` are the store around it, ``state`` the server's
    optimizer state after it, ``loss`` what the worker reported.

    - the gradient the server folded, (first moment) / (1 - b1), is the
      gradient the worker's own compiled step gives for the same
      parameters and batch: the D2H, the wire codec, the transport and
      the fold lost nothing;
    - the store moved by Adam's first update of that gradient,
      -lr g / (|g| + eps): the close is float32 Adam."""
    b1, eps = optimizer["b1"], optimizer["eps"]
    folded = {k: m / np.float32(1.0 - b1) for k, m in state["m"].items()}
    for batch in batches:
        pushed, again = trainer.compute_gradients(before, batch)
        if abs(again - loss) <= 1e-6 * abs(loss):
            break
    else:
        return {"round_reproduced": False}
    lr = np.float32(optimizer["learning_rate"])
    moved = {k: after[k] - before[k] for k in folded}
    adam = {k: -lr * g / (np.abs(g) + np.float32(eps))
            for k, g in folded.items()}
    wire, close = relative_rms(folded, pushed), relative_rms(moved, adam)
    return {"round_reproduced": True, "optimizer_step": int(state["step"]),
            "wire_error": wire, "wire_tolerance": tolerance["wire"],
            "wire_ok": wire <= tolerance["wire"],
            "close_error": close, "close_tolerance": tolerance["close"],
            "close_ok": close <= tolerance["close"]}


shrink = tiny      # run.py --rehearse: nothing of this job's own to shrink


def run(ctx) -> dict:
    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.config import (
        CoordinatorConfig, ParameterServerConfig, WorkerConfig)
    from parameter_server_distributed_tpu.obs import trace as obs_trace
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)
    from parameter_server_distributed_tpu import native

    traffic, config = ctx.traffic, ctx.config
    batch, seq = traffic["batch_size"], config["n_positions"]

    first_batches: list = []      # what the first rounds trained on

    def batches_fn(batch_size: int, seed: int):
        for tokens in traffic_gen.token_batches(
                batch_size, seq, config["vocab_size"], ctx.seed,
                traffic["data"]["zipf_alpha"]):
            if len(first_batches) < 2:
                first_batches.append(tokens)
            yield tokens

    name = program.register_model(config, batches_fn)
    workdir = os.path.join(CHECKOUT, ".perfbench_work", ctx.cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    ctx.setup.mark("traffic")

    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=workdir, optimizer=traffic["optimizer"],
        learning_rate=traffic["learning_rate"], autosave_period_s=3600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    coordinator_port = coordinator.start()
    worker = build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{coordinator_port}", worker_id=0,
        iterations=10 ** 9, model=name, batch_size=batch,
        scan_layers=True, fused_step=True, wire_dtype=traffic["wire_dtype"],
        heartbeat_period_s=3600.0), seed=program.program_seed(ctx.seed))
    losses: list[float] = []
    versions: list[int] = []
    try:
        worker.initialize()
        ctx.setup.mark("assembly")
        # the store: made on the device from --seed in the worker's dtype,
        # kept by the server as float32 on the host
        weights = program.make_weights(worker.trainer.model, ctx.seed)
        ps.core.initialize_parameters(
            {k: np.asarray(v, np.float32) for k, v in weights.items()})
        del weights
        store_before = ps.core.get_parameters()
        versions.append(ps.core.params_version)
        ctx.setup.mark("weights")
        iteration = 0
        for _ in range(traffic["warmup_rounds"]):
            losses.append(worker.run_iteration(iteration))
            versions.append(ps.core.params_version)
            iteration += 1
            if iteration == 1:
                # the server swaps new arrays in and never writes into
                # served ones, so these are the store around round 0
                store_after = ps.core.get_parameters()
                state_after = ps.core.optimizer_state()
                state_after.pop("v", None)
        if ctx.trace:
            obs_trace.clear()
            obs_trace.enable(True)
        before = program.registry_snapshot()
        opened = ctx.open_window()
        ctx.start_trace()
        rounds = 0
        now = opened
        while now - opened < ctx.seconds:
            with ctx.annotate("ps_round"):
                losses.append(worker.run_iteration(iteration))
            now = time.time()
            versions.append(ps.core.params_version)
            iteration += 1
            rounds += 1
            if rounds >= traffic["trace_rounds"]:
                ctx.stop_trace()
        ctx.close_window(now)
        after = program.registry_snapshot()
        spans = obs_trace.spans() if ctx.trace else []
        obs_trace.enable(False)
        transport = ("shm" if after["counters"].get("rpc.shm.bytes", 0)
                     > before["counters"].get("rpc.shm.bytes", 0) else "tcp")
        native_lib = native.lib() is not None
        first_round = round_checks(
            worker.trainer, first_batches, store_before, store_after,
            state_after, losses[0],
            dict(traffic["check"]["adam"],
                 learning_rate=traffic["learning_rate"]),
            traffic["check"]["round_tolerance"])
        del store_before, store_after, state_after
    finally:
        worker.shutdown()
        coordinator.stop()
        ps.stop()
        shutil.rmtree(workdir, ignore_errors=True)
    elapsed = now - opened
    tokens_per_s = rounds * batch * seq / elapsed
    window_losses = losses[traffic["warmup_rounds"] - 1:]
    say(detail="ps_window", rounds=rounds, elapsed_s=elapsed,
        tokens_per_round=batch * seq, transport=transport,
        native_lib=native_lib, loss_at_open=window_losses[0],
        loss_at_close=window_losses[-1])

    traced = ctx.finish_trace()
    del worker, ps, coordinator
    check = correct.compare_forward(
        config, program.build_model(config), ctx.seed, traffic["check"],
        backward=True)
    steps = [b - a for a, b in zip(versions, versions[1:])]
    checks = {
        "logits": check,
        "first_round": first_round,
        "first_round_ok": bool(first_round["round_reproduced"]
                               and first_round["wire_ok"]
                               and first_round["close_ok"]),
        "loss_finite": all(math.isfinite(l) for l in losses),
        # one worker: every acknowledged push closes the barrier, so the
        # store's version advances exactly once per round
        "version_once_per_round": all(s == 1 for s in steps),
        "transport_is_shm": transport == "shm",
        "native_lib": native_lib,
    }
    observed = {
        "window_s": elapsed, "rounds": rounds, "spans": spans,
        "window": (opened, now),
        "registry_before": before, "registry_after": after,
        "trace": traced, "memory": ctx.memory,
    }
    return {"attempted": rounds, "failed": 0, "checks": checks,
            "end_to_end": {"ps_tokens_per_s": tokens_per_s},
            "observed": observed, "traced": traced}
