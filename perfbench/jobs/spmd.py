"""SPMD training: ``parallel.train_loop.run_training`` as ``pst-train`` calls
it, fed by the benchmark's own token stream.

``run_training`` takes a number of steps, not a duration, and is one call.
The benchmark owns the data stream it reads, so the stream is the control:
it watches the loop's own fences (the JSONL record ``run_training`` writes
at every ``log_every``-th step, after it fetched that step's loss), opens
the window at the fence of a FIXED warm-up step count, and ends when a
fence at least ``--seconds`` later has been written.  ``run_training`` then
stops on ``StopIteration`` as it would at the end of any finite data set.
Throughput is whole steps between those two fences over their own time.
"""

from __future__ import annotations

import json
import math
import os
import shutil

from .. import correct, flops, program, reduce, traffic_gen
from ..harness import CHECKOUT, say, tiny
from ..peaks import peaks_for


class StepStream:
    """Token batches for ``run_training``; ends itself when the window has
    closed."""

    def __init__(self, ctx, batches, metrics_path: str, warmup_steps: int,
                 trace_steps: int):
        self.ctx = ctx
        self._batches = batches
        self._path = metrics_path
        self._warmup = warmup_steps
        self._trace_steps = trace_steps
        self._offset = 0
        self.fences: list[tuple[int, float, float]] = []   # step, t, loss
        self.opened: tuple[int, float] | None = None
        self.closed = False
        self.registry_at_open: dict | None = None

    def _read_fences(self) -> None:
        if not os.path.exists(self._path):
            return
        with open(self._path) as f:
            f.seek(self._offset)
            for line in f:
                if not line.endswith("\n"):
                    break
                self._offset += len(line.encode())
                record = json.loads(line)
                if "loss" in record:
                    self.fences.append((int(record["step"]),
                                        float(record["t"]),
                                        float(record["loss"])))

    def __iter__(self):
        return self

    def __next__(self):
        with self.ctx.annotate("data"):
            self._read_fences()
            if self.opened is None:
                for step, t, _ in self.fences:
                    if step == self._warmup:
                        self.opened = (step, t)
                        self.ctx.open_window(t)
                        self.registry_at_open = program.registry_snapshot()
                        self.ctx.start_trace()
            elif self.fences:
                step, t, _ = self.fences[-1]
                if step - self.opened[0] >= self._trace_steps:
                    self.ctx.stop_trace()
                if t - self.opened[1] >= self.ctx.seconds:
                    self.closed = True
                    raise StopIteration
            return next(self._batches)


shrink = tiny      # run.py --rehearse: nothing of this job's own to shrink


def run(ctx) -> dict:
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.train_loop import (
        TrainLoopConfig, run_training)

    traffic, config = ctx.traffic, ctx.config
    batch, seq = traffic["batch_size"], config["n_positions"]
    workdir = os.path.join(CHECKOUT, ".perfbench_work", ctx.cell["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    stream: list[StepStream] = []

    def batches_fn(batch_size: int, seed: int):
        stream.append(StepStream(
            ctx, traffic_gen.token_batches(
                batch_size, seq, config["vocab_size"], ctx.seed,
                traffic["data"]["zipf_alpha"]),
            metrics_path, traffic["warmup_steps"],
            traffic["trace_steps"]))
        return stream[-1]

    name = program.register_model(config, batches_fn)
    ctx.setup.mark("traffic")
    try:
        run_training(TrainLoopConfig(
            model=name, batch_size=batch, steps=10 ** 9,
            optimizer=traffic["optimizer"],
            learning_rate=traffic["learning_rate"],
            attention=traffic["attention"],
            log_every=traffic["log_every"], prefetch=traffic["prefetch"],
            mesh=MeshConfig(**traffic["mesh"]),
            seed=program.program_seed(ctx.seed),
            metrics_path=metrics_path))
    except StopIteration:
        if not (stream and stream[0].closed):
            raise
    source = stream[0]
    source._read_fences()
    shutil.rmtree(workdir, ignore_errors=True)
    after = program.registry_snapshot()
    fences = source.fences
    window = reduce.throughput_window(
        [(s, t) for s, t, _ in fences], traffic["warmup_steps"],
        ctx.seconds)
    if window is None:
        raise RuntimeError(f"the window never closed: fences {fences}")
    steps, elapsed, close_step = window
    ctx.close_window(source.opened[1] + elapsed)
    tokens_per_s = steps * batch * seq / elapsed
    losses = [loss for _, _, loss in fences]
    loss_open = next(l for s, _, l in fences if s == source.opened[0])
    loss_close = next(l for s, _, l in fences if s == close_step)
    say(detail="train_window", steps=steps, elapsed_s=elapsed,
        tokens_per_step=batch * seq, fences=len(fences),
        loss_at_open=loss_open, loss_at_close=loss_close)

    traced = ctx.finish_trace()
    # the trained state is gone with run_training's frame; the reference's
    # float32 copy of the weights takes its place on the first device
    check = correct.compare_forward(
        config, program.build_model(config), ctx.seed, traffic["check"],
        backward=True)
    checks = {
        "logits": check,
        "loss_finite": all(math.isfinite(l) for l in losses),
        # the run's own state is gone; that its backward pass and Adam
        # learn is held to the loss over the window's sixteen or so steps
        "loss_fell": loss_close < loss_open,
    }
    chips = MeshConfig(**traffic["mesh"]).num_devices
    observed = {
        "window_s": elapsed, "registry_before": source.registry_at_open,
        "registry_after": after, "trace": traced,
        "tokens_per_s": tokens_per_s, "chips": chips,
        "flops_per_token": flops.train_flops_per_token(config, seq),
        "peak_flops": None if ctx.rehearsal else peaks_for(
            ctx.devices[0].device_kind)["bf16_flops"],
        "memory": ctx.memory,
    }
    return {"attempted": steps, "failed": 0, "checks": checks,
            "end_to_end": {"train_tokens_per_s": tokens_per_s},
            "observed": observed, "traced": traced}
