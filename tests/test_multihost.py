"""Multi-host (multi-controller) training e2e: two OS processes, CPU
backend, jax.distributed over localhost — the DCN story of
parallel/distributed.py actually exercised.

Each process hosts 4 virtual CPU devices; the global mesh spans all 8.
The test drives the REAL CLI (cli.train_main with --coordinator/
--num-processes/--process-id), so it covers initialize_multihost, the
multi-controller batch/state placement in ShardedTrainer, and the training
loop end to end.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.parametrize("model,mesh", [
    ("mnist_mlp", "data:4,fsdp:2"),
    ("small_lm", "data:8"),
])
def test_two_process_training_e2e(model, mesh, tmp_path):
    """train_main --num-processes=2 on two real processes: both must
    finish, report identical losses (same global batch, same collectives),
    and actually form one 8-device cluster."""
    port = _free_port()
    args = [sys.executable, "-m",
            "parameter_server_distributed_tpu.cli.train_main",
            f"--coordinator=127.0.0.1:{port}", "--num-processes=2",
            f"--model={model}", f"--mesh={mesh}", "--steps=4",
            "--batch=16", "--optimizer=sgd", "--lr=0.1", "--log-every=2"]
    procs = [
        subprocess.Popen(args + [f"--process-id={i}"], env=_child_env(),
                         cwd=str(tmp_path), stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE)
        for i in range(2)
    ]
    outs = []
    for i, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"process {i} timed out")
        assert proc.returncode == 0, (
            f"process {i} rc={proc.returncode}\n"
            f"stderr tail:\n{err.decode(errors='replace')[-2000:]}")
        outs.append(out.decode(errors="replace"))

    summaries = []
    for i, out in enumerate(outs):
        line = [l for l in out.splitlines() if l.startswith("{")][-1]
        summaries.append(json.loads(line))
    losses = [s["final_loss"] for s in summaries]
    assert all(np.isfinite(l) for l in losses), losses
    # one logical computation on one global mesh -> identical results
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    assert summaries[0]["steps"] == 4


def test_two_process_per_process_data(tmp_path):
    """--per-process-data: each host loads only batch/2 rows at its own
    seed and the global batch is stitched from local shards
    (make_array_from_process_local_data).  Both processes must agree on
    the loss (one SPMD program) and show a learning signal."""
    port = _free_port()
    args = [sys.executable, "-m",
            "parameter_server_distributed_tpu.cli.train_main",
            f"--coordinator=127.0.0.1:{port}", "--num-processes=2",
            "--model=mnist_mlp", "--mesh=data:8", "--steps=6",
            "--batch=32", "--optimizer=sgd", "--lr=0.1", "--log-every=2",
            "--per-process-data",
            "--metrics=metrics_{}.jsonl"]
    procs = [
        subprocess.Popen(
            [a.replace("{}", str(i)) for a in args] + [f"--process-id={i}"],
            env=_child_env(), cwd=str(tmp_path), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
        for i in range(2)
    ]
    outs = []
    for i, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"process {i} timed out")
        assert proc.returncode == 0, (
            f"process {i} rc={proc.returncode}\n"
            f"stderr tail:\n{err.decode(errors='replace')[-2000:]}")
        outs.append(out.decode(errors="replace"))

    summaries = [json.loads([l for l in out.splitlines()
                             if l.startswith("{")][-1]) for out in outs]
    losses = [s["final_loss"] for s in summaries]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    # learning signal across the stitched global batches
    lines = [json.loads(l)
             for l in open(tmp_path / "metrics_0.jsonl")]
    assert lines[-1]["loss"] < lines[0]["loss"]


def test_hybrid_mesh_config_single_process():
    """hybrid_mesh_config factorizes the (virtual) global device count with
    model axes innermost."""
    from parameter_server_distributed_tpu.parallel.distributed import (
        hybrid_mesh_config)

    config = hybrid_mesh_config(tensor=2)
    assert config.tensor == 2
    assert config.num_devices == 8  # conftest forces 8 virtual devices

    with pytest.raises(ValueError, match="divisible"):
        hybrid_mesh_config(tensor=3)


def test_initialize_multihost_single_process_noop():
    from parameter_server_distributed_tpu.parallel.distributed import (
        initialize_multihost)

    assert initialize_multihost(num_processes=1) is False


def _run_procs(args, n_procs, tmp_path, devices_per_proc=2, timeout=480):
    env = _child_env()
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices_per_proc}")
    procs = [
        subprocess.Popen(args + [f"--process-id={i}"], env=env,
                         cwd=str(tmp_path), stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE)
        for i in range(n_procs)
    ]
    outs = []
    for i, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"process {i} timed out")
        assert proc.returncode == 0, (
            f"process {i} rc={proc.returncode}\n"
            f"stderr tail:\n{err.decode(errors='replace')[-2000:]}")
        outs.append(out.decode(errors="replace"))
    return [json.loads([l for l in out.splitlines()
                        if l.startswith("{")][-1]) for out in outs]


def test_four_process_fsdp_across_hosts(tmp_path):
    """4 OS processes x 2 virtual devices: one fsdp:8 mesh whose shard
    groups span every process boundary (DCN in production, localhost
    here).  All four controllers must agree bit-for-bit on the loss."""
    port = _free_port()
    args = [sys.executable, "-m",
            "parameter_server_distributed_tpu.cli.train_main",
            f"--coordinator=127.0.0.1:{port}", "--num-processes=4",
            "--model=mnist_mlp", "--mesh=fsdp:8", "--steps=3",
            "--batch=16", "--optimizer=sgd", "--lr=0.1", "--log-every=1"]
    summaries = _run_procs(args, 4, tmp_path)
    losses = [s["final_loss"] for s in summaries]
    assert all(np.isfinite(l) for l in losses), losses
    for l in losses[1:]:
        assert losses[0] == pytest.approx(l, rel=1e-6)
    assert summaries[0]["steps"] == 3


def test_four_process_pipeline_across_hosts(tmp_path):
    """4 processes x 2 devices, mesh pipe:4,data:2: each pipe group is 4
    consecutive devices = TWO processes, so the schedule's ppermute hops
    cross process boundaries — the DCN pipeline story end to end."""
    port = _free_port()
    args = [sys.executable, "-m",
            "parameter_server_distributed_tpu.cli.train_main",
            f"--coordinator=127.0.0.1:{port}", "--num-processes=4",
            "--model=small_lm4", "--mesh=pipe:4,data:2", "--steps=2",
            "--batch=16", "--optimizer=sgd", "--lr=0.1", "--log-every=1",
            "--pipeline-schedule=gpipe"]
    summaries = _run_procs(args, 4, tmp_path, timeout=540)
    losses = [s["final_loss"] for s in summaries]
    assert all(np.isfinite(l) for l in losses), losses
    for l in losses[1:]:
        assert losses[0] == pytest.approx(l, rel=1e-6)
