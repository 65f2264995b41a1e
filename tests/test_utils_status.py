"""Metrics utilities + status CLI + distributed helpers."""

import json
import os

import numpy as np
import pytest

from parameter_server_distributed_tpu.obs import (MetricsLogger, StepTimer,
                                                  samples_per_sec)


def test_step_timer_percentiles():
    timer = StepTimer()
    for d in [0.01, 0.02, 0.03, 0.04, 0.10]:
        timer.record(d)
    s = timer.summary()
    assert s["count"] == 5
    assert s["p50_s"] == 0.03
    assert s["p95_s"] == 0.10
    assert abs(s["mean_s"] - 0.04) < 1e-9


def test_step_timer_context_manager():
    timer = StepTimer()
    with timer:
        pass
    assert timer.count == 1 and timer.summary()["last_s"] >= 0


def test_metrics_logger_jsonl(tmp_path):
    path = str(tmp_path / "metrics" / "train.jsonl")
    logger = MetricsLogger(path)
    logger.log(step=1, loss=2.5)
    logger.log(step=2, loss=2.1, samples_per_sec=100.0)
    assert logger.latest("loss") == 2.1
    assert logger.latest("samples_per_sec") == 100.0
    lines = [json.loads(l) for l in open(path)]
    assert [l["step"] for l in lines] == [1, 2]
    assert all("t" in l for l in lines)


def test_samples_per_sec():
    assert samples_per_sec(128, 0.5) == 256.0
    assert samples_per_sec(128, 0.5, num_chips=4) == 64.0


def test_status_cli_against_live_cluster(capsys):
    from parameter_server_distributed_tpu.cli.status_main import main
    from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                         ParameterServerConfig)
    from parameter_server_distributed_tpu.server.coordinator_service import Coordinator
    from parameter_server_distributed_tpu.server.ps_service import ParameterServer

    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        autosave_period_s=600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()
    coordinator.core.register_worker(3, "10.0.0.9", 50063, "hostX")
    try:
        assert main([f"127.0.0.1:{coord_port}", "--iteration=5"]) == 0
        out = capsys.readouterr().out
        assert "registered workers: 1" in out
        assert "worker 3: 10.0.0.9:50063 (hostX)" in out
        assert "ready=False received=0/2" in out
    finally:
        coordinator.stop()
        ps.stop()


def test_hybrid_mesh_config_single_host():
    from parameter_server_distributed_tpu.parallel.distributed import (
        hybrid_mesh_config, initialize_multihost)
    assert initialize_multihost() is False  # single-process no-op
    config = hybrid_mesh_config(tensor=2)
    assert config.num_devices == 8 and config.tensor == 2
    with pytest.raises(ValueError):
        hybrid_mesh_config(tensor=3)


def test_status_main_shows_shards(tmp_path, capsys):
    """pst-status lists shard addresses and per-shard sync state when the
    coordinator reports a sharded store."""
    from parameter_server_distributed_tpu.cli.status_main import main
    from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                         ParameterServerConfig)
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    shards = []
    ports = []
    for i in range(2):
        ps = ParameterServer(ParameterServerConfig(
            bind_address="127.0.0.1", port=0, total_workers=1,
            checkpoint_dir=str(tmp_path / f"s{i}"), autosave_period_s=600.0))
        shards.append(ps)
        ports.append(ps.start())
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ports[0], ps_shards=(f"127.0.0.1:{ports[1]}",),
        reap_period_s=600.0))
    coord_port = coordinator.start()
    try:
        rc = main([f"127.0.0.1:{coord_port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "ps shards: 2" in out
        assert f"shard 1: 127.0.0.1:{ports[1]}" in out
        assert out.count("sync status") == 2  # one per shard
    finally:
        coordinator.stop()
        for ps in shards:
            ps.stop()
