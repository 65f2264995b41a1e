"""End-to-end training: coordinator + PS + 2 workers over real gRPC,
with real jitted gradients (the reference's only test is the localhost
multi-process smoke script, scripts/test_local.sh — this is its in-process
analogue plus actual learning-signal assertions)."""

import threading

import numpy as np
import pytest

from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                     ParameterServerConfig,
                                                     WorkerConfig)
from parameter_server_distributed_tpu.cli.worker_main import build_worker
from parameter_server_distributed_tpu.server.coordinator_service import Coordinator
from parameter_server_distributed_tpu.server.ps_service import ParameterServer


@pytest.fixture
def cluster(tmp_path):
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_interval=2, checkpoint_dir=str(tmp_path),
        learning_rate=0.05, autosave_period_s=600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0,
        ps_address="127.0.0.1", ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()
    yield ps, coordinator, coord_port, tmp_path
    coordinator.stop()
    ps.stop()


def make_worker(coord_port, worker_id, iterations=6):
    config = WorkerConfig(
        coordinator_address=f"127.0.0.1:{coord_port}",
        worker_id=worker_id, iterations=iterations,
        address="127.0.0.1", port=50060 + worker_id,
        batch_size=16, model="mnist_mlp",
        heartbeat_period_s=1.0)
    return build_worker(config)


def run_workers(workers, iterations):
    """Drive N workers in lockstep threads (the barrier synchronizes them)."""
    losses = {w.config.worker_id: [] for w in workers}
    errors = []

    def loop(worker):
        try:
            for it in range(iterations):
                losses[worker.config.worker_id].append(worker.run_iteration(it))
        except Exception as exc:  # noqa: BLE001
            errors.append((worker.config.worker_id, exc))

    threads = [threading.Thread(target=loop, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, f"worker failures: {errors}"
    return losses


def test_two_worker_sync_training_loss_decreases(cluster):
    ps, coordinator, coord_port, tmp_path = cluster
    workers = [make_worker(coord_port, 0), make_worker(coord_port, 1)]
    for w in workers:
        w.initialize()
    assert coordinator.core.live_worker_count() == 2
    try:
        losses = run_workers(workers, 8)
    finally:
        for w in workers:
            w.shutdown()
    # iteration 0 is the bootstrap (nan); real losses from iteration 1 on
    for wid, history in losses.items():
        assert len(history) == 8
        real = history[1:]
        assert not np.isnan(real).any()
        # learning signal: mean of last 3 < first loss
        assert np.mean(real[-3:]) < real[0], f"worker {wid}: {real}"
    assert ps.core.current_iteration == 7


def test_autosave_and_rpc_restore_roundtrip(cluster):
    ps, coordinator, coord_port, tmp_path = cluster
    worker = make_worker(coord_port, 0)
    # shrink barrier to 1 for a single-worker run (elastic-style)
    ps.core.set_total_workers(1)
    worker.initialize()
    try:
        for it in range(5):
            worker.run_iteration(it)
        # epoch = 4 // 2 = 2 -> autosave writes checkpoint_epoch_2.ckpt
        path = ps.ckpt.maybe_autosave()
        assert path is not None and path.endswith("checkpoint_epoch_2.ckpt")
        before = ps.core.get_parameters()
        # keep training, then restore via the worker-facing RPC
        for it in range(5, 7):
            worker.run_iteration(it)
        after = ps.core.get_parameters()
        assert any(not np.array_equal(before[k], after[k]) for k in before)
        assert worker.load_checkpoint_from_server(path)
        restored = ps.core.get_parameters()
        for k in before:
            np.testing.assert_array_equal(restored[k], before[k])
    finally:
        worker.shutdown()


def test_worker_reconnect_after_coordinator_restart(cluster):
    ps, coordinator, coord_port, tmp_path = cluster
    worker = make_worker(coord_port, 0)
    worker.initialize()
    try:
        # coordinator forgets the worker (simulates eviction); re-register
        evicted = coordinator.core.remove_stale_workers(timeout_s=-1)
        assert evicted == [0]
        worker.reconnect()
        assert coordinator.core.live_worker_count() == 1
    finally:
        worker.shutdown()


def test_bf16_wire_training_loss_decreases(cluster):
    """Workers configured with --wire=bf16 train end to end; the PS decodes
    the packed payloads transparently and learning still happens."""
    ps, coordinator, coord_port, _ = cluster
    workers = []
    for wid in range(2):
        config = WorkerConfig(
            coordinator_address=f"127.0.0.1:{coord_port}",
            worker_id=wid, iterations=5,
            address="127.0.0.1", port=50060 + wid,
            batch_size=16, model="mnist_mlp",
            heartbeat_period_s=600.0, wire_dtype="bf16")
        w = build_worker(config)
        w.initialize()
        workers.append(w)
    try:
        losses = run_workers(workers, 5)
        for wid, series in losses.items():
            real = [x for x in series if np.isfinite(x)]
            assert len(real) >= 3
            assert real[-1] < real[0], f"worker {wid} loss did not decrease"
    finally:
        for w in workers:
            w.shutdown()


def test_unknown_wire_dtype_rejected(cluster):
    _, _, coord_port, _ = cluster
    with pytest.raises(ValueError, match="wire_dtype"):
        build_worker(WorkerConfig(
            coordinator_address=f"127.0.0.1:{coord_port}", worker_id=0,
            wire_dtype="fp16"))


def test_device_apply_training_loss_decreases(tmp_path, monkeypatch):
    """ISSUE 11 acceptance: with PSDT_DEVICE_APPLY=1 and a device
    optimizer selected, the existing two-worker e2e training run has
    zero failed steps and the same learning signal — the barrier closes
    are accelerator-resident end to end (device folds via
    core.device_fold, sharded device apply, async readback feeding the
    serve encodes)."""
    monkeypatch.setenv("PSDT_DEVICE_APPLY", "1")
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.05, optimizer="device_sgd",
        autosave_period_s=600.0))
    from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
        ShardedDeviceOptimizer)

    assert isinstance(ps.core._optimizer, ShardedDeviceOptimizer)
    assert ps.core.device_fold
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()
    workers = [make_worker(coord_port, 0), make_worker(coord_port, 1)]
    try:
        for w in workers:
            w.initialize()
        losses = run_workers(workers, 8)  # asserts zero failed steps
    finally:
        for w in workers:
            w.shutdown()
        coordinator.stop()
        ps.stop()
    for wid, history in losses.items():
        real = history[1:]  # iteration 0 is the bootstrap (nan)
        assert not np.isnan(real).any()
        assert np.mean(real[-3:]) < real[0], f"worker {wid}: {real}"
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    # the closes really ran device-resident
    assert obs_stats.REGISTRY.snapshot()["counters"].get(
        "ps.apply.device", 0) >= 7


def test_arena_apply_training_loss_decreases(tmp_path, monkeypatch):
    """ISSUE 15 acceptance, end to end: PSDT_ARENA=1 on top of the
    device apply runs the same two-worker training over the real gRPC
    plane with zero failed steps and the same learning signal — folds
    scatter into the per-stripe sum arenas, the closes run flat, and
    the serve encodes read the contiguous readback's slab views."""
    from parameter_server_distributed_tpu.obs import stats as obs_stats

    monkeypatch.setenv("PSDT_DEVICE_APPLY", "1")
    monkeypatch.setenv("PSDT_ARENA", "1")
    # (the registry is the process's: another file's fallbacks, run before
    # this one by the same worker, are not this run's)
    before = obs_stats.REGISTRY.snapshot()["counters"]
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.05, optimizer="device_sgd",
        autosave_period_s=600.0))
    assert ps.core._arena is not None
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()
    workers = [make_worker(coord_port, 0), make_worker(coord_port, 1)]
    try:
        for w in workers:
            w.initialize()
        losses = run_workers(workers, 8)  # asserts zero failed steps
    finally:
        for w in workers:
            w.shutdown()
        coordinator.stop()
        ps.stop()
    for wid, history in losses.items():
        real = history[1:]
        assert not np.isnan(real).any()
        assert np.mean(real[-3:]) < real[0], f"worker {wid}: {real}"
    # the closes really ran FLAT (post-bootstrap; the seed close has no
    # table yet), with no silent per-tensor fallbacks
    counters = obs_stats.REGISTRY.snapshot()["counters"]

    def moved(name):
        return counters.get(name, 0) - before.get(name, 0)

    assert moved("ps.apply.arena") >= 6
    assert moved("ps.apply.arena_fallback") == 0


def test_bf16_worker_falls_back_against_f32_only_ps(tmp_path):
    """A PS that ignores the packed extension (the reference's behavior: it
    skips unknown fields) must not receive packed pushes — the worker detects
    the f32-only response on its first pull and downgrades itself."""
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.05, autosave_period_s=600.0))
    seen_encodings = []
    orig_serve = type(ps.service).ServeParameters
    orig_recv = type(ps.service).ReceiveGradients

    def serve_f32_only(request, context):
        request.wire_dtype = 0  # a reference PS never sees field 3
        return orig_serve(ps.service, request, context)

    def recording_recv(request, context):
        seen_encodings.extend(t.packed_dtype for t in request.gradients)
        return orig_recv(ps.service, request, context)

    def unimplemented_stream(request, context):
        # a reference PS has no chunk-stream extension methods at all; an
        # unknown method surfaces to the client as UNIMPLEMENTED, which is
        # exactly what aborting here produces
        import grpc
        context.abort(grpc.StatusCode.UNIMPLEMENTED,
                      "reference PS: no streaming data plane")

    # patch BEFORE start(): bind_service captures bound methods at bind time
    ps.service.ServeParameters = serve_f32_only
    ps.service.ReceiveGradients = recording_recv
    ps.service.PushGradientsStream = unimplemented_stream
    ps.service.ServeParametersStream = unimplemented_stream
    ps.service.PushPullStream = unimplemented_stream  # no fused plane either
    # nor the versioned-delta extension (delta/, ISSUE 10): a bf16 delta
    # pull would mask the f32-only unary response the downgrade keys on
    ps.service.PullParametersDelta = unimplemented_stream
    ps.service.PushPullDeltaStream = unimplemented_stream
    ps.service.SubscribeWeights = unimplemented_stream
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0,
        ps_address="127.0.0.1", ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()

    workers = []
    try:
        for wid in range(2):
            w = build_worker(WorkerConfig(
                coordinator_address=f"127.0.0.1:{coord_port}",
                worker_id=wid, iterations=3, address="127.0.0.1",
                port=50060 + wid, batch_size=16, model="mnist_mlp",
                heartbeat_period_s=600.0, wire_dtype="bf16"))
            w.initialize()
            workers.append(w)
        losses = run_workers(workers, 3)
        # every push that reached the PS was plain f32 (no invisible payloads)
        assert seen_encodings and all(e == 0 for e in seen_encodings)
        for wid, series in losses.items():
            real = [x for x in series if np.isfinite(x)]
            assert real and real[-1] < real[0]
    finally:
        for w in workers:
            w.shutdown()
        coordinator.stop()
        ps.stop()


def test_int8_error_feedback_cancels_quantization_bias():
    """Pushing the same gradient repeatedly with error feedback: the mean
    of what the PS decodes converges to the true gradient, far below the
    single-shot quantization error."""
    from parameter_server_distributed_tpu.cli.worker_main import build_worker

    w = build_worker(WorkerConfig(worker_id=0, wire_dtype="int8",
                                  heartbeat_period_s=600.0))
    try:
        w._peer_packed_ok = True  # pretend negotiation succeeded
        rng = np.random.default_rng(0)
        g = {"w": rng.standard_normal(512).astype(np.float32)}
        decoded = []
        for _ in range(64):
            tensors, residual = w._compress_with_feedback(g, 3)  # WIRE_INT8
            w._ef_residual = residual  # as a successful push would
            decoded.append(tensors[0].to_array())
        single_err = np.abs(decoded[0] - g["w"]).max()
        mean_err = np.abs(np.mean(decoded, axis=0) - g["w"]).max()
        assert mean_err < single_err / 5  # bias cancelled over pushes
        assert any(np.abs(r).sum() > 0 for r in w._ef_residual.values())
    finally:
        w.shutdown()


def test_topk_error_feedback_delivers_full_mass():
    """Top-k sparsified pushes at 25% density: each push delivers only
    the largest entries, but the residual carries everything unsent —
    including the bf16 rounding of what WAS sent — so the telescoping
    identity sum(decoded pushes) + final_residual == N * true_gradient
    holds exactly (nothing is ever dropped, only deferred)."""
    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.rpc import messages as m

    w = build_worker(WorkerConfig(worker_id=0, wire_dtype="topk",
                                  topk_density=0.25,
                                  heartbeat_period_s=600.0))
    try:
        w._peer_packed_ok = True
        rng = np.random.default_rng(0)
        g = {"w": rng.standard_normal(256).astype(np.float32)}
        total = np.zeros(256, np.float32)
        n = 64
        for _ in range(n):
            tensors, residual = w._compress_with_feedback(g, m.WIRE_TOPK)
            w._ef_residual = residual
            arr = tensors[0].to_array()
            assert np.count_nonzero(arr) <= 64  # 25% of 256
            total += arr
        np.testing.assert_allclose(total + w._ef_residual["w"],
                                   n * g["w"], atol=1e-3)
        # and the deferred mass is bounded: the mean of what the PS saw
        # tracks the true gradient to O(residual / n)
        bound = np.abs(w._ef_residual["w"]).max() / n + 1e-3
        assert np.abs(total / n - g["w"]).max() <= bound
    finally:
        w.shutdown()


def test_int8_wire_training_loss_decreases(cluster):
    """End to end: int8 error-feedback pushes + bf16 pulls still learn."""
    ps, coordinator, coord_port, _ = cluster
    workers = []
    for wid in range(2):
        w = build_worker(WorkerConfig(
            coordinator_address=f"127.0.0.1:{coord_port}",
            worker_id=wid, iterations=5, address="127.0.0.1",
            port=50060 + wid, batch_size=16, model="mnist_mlp",
            heartbeat_period_s=600.0, wire_dtype="int8"))
        w.initialize()
        workers.append(w)
    try:
        losses = run_workers(workers, 5)
        for wid, series in losses.items():
            real = [x for x in series if np.isfinite(x)]
            assert len(real) >= 3
            assert real[-1] < real[0], f"worker {wid} loss did not decrease"
        # error feedback engaged on both workers
        for w in workers:
            assert w._wire_dtype == 3 and w._ef_residual
    finally:
        for w in workers:
            w.shutdown()


def test_topk_wire_training_loss_decreases(cluster):
    """End to end: top-k sparsified error-feedback pushes (10% density)
    + bf16 pulls still learn over real gRPC."""
    ps, coordinator, coord_port, _ = cluster
    workers = []
    for wid in range(2):
        w = build_worker(WorkerConfig(
            coordinator_address=f"127.0.0.1:{coord_port}",
            worker_id=wid, iterations=5, address="127.0.0.1",
            port=50070 + wid, batch_size=16, model="mnist_mlp",
            heartbeat_period_s=600.0, wire_dtype="topk",
            topk_density=0.1))
        w.initialize()
        workers.append(w)
    try:
        losses = run_workers(workers, 5)
        for wid, series in losses.items():
            real = [x for x in series if np.isfinite(x)]
            assert len(real) >= 3
            assert real[-1] < real[0], f"worker {wid} loss did not decrease"
        for w in workers:
            assert w._wire_dtype == 4 and w._ef_residual  # WIRE_TOPK
    finally:
        for w in workers:
            w.shutdown()
