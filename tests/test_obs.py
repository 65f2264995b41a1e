"""Observability subsystem tests (obs/): span propagation over real gRPC
(including after packed-wire renegotiation), log-bucket histogram
percentile correctness, Chrome-trace JSON validity, coordinator rollup of
worker snapshots, and wire-byte accounting through the throttled relay
(compressed pushes must actually shrink on-the-wire traffic)."""

import json

import numpy as np
import pytest

from parameter_server_distributed_tpu.cli import status_main
from parameter_server_distributed_tpu.cli.worker_main import build_worker
from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                     ParameterServerConfig,
                                                     WorkerConfig)
from parameter_server_distributed_tpu.core.tensor import to_wire
from parameter_server_distributed_tpu.obs import export as obs_export
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.obs import trace as obs_trace
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc.service import RpcClient
from parameter_server_distributed_tpu.server.coordinator_service import Coordinator
from parameter_server_distributed_tpu.server.ps_service import ParameterServer
from parameter_server_distributed_tpu.utils.netsim import ThrottledRelay


@pytest.fixture
def tracing():
    obs_trace.clear()
    obs_trace.enable(True)
    yield
    obs_trace.enable(False)
    obs_trace.clear()


@pytest.fixture
def cluster1(tmp_path):
    """One-worker cluster: PS (barrier of 1) + coordinator, real sockets."""
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.05, autosave_period_s=600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0,
        ps_address="127.0.0.1", ps_port=ps_port, reap_period_s=600.0))
    coord_port = coordinator.start()
    yield ps, ps_port, coordinator, coord_port
    coordinator.stop()
    ps.stop()


def _ps_client(port):
    return RpcClient(f"127.0.0.1:{port}", m.PARAMETER_SERVER_SERVICE,
                     m.PARAMETER_SERVER_METHODS)


# ---------------------------------------------------------------- stats
def test_histogram_percentiles_within_bucket_error():
    h = obs_stats.Histogram()
    values = np.random.default_rng(0).lognormal(-3.0, 1.0, size=5000)
    for v in values:
        h.observe(v)
    # geometric buckets at ratio 2**0.25: any percentile read off a bucket
    # midpoint is within ~9% of the true value (stats.py docstring)
    for q in (50, 95, 99):
        true = float(np.percentile(values, q))
        assert abs(h.percentile(q) - true) / true < 0.10, q
    s = h.summary()
    assert s["count"] == 5000
    assert s["min"] == pytest.approx(values.min())
    assert s["max"] == pytest.approx(values.max())
    assert s["mean"] == pytest.approx(values.mean(), rel=1e-6)


def test_histogram_percentile_survives_json_roundtrip():
    """Bucket keys become strings when a snapshot rides a heartbeat as
    JSON; percentile_from must read both forms identically."""
    h = obs_stats.Histogram()
    for v in (0.001, 0.01, 0.1, 1.0) * 10:
        h.observe(v)
    snap = json.loads(json.dumps(h.snapshot()))
    for q in (50, 95):
        assert obs_stats.percentile_from(snap, q) == h.percentile(q)


def test_histogram_zeros_and_clamping():
    h = obs_stats.Histogram()
    for v in (0.0, -1.0, 5.0):
        h.observe(v)
    assert h.percentile(50) <= 0.0       # rank 2 of 3 is a non-positive
    assert h.percentile(99) == 5.0       # clamped to observed max
    assert h.snapshot()["zeros"] == 2


def test_registry_type_conflict_raises():
    r = obs_stats.Registry()
    r.counter("x")
    with pytest.raises(TypeError):
        r.histogram("x")


# ---------------------------------------------------------------- trace
def test_wire_context_empty_when_disabled():
    assert not obs_trace.enabled()
    assert obs_trace.wire_context() == b""
    # field 999 elides at its default: the encoded bytes are identical to
    # a message that never heard of the extension
    upd = m.GradientUpdate(worker_id=1, iteration=2, gradients=[])
    assert upd.trace_context == b""
    assert b"\xba\x3e" not in upd.encode()  # tag of field 999/wiretype 2


def test_chrome_trace_export_and_merge(tmp_path, tracing):
    with obs_trace.span("outer", worker=0):
        with obs_trace.span("inner"):
            pass
    path = obs_trace.export_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert events and all(
        e["ph"] == "X" and e["dur"] > 0 and {"ts", "pid", "tid"} <= set(e)
        for e in events)
    by_name = {e["name"]: e for e in events}
    outer, inner = by_name["outer"], by_name["inner"]
    assert inner["args"]["trace_id"] == outer["args"]["trace_id"]
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["args"]["worker"] == 0
    merged = obs_trace.merge_chrome_traces(
        [path, path], str(tmp_path / "merged.json"))
    with open(merged) as fh:
        assert len(json.load(fh)["traceEvents"]) == 2 * len(events)


def test_span_context_parse_rejects_garbage():
    assert obs_trace.parse_context(b"") is None
    assert obs_trace.parse_context(b"\xff\xfe") is None
    assert obs_trace.parse_context(b"notlongenough/x") is None
    obs_trace.enable(True)
    try:
        with obs_trace.span("s"):
            ctx = obs_trace.wire_context()
            trace_id, span_id = obs_trace.parse_context(ctx)
            assert (trace_id, span_id) == obs_trace.current()
    finally:
        obs_trace.enable(False)
        obs_trace.clear()


@pytest.mark.parametrize("iteration", [None, 0, 7])
def test_span_holder_adopts_the_chunks_iteration(tracing, iteration):
    """What a handler's thread opens after the context arrived inherits
    the chunk's iteration (0 is a round like any other); what it did
    before keeps none, and neither does anything after ``finish``."""
    with obs_trace.span("caller"):
        ctx = obs_trace.wire_context()
    caller_trace, caller_span = obs_trace.parse_context(ctx)
    holder = obs_trace.SpanHolder("handler")
    with obs_trace.span("before"):
        pass
    holder.adopt(ctx, iteration)
    holder.adopt(ctx, 99)               # the first context wins
    with obs_trace.span("leg"):
        with obs_trace.timed("frame") as frame:
            with frame.carve("blocked"):
                pass
    holder.finish()
    with obs_trace.span("after"):
        pass
    spans = {s["name"]: s for s in obs_trace.spans()}
    want = {} if iteration is None else {"iteration": iteration}
    for name in ("handler", "leg", "frame", "blocked"):
        assert spans[name].get("args", {}) == want, name
        assert spans[name]["trace_id"] == caller_trace
    assert spans["handler"]["parent_id"] == caller_span
    assert spans["leg"]["parent_id"] == spans["handler"]["span_id"]
    for name in ("before", "after"):
        assert "args" not in spans[name]
        assert spans[name]["trace_id"] != caller_trace


def test_phases_cut_a_block_without_a_gap_under_one_parent(tracing):
    """``phases``: spans that follow one another to the clock's digit, each
    a child of the span open around the block with its iteration, none of
    them on the stack (what the block opens keeps its parent)."""
    with obs_trace.span("call", iteration=5):
        with obs_trace.phases("a", transport="shm") as phase:
            with obs_trace.span("leg"):
                pass
            phase.next("b")
            phase.next("c")
            assert obs_trace.current()[1] != ""
    spans = {s["name"]: s for s in obs_trace.spans()}
    a, b, c, call = (spans[n] for n in ("a", "b", "c", "call"))
    assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-9)
    assert b["ts"] + b["dur"] == pytest.approx(c["ts"], abs=1e-9)
    assert call["ts"] <= a["ts"] and \
        c["ts"] + c["dur"] <= call["ts"] + call["dur"]
    for phase in (a, b, c):
        assert phase["parent_id"] == call["span_id"]
        assert phase["trace_id"] == call["trace_id"]
        assert phase["args"] == {"transport": "shm", "iteration": 5}
    assert len({a["span_id"], b["span_id"], c["span_id"]}) == 3
    assert spans["leg"]["parent_id"] == call["span_id"]


def test_phases_cost_no_span_when_recording_is_off():
    obs_trace.clear()
    with obs_trace.phases("a") as phase:
        phase.next("b")
    assert obs_trace.spans() == []


def test_span_propagates_over_grpc(cluster1, tracing):
    """Client span -> request extension field -> server handler span, in
    one trace; the PS-side ps/serve span nests under the handler."""
    ps, ps_port, _, _ = cluster1
    ps.service.core.initialize_parameters(
        {"w": np.array([1.0, 2.0], np.float32)})
    with _ps_client(ps_port) as client:
        with obs_trace.span("test/root"):
            client.call("ServeParameters",
                        m.PullRequest(worker_id=0, iteration=1))
    spans = {s["name"]: s for s in obs_trace.spans()}
    root = spans["test/root"]
    cli = spans["rpc/client/ServeParameters"]
    srv = spans["rpc/server/ServeParameters"]
    serve = spans["ps/serve"]
    assert cli["trace_id"] == root["trace_id"]
    assert srv["trace_id"] == root["trace_id"]
    assert srv["parent_id"] == cli["span_id"]
    assert serve["trace_id"] == root["trace_id"]
    assert serve["parent_id"] == srv["span_id"]


@pytest.mark.slow
def test_step_trace_spans_one_trace_after_packed_renegotiation(
        cluster1, tracing, tmp_path):
    """One training step's spans — worker pull -> compute -> push -> PS
    apply — share a single trace id, and still do after the first pull
    flips the packed-wire negotiation (the trace context rides every
    chunk of the streamed packed push)."""
    _, _, coordinator, coord_port = cluster1
    w = build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{coord_port}", worker_id=0,
        iterations=3, address="127.0.0.1", port=50070, batch_size=16,
        model="mnist_mlp", heartbeat_period_s=600.0, wire_dtype="bf16"))
    w.initialize()
    try:
        w.run_iteration(0)            # bootstrap push (empty first pull)
        w.run_iteration(1)            # first non-empty pull renegotiates
        assert w._peer_packed_ok
        obs_trace.clear()
        w.run_iteration(2)            # fully post-renegotiation step
        spans = obs_trace.spans()
        steps = [s for s in spans if s["name"] == "worker/step"]
        assert len(steps) == 1
        tid = steps[0]["trace_id"]
        names_in_trace = {s["name"] for s in spans
                          if s["trace_id"] == tid}
        # steady state rides the fused data plane: the step's whole
        # communication is one worker/fused span, and the PS-side apply
        # still joins the worker's trace (context rides every chunk)
        assert {"worker/step", "worker/fused",
                "worker/compute", "ps/apply"} <= names_in_trace, \
            names_in_trace
        # and the Chrome-trace export keeps the correlation in args
        path = obs_trace.export_chrome_trace(str(tmp_path / "step.json"))
        with open(path) as fh:
            events = json.load(fh)["traceEvents"]
        assert {"worker/fused", "ps/apply"} <= {
            e["name"] for e in events if e["args"]["trace_id"] == tid}
        # heartbeat piggyback: the coordinator aggregates this worker
        assert w.send_heartbeat()
        rollup = coordinator.service.aggregator.rollup()
        assert rollup["cluster"]["workers"] == 1
        assert rollup["per_worker"][0]["rpc"], "expected client RPC stats"
        assert rollup["per_worker"][0]["bytes_sent"] > 0
    finally:
        w.shutdown()


# --------------------------------------------------------------- export
def _fake_snapshot(step_s: float, nbytes: int) -> bytes:
    h = obs_stats.Histogram()
    for _ in range(8):
        h.observe(step_s)
    lat = obs_stats.Histogram()
    for _ in range(4):
        lat.observe(step_s / 10)
    snap = {"counters": {"rpc.client.ReceiveGradients.request_bytes": nbytes,
                         "rpc.client.retries": 1},
            "gauges": {},
            "histograms": {
                "worker.step_s": h.snapshot(),
                "rpc.client.ReceiveGradients.latency_s": lat.snapshot()},
            "t": 1.0}
    return json.dumps(snap).encode()


def test_cluster_aggregator_rolls_up_two_workers():
    agg = obs_export.ClusterAggregator()
    assert agg.ingest(0, _fake_snapshot(0.1, 1000))
    assert agg.ingest(1, _fake_snapshot(0.4, 3000))
    assert not agg.ingest(1, b"\xff not json")   # garbage is dropped
    rollup = agg.rollup()
    assert rollup["cluster"]["workers"] == 2
    assert rollup["cluster"]["bytes_sent"] == 4000
    straggler = rollup["cluster"]["straggler"]
    assert straggler["slowest_worker"] == 1
    assert straggler["spread"] == pytest.approx(4.0, rel=0.25)
    worst = rollup["cluster"]["slowest_rpc"]["ReceiveGradients"]
    assert worst["worker"] == 1
    text = obs_export.render_rollup(rollup)
    assert "2 workers" in text and "ReceiveGradients" in text


def test_status_cli_metrics_view(cluster1, capsys):
    """pst-status --metrics against a live coordinator prints the rollup
    aggregated from heartbeat-piggybacked snapshots."""
    _, _, coordinator, coord_port = cluster1
    with RpcClient(f"127.0.0.1:{coord_port}", m.COORDINATOR_SERVICE,
                   m.COORDINATOR_METHODS) as coord:
        coord.call("RegisterWorker",
                   m.WorkerInfo(worker_id=0, address="127.0.0.1",
                                port=50060, hostname="h0"))
        coord.call("Heartbeat",
                   m.HeartbeatRequest(worker_id=0,
                                      status=m.WorkerStatus.TRAINING,
                                      obs_snapshot=_fake_snapshot(0.2, 512)))
    assert status_main.main([f"127.0.0.1:{coord_port}", "--metrics"]) == 0
    out = capsys.readouterr().out
    assert "cluster metrics (1 workers reporting)" in out
    assert "rpc ReceiveGradients" in out
    assert status_main.main([f"127.0.0.1:{coord_port}",
                             "--metrics-json"]) == 0
    rollup = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rollup["per_worker"]["0"]["bytes_sent"] == 512


# --------------------------------------------------------------- netsim
def test_relay_byte_counters_show_compression_win(cluster1):
    """Push the same gradients as f32 and as bf16 through the throttled
    relay: the bf16 push must put measurably fewer bytes on the wire
    (this is the assertion loopback benchmarks could never make)."""
    ps, ps_port, _, _ = cluster1
    grads = {"w": np.random.default_rng(1).standard_normal(
        8192).astype(np.float32)}
    ps.service.core.initialize_parameters(
        {"w": np.zeros(8192, np.float32)})
    relay = ThrottledRelay(ps_port)
    relay_port = relay.start()
    try:
        sizes = {}
        for it, dtype in ((1, m.WIRE_F32), (2, m.WIRE_BF16)):
            relay.reset_byte_counts()
            with _ps_client(relay_port) as client:
                resp = client.call(
                    "ReceiveGradients",
                    m.GradientUpdate(worker_id=0, iteration=it,
                                     gradients=to_wire(grads, dtype)))
                assert resp.success
            to_target, from_target = relay.byte_counts()
            assert from_target > 0        # response came back through it
            sizes[dtype] = to_target
        assert sizes[m.WIRE_F32] > 4 * 8192     # f32 payload dominates
        assert sizes[m.WIRE_BF16] < 0.7 * sizes[m.WIRE_F32]
    finally:
        relay.stop()
