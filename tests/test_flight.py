"""Flight recorder + pst-trace postmortems (ISSUE 8): ring roundtrip and
wraparound, kill -9 crash survival, the lockcheck-marked multi-thread
write hammer, timeline/critical-path reconstruction, the pst-trace golden
run over a netsim failover, the shm exactly-once segment release, and the
pst-status --watch time-series ring."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.cli.status_main import (
    render_watch_line, rollup_to_snapshot)
from parameter_server_distributed_tpu.cli.trace_main import main as trace_main
from parameter_server_distributed_tpu.obs import flight, postmortem
from parameter_server_distributed_tpu.obs.stats import (TimeSeriesRing,
                                                        snapshot_rates)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ring_dir(tmp_path):
    """A flight directory; the module-global recorder is torn down after
    the test so the rest of the suite stays unrecorded."""
    yield str(tmp_path / "flight")
    flight.disable()


# ----------------------------------------------------------- ring mechanics

def test_ring_roundtrip_fields(ring_dir):
    flight.enable(ring_dir, role="ps:127.0.0.1:1", records=64)
    flight.record("push.commit", iteration=7, worker=3, a=2, b=4,
                  note="hello")
    flight.record("barrier.publish", iteration=7, a=4, b=4)
    flight.disable()
    rings = postmortem.load_rings(ring_dir)
    assert len(rings) == 1
    ring = rings[0]
    assert ring["role"] == "ps:127.0.0.1:1"
    assert ring["clean"] is True
    assert ring["pid"] == os.getpid()
    events = {e["event"]: e for e in ring["events"]}
    assert events["push.commit"]["iteration"] == 7
    assert events["push.commit"]["worker"] == 3
    assert events["push.commit"]["a"] == 2
    assert events["push.commit"]["note"] == "hello"
    # lifecycle markers bracket the payload events
    assert ring["events"][0]["event"] == "proc.start"
    assert ring["events"][-1]["event"] == "proc.exit"


def test_ring_wraparound_keeps_newest(ring_dir):
    flight.enable(ring_dir, role="wrap", records=16)
    for i in range(50):
        flight.record("fold.reserve", iteration=i, worker=0, a=i)
    flight.disable()
    ring = postmortem.load_rings(ring_dir)[0]
    seqs = [e["seq"] for e in ring["events"]]
    # exactly one ring's worth survives, contiguous, ending at the newest
    assert len(seqs) == 16
    assert seqs == list(range(seqs[0], seqs[0] + 16))
    assert ring["dropped"] == seqs[0] - 1 > 0
    assert ring["events"][-1]["event"] == "proc.exit"


def test_note_truncation_and_unknown_code(ring_dir):
    flight.enable(ring_dir, role="t", records=32)
    flight.record("shm.refuse", note="x" * 100)
    rec = flight.recorder()
    rec.record_event(9999, a=5)  # future event code: stays decodable
    flight.disable()
    events = postmortem.load_rings(ring_dir)[0]["events"]
    by = {e["event"]: e for e in events}
    assert by["shm.refuse"]["note"] == "x" * 48
    assert by["ev9999"]["a"] == 5


def test_sampling_thins_hot_events(ring_dir):
    flight.enable(ring_dir, role="s", records=4096, sample=10)
    for _ in range(100):
        flight.record("fold.reserve", iteration=1, worker=0)
    for _ in range(100):
        flight.record("push.commit", iteration=1, worker=0)  # not sampled
    flight.disable()
    events = postmortem.load_rings(ring_dir)[0]["events"]
    folds = [e for e in events if e["event"] == "fold.reserve"]
    commits = [e for e in events if e["event"] == "push.commit"]
    assert len(folds) == 10  # 1-in-10
    assert len(commits) == 100  # structural events are never sampled


# --------------------------------------------------------- crash survival

def test_kill9_crash_survival_and_postmortem(ring_dir):
    """THE crash-survival acceptance: a child process records events,
    dies by SIGKILL (no atexit, no flush), and its on-disk ring decodes
    — pst-trace marks it DIED and its last events are readable."""
    child_src = (
        "import sys, time\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from parameter_server_distributed_tpu.obs import flight\n"
        f"flight.enable({ring_dir!r}, role='ps:victim', records=256)\n"
        "flight.record('push.commit', iteration=5, worker=1, a=1, b=2)\n"
        "flight.record('barrier.seal', iteration=5, a=2)\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    proc = subprocess.Popen([sys.executable, "-c", child_src],
                            stdout=subprocess.PIPE)
    try:
        line = proc.stdout.readline()
        assert b"READY" in line
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    rings = postmortem.load_rings(ring_dir)
    victim = next(r for r in rings if r["role"] == "ps:victim")
    assert victim["clean"] is False  # died: no clean-shutdown marker
    names = [e["event"] for e in victim["events"]]
    assert "push.commit" in names and "barrier.seal" in names
    assert "proc.exit" not in names  # SIGKILL skipped the atexit path
    rep = postmortem.report(ring_dir)
    dead = rep["narrative"]["dead_processes"]
    assert any(d["role"] == "ps:victim" for d in dead)
    text = postmortem.render_report(rep)
    assert "DIED" in text


# -------------------------------------------------------- concurrency hammer

@pytest.mark.lockcheck
def test_multithread_flight_write_hammer(ring_dir):
    """8 threads hammer the lock-free record path: every record must land
    exactly once (unique contiguous seqs, no torn notes), under
    PSDT_LOCK_CHECK=1 so any lock the recorder DID take would be
    order-asserted."""
    flight.enable(ring_dir, role="hammer", records=32768)
    n_threads, per_thread = 8, 500
    start = threading.Barrier(n_threads)

    def writer(tid: int) -> None:
        start.wait()
        for i in range(per_thread):
            flight.record("push.commit", iteration=i, worker=tid,
                          a=tid * per_thread + i, note=f"t{tid}")

    threads = [threading.Thread(target=writer, args=(t,), daemon=True,
                                name=f"flight-hammer-{t}")
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    flight.disable()
    ring = postmortem.load_rings(ring_dir)[0]
    commits = [e for e in ring["events"] if e["event"] == "push.commit"]
    assert len(commits) == n_threads * per_thread
    # exactly-once: the distinct payload tokens all arrived, each note
    # consistent with its writer (no torn slot)
    seen = set()
    for e in commits:
        seen.add(e["a"])
        assert e["note"] == f"t{e['worker']}"
    assert len(seen) == n_threads * per_thread
    seqs = sorted(e["seq"] for e in ring["events"])
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))


# ------------------------------------------------- timeline reconstruction

def test_timeline_critical_path_and_straggler(ring_dir):
    flight.enable(ring_dir, role="ps:demo", records=1024)
    flight.record("step.start", iteration=4, worker=0)
    flight.record("step.start", iteration=4, worker=1)
    flight.record("push.commit", iteration=4, worker=0, a=1, b=2)
    time.sleep(0.02)  # worker 1 straggles
    flight.record("push.commit", iteration=4, worker=1, a=2, b=2)
    flight.record("barrier.seal", iteration=4, a=2, b=2)
    flight.record("barrier.drain", iteration=4, a=0)
    flight.record("apply.start", iteration=4)
    flight.record("apply.end", iteration=4, a=1500)
    flight.record("barrier.publish", iteration=4, a=2, b=2)
    flight.record("step.end", iteration=4, worker=0, a=30000)
    flight.record("step.end", iteration=4, worker=1, a=32000)
    flight.disable()
    rep = postmortem.report(ring_dir)  # defaults to last published it
    assert rep["iteration"] == 4
    tl = rep["timeline"]
    assert tl["straggler"] == 1
    assert tl["commit_spread_s"] >= 0.015
    assert tl["contributors"] == 2 and tl["barrier_width"] == 2
    assert tl["apply_s"] == pytest.approx(1500e-6)
    path = rep["critical_path"]
    assert path, "no critical path reconstructed"
    whats = [link["what"] for link in path]
    assert whats[-1] == "barrier publish"
    assert any("worker 1" in w and "closes barrier" in w for w in whats)
    text = postmortem.render_report(rep)
    assert "straggler worker 1" in text
    assert "critical path" in text


def test_pst_trace_cli_text_json_chrome(ring_dir, tmp_path, capsys):
    flight.enable(ring_dir, role="cli", records=256)
    flight.record("step.start", iteration=1, worker=0)
    flight.record("push.commit", iteration=1, worker=0, a=1, b=1)
    flight.record("barrier.publish", iteration=1, a=1, b=1)
    flight.record("step.end", iteration=1, worker=0, a=1000)
    flight.disable()
    assert trace_main([ring_dir]) == 0
    text = capsys.readouterr().out
    assert "flight postmortem" in text and "iteration 1:" in text
    assert trace_main([ring_dir, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["iteration"] == 1
    assert rep["processes"][0]["role"] == "cli"
    out = tmp_path / "merged.json"
    assert trace_main([ring_dir, f"--chrome={out}"]) == 0
    capsys.readouterr()
    doc = json.loads(out.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    # paired start/end became one duration slice; singles are instants
    assert "step" in names and "barrier.publish" in names
    step = next(e for e in doc["traceEvents"] if e["name"] == "step")
    assert step["ph"] == "X" and step["dur"] > 0
    # empty dir: pst-trace reports, not crashes
    assert trace_main([str(tmp_path / "nothing")]) == 1


# ------------------------------------------------ shm exactly-once release

def test_shm_release_segments_exactly_once(ring_dir):
    """The PR-7 flake fix: both reap paths route through the release
    latch — the second caller is a recorded no-op, never a second unmap."""
    shared_memory = pytest.importorskip("multiprocessing.shared_memory")
    del shared_memory
    from parameter_server_distributed_tpu.rpc import shm_transport
    flight.enable(ring_dir, role="shm", records=256)
    server = shm_transport.ShmServer(lambda chunks, ctx: iter(()),
                                     capacity=1 << 16)
    resp = server.negotiate(shm_transport.ShmNegotiateRequest(
        host_id=shm_transport.host_id(), worker_id=0))
    if not resp.accepted:
        pytest.skip(f"shm unavailable: {resp.message}")
    conn = server._conns[0]
    assert conn.release_segments() is True
    assert conn.release_segments() is False  # latched
    server.close()  # shutdown path: third attempt, also absorbed
    flight.disable()
    events = [e["event"]
              for e in postmortem.load_rings(ring_dir)[0]["events"]]
    assert events.count("shm.reap") == 1
    assert events.count("shm.reap.dup") >= 1
    assert "shm.negotiate" in events


def test_shm_ring_invalidate_degrades_cleanly():
    """After invalidate() the ring's native raw-address path is gone: an
    operation on a released segment raises ShmTransportError instead of
    dereferencing a stale base pointer."""
    pytest.importorskip("multiprocessing.shared_memory")
    from multiprocessing import shared_memory

    from parameter_server_distributed_tpu.rpc import shm_transport
    seg = shared_memory.SharedMemory(create=True, size=shm_transport._HEADER
                                     + 4096)
    try:
        ring = shm_transport.ShmRing(seg, 4096)
        hello = shm_transport.ShmNegotiateRequest(host_id="abc")
        ring.write_message(hello, time.monotonic() + 5,
                           "rpc/client/encode")
        ring.invalidate()
        assert ring._base == 0 and ring._copy is None
        # the memoryview fallback still works while the segment is mapped
        assert ring.read_frame(time.monotonic() + 5) == hello.encode()
        seg.close()  # unmap under the ring
        with pytest.raises(shm_transport.ShmTransportError):
            ring.write_message(hello, time.monotonic() + 1,
                               "rpc/client/encode")
    finally:
        try:
            seg.close()
        except Exception:  # noqa: BLE001 — double close in teardown
            pass
        try:
            seg.unlink()
        except FileNotFoundError:
            pass


# ----------------------------------------------------- watch / time series

def test_snapshot_rates_and_ring():
    ring = TimeSeriesRing(capacity=8)
    assert ring.rates() is None
    ring.push({"t": 100.0, "counters": {"x": 10, "restarts": 5},
               "histograms": {"h": {"count": 4, "sum": 2.0}},
               "gauges": {"g": 7.0}})
    ring.push({"t": 102.0, "counters": {"x": 30, "restarts": 2},
               "histograms": {"h": {"count": 8, "sum": 4.0}},
               "gauges": {"g": 9.0}})
    rates = ring.rates()
    assert rates["dt_s"] == pytest.approx(2.0)
    assert rates["counters"]["x"] == pytest.approx(10.0)  # 20 over 2 s
    # a counter that went backward (restart) reads as a burst, not
    # a negative rate
    assert rates["counters"]["restarts"] == pytest.approx(1.0)
    assert rates["histograms"]["h"]["per_s"] == pytest.approx(2.0)
    assert rates["histograms"]["h"]["mean"] == pytest.approx(0.5)
    assert rates["gauges"]["g"] == 9.0
    for i in range(20):
        ring.push({"t": 103.0 + i, "counters": {}, "histograms": {},
                   "gauges": {}})
    assert len(ring) == 8  # bounded


def test_watch_rollup_flatten_and_render():
    rollup = {"per_worker": {
        "0": {"step": {"count": 10, "p50": 0.1, "p95": 0.2, "mean": 0.1},
              "bytes_sent": 1000, "bytes_received": 2000, "rpc": {},
              "phases": {}},
        "1": {"step": {"count": 12, "p50": 0.1, "p95": 0.2, "mean": 0.1},
              "bytes_sent": 1500, "bytes_received": 2500, "rpc": {},
              "phases": {}},
    }}
    snap0 = rollup_to_snapshot(rollup, t=10.0)
    rollup2 = json.loads(json.dumps(rollup))
    rollup2["per_worker"]["0"]["step"]["count"] = 20
    rollup2["per_worker"]["0"]["bytes_sent"] = 3_001_000
    snap1 = rollup_to_snapshot(rollup2, t=12.0)
    rates = snapshot_rates(snap0, snap1)
    line = render_watch_line(rates, workers=2)
    assert "w0=5.00/s" in line  # 10 steps over 2 s
    # a stalled worker must SHOW as 0.00/s, not vanish from the line
    assert "w1=0.00/s" in line
    assert "MB/s out" in line
    baseline = render_watch_line(None, workers=2)
    assert "collecting baseline" in baseline


# ------------------------------------- golden: netsim failover postmortem

def _run_failover_cluster(tmp_path, flight_dir, base_port):
    """Compact netsim failover scenario (mirrors tests/test_replication's
    acceptance scaffold): primary + sync backup behind a ThrottledRelay,
    2 workers; the relay hard-drops mid-run, the backup is promoted, and
    the round retries against it — all recorded into flight rings."""
    import threading as _threading

    from parameter_server_distributed_tpu.cli.worker_main import build_worker
    from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                         ParameterServerConfig,
                                                         WorkerConfig)
    from parameter_server_distributed_tpu.server.coordinator_service import (
        Coordinator)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)
    from parameter_server_distributed_tpu.utils.netsim import ThrottledRelay

    flight.enable(flight_dir, role="cluster", records=65536)
    iterations = 6

    def make_ps(name, **kw):
        ps = ParameterServer(ParameterServerConfig(
            bind_address="127.0.0.1", port=0, total_workers=2,
            checkpoint_dir=str(tmp_path / name), learning_rate=0.1,
            autosave_period_s=600.0, **kw))
        return ps, ps.start()

    backup, bport = make_ps("bk")
    primary, pport = make_ps("pr", backup_address=f"127.0.0.1:{bport}",
                             replication="sync")
    relay = ThrottledRelay(pport)
    relay_port = relay.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=relay_port, ps_backups=(f"127.0.0.1:{bport}",),
        reap_period_s=600.0))
    coord_port = coordinator.start()
    workers = [build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{coord_port}", worker_id=i,
        address="127.0.0.1", port=base_port + i, model="mnist_mlp",
        batch_size=32, heartbeat_period_s=600.0)) for i in range(2)]
    losses = {0: [], 1: []}
    errors = []
    try:
        for w in workers:
            w.initialize()

        def run(w, wid):
            try:
                for it in range(iterations):
                    losses[wid].append(w.run_iteration(it))
            except BaseException as exc:  # noqa: BLE001 — asserted below
                errors.append(exc)

        threads = [_threading.Thread(target=run, args=(w, i), daemon=True,
                                     name=f"flight-worker-{i}")
                   for i, w in enumerate(workers)]
        for t in threads:
            t.start()
        # drop the instant BOTH workers finish the bootstrap iteration:
        # later iterations then provably cross the failover (waiting for
        # 2 completed real iterations can race a fast run to completion)
        deadline = time.monotonic() + 60
        while (min(len(ls) for ls in losses.values()) < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        relay.drop_connections()  # kill the primary mid-run
        for t in threads:
            t.join(timeout=180)
            assert not t.is_alive(), "worker wedged"
        assert not errors, errors
        assert all(len(ls) == iterations for ls in losses.values())
        assert coordinator.core.get_shard_map()[1][0].primary \
            == f"127.0.0.1:{bport}", "promotion never happened"
        return f"127.0.0.1:{bport}"
    finally:
        for w in workers:
            w.shutdown()
        coordinator.stop()
        relay.stop()
        primary.stop(0)
        backup.stop(0)
        flight.disable()


def test_pst_trace_golden_over_netsim_failover(tmp_path, capsys):
    """THE acceptance: pst-trace reconstructs the netsim killed-primary
    failover end-to-end from the on-disk rings, NAMING the promotion
    (shard + promoted backup address) and the retried iteration."""
    flight_dir = str(tmp_path / "flight")
    backup_addr = _run_failover_cluster(tmp_path, flight_dir,
                                        base_port=15700)
    assert trace_main([flight_dir]) == 0
    text = capsys.readouterr().out
    # the promotion is named with the promoted backup's address
    assert "PROMOTION" in text, text
    assert backup_addr in text, text
    # ... and the same-iteration failover retry is named with its number
    assert "RETRIED ITERATION" in text, text
    # the JSON view carries the structured narrative for tooling
    assert trace_main([flight_dir, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    promos = rep["narrative"]["promotions"]
    assert promos and promos[0]["new_primary"] == backup_addr
    retries = rep["narrative"]["failover_retries"]
    assert retries and retries[0]["iteration"] >= 0
    retried_it = retries[0]["iteration"]
    # the retried iteration still published a barrier (zero failed steps)
    assert retried_it in rep["iterations"]["published"]
    # per-iteration timeline of the retried iteration shows the failover
    assert trace_main([flight_dir, f"--iteration={retried_it}",
                       "--json"]) == 0
    tl = json.loads(capsys.readouterr().out)["timeline"]
    assert tl.get("failover_retries"), tl
    # events survived from every edge: barrier close, replication ship,
    # commit stamps
    events = {e["event"]
              for e in postmortem.merge_events(
                  postmortem.load_rings(flight_dir))}
    assert {"push.commit", "barrier.publish", "repl.ship.end",
            "failover.promote", "failover.retry"} <= events


def test_flight_off_by_default_costs_nothing():
    """With no recorder, record() must be a cheap no-op (the always-on
    hot-path budget)."""
    assert not flight.enabled()
    t0 = time.perf_counter()
    for _ in range(10000):
        flight.record("push.commit", iteration=1, worker=0)
    dt = time.perf_counter() - t0
    assert dt < 0.5  # ~µs-scale per call even on a loaded CI box
    rng = np.random.default_rng(0)  # keep numpy import honest
    assert rng is not None
