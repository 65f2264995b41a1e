"""The legs of the PS round and of the decode round (ISSUE 24): spans and
histograms where the work happens, their mirror on the profiler's timeline,
and the ``jax.named_scope`` names on the device operations.  An admission
by its five legs, and the slow leg that keeps its evidence (ISSUE 38,
``obs/legs.py``).

Recording is process-wide state; every test that turns it on goes through
the ``recording`` fixture, which puts it back.
"""

import collections
import contextlib
import gc
import glob
import re
import sys
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from parameter_server_distributed_tpu.cli.worker_main import build_worker
from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                     ParameterServerConfig,
                                                     WorkerConfig)
from parameter_server_distributed_tpu.models import generation, serving
from parameter_server_distributed_tpu.models.serving import DecodeServer
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)
from parameter_server_distributed_tpu.obs import flight
from parameter_server_distributed_tpu.obs import legs as obs_legs
from parameter_server_distributed_tpu.obs import postmortem
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.obs import trace as obs_trace
from parameter_server_distributed_tpu.parallel.train_step import (
    TrainState, make_train_step)
from parameter_server_distributed_tpu.server.coordinator_service import (
    Coordinator)
from parameter_server_distributed_tpu.server.ps_service import ParameterServer

WORKER_LEAVES = ("worker/pack", "worker/h2d", "worker/dispatch",
                 "worker/device_wait", "worker/d2h", "rpc/client/encode",
                 "rpc/client/decode", "rpc/shm/copy", "rpc/shm/wait")
ADMIT_LEGS = ("lookup", "forward", "tree", "first_token", "splice")
SERVING_HISTOGRAMS = ("serve.admit_s", "serve.admit_device_s",
                      "serve.round_device_s", "serve.round_host_s",
                      "serve.between_rounds_s", "serve.round_s",
                      *(f"serve.admit_{leg}_s" for leg in ADMIT_LEGS))


@pytest.fixture
def recording():
    obs_trace.clear()
    obs_trace.enable(True)
    yield
    obs_trace.enable(False)
    obs_trace.clear()


def tiny(**kw):
    cfg = dict(vocab=96, d_model=48, n_heads=4, n_layers=2, d_ff=96,
               max_seq=128, dtype=jnp.float32)
    cfg.update(kw)
    return Transformer(TransformerConfig(**cfg))


def counts() -> dict:
    snap = obs_stats.REGISTRY.snapshot()
    out = {name: snap["histograms"].get(name, {}).get("count", 0)
           for name in SERVING_HISTOGRAMS}
    out["serve.programs"] = snap["counters"].get("serve.programs", 0)
    return out


# ------------------------------------------------ (a) the profiler's mirror
def host_events(trace_dir) -> list[tuple[str, str, float, float]]:
    path = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("psdt/"):
                    out.append((ev.name, f"{line.name}#{index}",
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_spans_are_mirrored_onto_the_profilers_timeline(tmp_path, recording):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("mirror/outer", iteration=7):
            with obs_trace.timed("mirror/inner") as block:
                blocked = block.carve("mirror/carved")
                with blocked:
                    time.sleep(0.002)
                jnp.ones((8, 8)).sum().block_until_ready()
                with blocked:
                    time.sleep(0.002)
        holder = obs_trace.SpanHolder("mirror/holder")
        holder.finish()
        obs_trace.enable(False)
        with obs_trace.span("mirror/off"):
            with obs_trace.timed("mirror/off_timed"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = host_events(tmp_path)
    by_name = collections.defaultdict(list)
    for name, thread, start, end in events:
        by_name[name].append((thread, start, end))
    assert {n: len(v) for n, v in by_name.items()} == {
        "psdt/mirror/outer": 1, "psdt/mirror/inner": 1,
        "psdt/mirror/carved": 2, "psdt/mirror/holder": 1}
    (thread, o0, o1), = by_name["psdt/mirror/outer"]
    (thread_i, i0, i1), = by_name["psdt/mirror/inner"]
    # nested as the spans nest, on the thread that did the work
    assert thread_i == thread and o0 <= i0 and i1 <= o1
    for thread_c, c0, c1 in by_name["psdt/mirror/carved"]:
        assert thread_c == thread and i0 <= c0 and c1 <= i1
    # the buffer: the carved leg once, summed, and the block's own time
    spans = {s["name"]: s for s in obs_trace.spans()}
    assert set(spans) == {"mirror/outer", "mirror/inner", "mirror/carved",
                          "mirror/holder"}
    inner, carved = spans["mirror/inner"], spans["mirror/carved"]
    assert carved["dur"] >= 0.004 and carved["parent_id"] == \
        inner["parent_id"] == spans["mirror/outer"]["span_id"]
    # laid end to end inside the block's real interval, and the iteration
    # comes down from the enclosing span
    assert carved["ts"] + carved["dur"] == pytest.approx(inner["ts"])
    assert inner["args"]["iteration"] == carved["args"]["iteration"] == 7
    # one clock per session: ONE constant ts - start_ns fits every span.
    # No bound on a gap between two statements (a sleep that overshoots, a
    # thread that loses its core) enters: a span's annotation opens before
    # its ts is read and closes after its dur is, a SpanHolder's the other
    # way round, so each span bounds the constant from both sides and a gap
    # only widens its bounds.  1 ms is for the two clocks' own readings.
    (_, h0, h1), = by_name["psdt/mirror/holder"]
    outer, holder = spans["mirror/outer"], spans["mirror/holder"]
    block_end = inner["ts"] + inner["dur"]  # the block opened at carved's ts
    at_most = [outer["ts"] - o0 * 1e-9, carved["ts"] - i0 * 1e-9,
               holder["ts"] + holder["dur"] - h1 * 1e-9]
    at_least = [outer["ts"] + outer["dur"] - o1 * 1e-9,
                block_end - i1 * 1e-9, holder["ts"] - h0 * 1e-9]
    assert max(at_least) <= min(at_most) + 0.001, (at_least, at_most)


def test_a_process_without_jax_is_not_made_to_import_it(monkeypatch,
                                                        recording):
    monkeypatch.delitem(sys.modules, "jax")
    with obs_trace.span("nojax/a"):
        with obs_trace.timed("nojax/b"):
            pass
    assert "jax" not in sys.modules
    assert [s["name"] for s in obs_trace.spans()] == ["nojax/b", "nojax/a"]


# ------------------------------------------------------ (c) recording off
def test_recording_off_opens_nothing(monkeypatch):
    assert not obs_trace.enabled()
    obs_trace.clear()

    def never(*a, **k):
        raise AssertionError("allocated while recording is off")

    monkeypatch.setattr(obs_trace, "_new_id", never)
    monkeypatch.setattr(obs_trace, "_mirror", never)
    monkeypatch.setattr(obs_trace, "_stack", never)
    hist = obs_stats.Histogram()
    with obs_trace.span("off/a", iteration=1):
        with obs_trace.timed("off/b", hist) as block:
            with block.carve("off/c"):
                pass
    with obs_trace.server_span("off/d", b""):
        pass
    obs_trace.SpanHolder("off/e").finish()
    # a watched leg is a ``timed`` and two marks; a slow one keeps its
    # evidence and still opens nothing
    watch = obs_legs.SlowLegs(dict)
    monkeypatch.setattr(obs_legs, "SLOW_LEG_S", 0.0)
    with watch.leg("off/f", hist, n=1) as leg:
        leg.args["m"] = 2
    assert obs_trace.spans() == [] and hist.count == 2
    assert [(r["leg"], r["n"], r["m"]) for r in watch.records] == \
        [("off/f", 1, 2)]


# --------------------------------------------- (b) the decode round's legs
@pytest.mark.parametrize("rec", [False, True], ids=["off", "recording"])
def test_decode_server_legs_once_per_admission_and_round(rng, rec):
    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=4, max_len=64)
    srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=2)
    srv.run_to_completion()            # every program built, server idle
    obs_trace.clear()
    obs_trace.enable(rec)
    try:
        before = counts()
        srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=5)
        srv.step()
        srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=3)
        rounds = 1
        while not srv.idle:
            srv.step()
            rounds += 1
        with pytest.raises(ValueError):
            srv.submit([], max_new_tokens=1)     # refused: no admission
        after = counts()
        spans = collections.Counter(s["name"] for s in obs_trace.spans())
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
    moved = {k: after[k] - before[k] for k in after}
    # always on, with recording off too; the first round after idle has no
    # round before it, the second admission falls between two rounds
    # (no prefix cache: no lookup and no tree leg)
    assert moved == {"serve.admit_s": 2, "serve.admit_device_s": 2,
                     "serve.admit_lookup_s": 0, "serve.admit_tree_s": 0,
                     "serve.admit_forward_s": 2,
                     "serve.admit_first_token_s": 2,
                     "serve.admit_splice_s": 2,
                     "serve.round_device_s": rounds,
                     "serve.round_host_s": rounds, "serve.round_s": rounds,
                     "serve.between_rounds_s": rounds - 1,
                     "serve.programs": 0}
    assert spans == ({"serve/admit": 2, "serve/admit/device": 2,
                      "serve/admit/forward": 2,
                      "serve/admit/first_token": 2, "serve/admit/splice": 2,
                      "serve/round/host": rounds,
                      "serve/round/device": rounds} if rec else {})


def test_round_legs_add_up_and_programs_are_counted(rng):
    model = tiny(d_model=32, n_heads=2)     # a model no other test built
    before = counts()
    srv = DecodeServer(model, model.init_params(0), slots=2, max_len=64)
    srv.submit(list(rng.integers(0, 96, 5)), max_new_tokens=4)
    srv.run_to_completion()
    after = counts()
    # step, prefill and splice programs at the least
    assert after["serve.programs"] - before["serve.programs"] >= 3
    snap = obs_stats.REGISTRY.snapshot()["histograms"]
    parts = (snap["serve.round_device_s"]["sum"]
             + snap["serve.round_host_s"]["sum"])
    assert parts == pytest.approx(snap["serve.round_s"]["sum"], rel=0.05)


# --------------------------------------- (f) a slow leg keeps its evidence
def slow_counters() -> dict:
    counters = obs_stats.REGISTRY.snapshot()["counters"]
    return {name: counters[name] for name in obs_legs.COUNTERS}


def spin(cpu_s: float) -> None:
    """Burn ``cpu_s`` of this thread's CPU time."""
    until = time.thread_time() + cpu_s
    while time.thread_time() < until:
        pass


def fetch_delayed(srv, monkeypatch):
    """The round's tokens come late: the host sleeps in the fetch."""
    real = jax.device_get

    def late(tree):
        monkeypatch.setattr(jax, "device_get", real)
        time.sleep(0.15)
        return real(tree)

    monkeypatch.setattr(jax, "device_get", late)
    srv.step()


def caller_busy(srv, monkeypatch):
    """The caller computes for 0.15 s between two rounds."""
    spin(0.15)
    srv.step()


def gc_in_tree(srv, monkeypatch):
    """A full collection of a large graph during the tree's insert."""
    evict = srv._prefix_tree.evict_over_budget

    def collecting():
        graph = [[] for _ in range(300_000)]
        for node in graph:
            node.append(graph)
        del graph, node
        gc.collect()
        time.sleep(0.1)      # (slow whatever the collector took)
        return evict()

    monkeypatch.setattr(srv._prefix_tree, "evict_over_budget", collecting)
    srv.submit(list(range(40, 52)), max_new_tokens=2)


def slow_admission(srv, monkeypatch):
    """An admission whose lookup takes 0.15 s falls between two rounds:
    its seconds are the lookup's, and not the caller's as well."""
    lookup = srv._prefix_tree.lookup

    def late(key):
        time.sleep(0.15)
        return lookup(key)

    monkeypatch.setattr(srv._prefix_tree, "lookup", late)
    srv.submit(list(range(60, 72)), max_new_tokens=2)
    srv.step()


@pytest.mark.parametrize("make_slow,leg,waits_for_chip", [
    (fetch_delayed, "serve/round/device", True),
    (caller_busy, "serve/caller", False),
    (gc_in_tree, "serve/admit/tree", False),
    (slow_admission, "serve/admit/lookup", False),
], ids=lambda p: getattr(p, "__name__", None))
def test_a_slow_leg_is_kept_under_its_name_with_its_evidence(
        rng, monkeypatch, caplog, make_slow, leg, waits_for_chip):
    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=4, max_len=64,
                       prompt_cache=8)
    for start in (0, 20):       # every program built, then a live request
        srv.submit(list(range(start, start + 12)), max_new_tokens=40)
        srv.step()
        srv.step()
    srv.slow_legs.clear()       # (the compiles')
    caplog.clear()
    before = slow_counters()
    obs_trace.clear()
    obs_trace.enable(True)
    try:
        with caplog.at_level("WARNING", logger=obs_legs.__name__):
            make_slow(srv, monkeypatch)
        spans = [s for s in obs_trace.spans()
                 if s["name"] == "serve/slow_leg"]
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
    record, = srv.slow_legs         # counted once, under one name
    assert record["leg"] == leg
    assert 0.1 < record["wall_s"] < 5.0
    assert 0.0 <= record["cpu_s"] <= record["wall_s"]
    assert abs(record["at"] + record["wall_s"] - time.time()) < 60.0
    assert record["active_slots"] >= 1
    assert record["round_in_flight"] is (srv._flight is not None)
    assert all(record[key] >= 0 for key in (
        "involuntary_switches", "voluntary_switches", "major_faults",
        "programs_built"))
    # the right kind of evidence
    if make_slow is fetch_delayed:
        assert record["cpu_s"] < 0.05               # blocked, not computing
        assert record["voluntary_switches"] >= 1    # it slept
    elif make_slow is caller_busy:
        assert record["cpu_s"] >= 0.14              # computing
    elif make_slow is gc_in_tree:
        assert record["gc_s"] > 0 and record["gc_collections"][2] >= 1
        assert record["evicted"] == 0 and record["tree_bytes"] > 0
        assert record["prompt_tokens"] == 12
    else:
        assert record["cpu_s"] < 0.05
        assert (record["prompt_tokens"], record["matched"]) == (12, 0)
    # the four places and the five counters
    line, = [r.getMessage() for r in caplog.records]
    assert line.startswith(f"slow leg {leg}: ") and '"wall_s"' in line
    span, = spans
    assert span["args"]["leg"] == leg
    assert span["args"]["wall_s"] == record["wall_s"]
    moved = {name: value - before[name]
             for name, value in slow_counters().items()}
    assert moved == {
        "serve.slow_legs": 1,
        "serve.slow_leg_s": pytest.approx(record["wall_s"]),
        "serve.slow_leg_cpu_s": pytest.approx(record["cpu_s"]),
        "serve.slow_leg_gc_s": pytest.approx(record["gc_s"]),
        "serve.slow_leg_device_wait_s": pytest.approx(
            record["wall_s"] if waits_for_chip else 0.0)}
    assert moved["serve.slow_leg_s"] >= 0.1 * moved["serve.slow_legs"]


def test_the_five_counters_read_zero_on_a_fresh_server(monkeypatch):
    monkeypatch.setattr(obs_stats, "REGISTRY", obs_stats.Registry())
    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=2, max_len=32)
    snap = obs_stats.REGISTRY.snapshot()
    assert {name: snap["counters"].get(name)
            for name in obs_legs.COUNTERS} == dict.fromkeys(
                obs_legs.COUNTERS, 0)
    assert len(obs_legs.COUNTERS) == 5 and not srv.slow_legs
    # the collector's histogram is there before a collection is
    assert "proc.gc_s" in snap["histograms"]
    seen = obs_stats.histogram("proc.gc_s").count
    gc.collect()
    assert obs_stats.histogram("proc.gc_s").count == seen + 1


def test_a_slow_leg_round_trips_through_the_flight_ring(tmp_path):
    flight.enable(str(tmp_path), role="serve:test", records=64)
    try:
        watch = obs_legs.SlowLegs(lambda: {"active_slots": 3})
        with watch.leg("serve/admit/tree") as leg:
            time.sleep(0.11)
            leg.args.update(evicted=2, tree_bytes=1 << 20)
    finally:
        flight.disable()
    record, = watch.records
    rings = postmortem.load_rings(str(tmp_path))
    event, = [e for e in postmortem.merge_events(rings)
              if e["event"] == "serve.slow_leg"]
    decoded = postmortem.decode_slow_leg(event)
    assert decoded["leg"] == record["leg"] == "serve/admit/tree"
    assert decoded["wall_s"] == pytest.approx(record["wall_s"], abs=2e-6)
    assert decoded["cpu_s"] == pytest.approx(record["cpu_s"], abs=2e-6)
    assert decoded["gc_s"] == pytest.approx(record["gc_s"], abs=1e-3)
    assert decoded["evicted"] == 2
    assert decoded["voluntary_switches"] == record["voluntary_switches"]
    assert decoded["involuntary_switches"] == \
        record["involuntary_switches"]
    assert decoded["major_faults"] == record["major_faults"]
    # the leg ended when the event was written
    assert decoded["ended_at"] == pytest.approx(
        record["at"] + record["wall_s"], abs=0.05)
    # and pst-trace says so
    text = postmortem.render_report(postmortem.report(str(tmp_path)))
    assert "SLOW LEG: serve/admit/tree" in text and "serve:test" in text
    assert "serve.slow_leg" in postmortem.EVENT_DECODE


# ------------------------------------------------- (b) the PS round's legs
@pytest.fixture
def cluster1(tmp_path):
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=str(tmp_path), learning_rate=0.05,
        autosave_period_s=600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    port = coordinator.start()
    yield port
    coordinator.stop()
    ps.stop()


def test_ps_round_legs_are_disjoint_and_cover_the_step(cluster1,
                                                       monkeypatch):
    # small buckets and chunks: several D2H fetches and ring frames a round
    monkeypatch.setenv("PSDT_BUCKET_BYTES", str(64 << 10))
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", str(64 << 10))
    # a ring smaller than the pull's LAST frame: the server is still
    # serving while the worker decodes the frames before it
    monkeypatch.setenv("PSDT_SHM_RING_BYTES", str(8 << 10))
    worker = build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{cluster1}", worker_id=0,
        iterations=4, batch_size=16, model="mnist_mlp",
        heartbeat_period_s=600.0, fused_step=True))
    worker.initialize()
    fetched = []
    compute = worker.trainer.compute_gradient_buckets
    worker.trainer.compute_gradient_buckets = lambda params, batch: compute(
        params, batch, on_fetch=lambda i, n: fetched.append((i, n)))
    try:
        for iteration in range(3):      # seed, renegotiate, steady state
            worker.run_iteration(iteration)
        assert worker._ps.shm_active
        del fetched[:]
        obs_trace.clear()
        obs_trace.enable(True)
        worker.run_iteration(3)
        obs_trace.enable(False)
        spans = obs_trace.spans()
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
        worker.shutdown()
    step, = [s for s in spans if s["name"] == "worker/step"]
    mine = [s for s in spans if s["tid"] == step["tid"]]
    theirs = [s for s in spans if s["tid"] != step["tid"]]
    count = collections.Counter(s["name"] for s in mine)
    served = collections.Counter(s["name"] for s in theirs)
    buckets = fetched[0][1]
    assert buckets > 2 and len(fetched) == buckets
    # once per unit of work: per round, per bucket, per tensor and chunk,
    # per ring frame (a read also sees the 4-byte end marker)
    assert count["worker/pack"] == count["worker/dispatch"] == \
        count["worker/device_wait"] == 1
    # the upload at dispatch (of nothing: the store went up as it landed)
    # and one put a section of the NEXT step's input, as the pull lands it,
    # with one more for their join on the device
    streamed = [s for s in mine if s["name"] == "worker/h2d"
                and ("section" in s["args"] or "joined" in s["args"])]
    sections = len(worker.trainer._cuts)
    assert sections > 2 and count["worker/h2d"] == 1 + sections + 1
    assert sorted(s["args"]["section"] for s in streamed[:-1]) == \
        list(range(sections))
    assert streamed[-1]["args"]["joined"] == sections
    assert count["worker/d2h"] == buckets - 1   # bucket 0 is device_wait
    tensors = len(worker.trainer._layout)
    sent = served["ps/fold"]                     # chunks with gradients
    assert sent > 1 and served["rpc/server/decode"] == 2 * sent
    assert count["rpc/client/encode"] == tensors + sent
    received = sum(1 for s in mine if s["name"] == "rpc/client/decode"
                   and "bytes" in s["args"])
    assert count["rpc/client/decode"] == 2 * received - 1   # push verdict
    assert count["rpc/shm/copy"] == sent + received + 1
    assert count["rpc/shm/wait"] <= count["rpc/shm/copy"]
    assert served["ps/close"] == served["ps/apply"] == 1
    assert served["rpc/server/encode"] == 1 + received
    # a frame is encoded INTO the ring: what is left of encoding on an f32
    # wire (the sizes) is one span a frame, before the frame's copy leg,
    # and nests where the frame-sized encode used to: under the client's
    # call, and on the server under the serve leg (the push verdict, which
    # goes out before it, under the handler's span)
    by_id = {s["span_id"]: s for s in spans}
    call, = [s for s in mine if s["name"] == "rpc/client/PushPullStream"]
    frames = [s for s in mine if s["name"] == "rpc/client/encode"
              and "tensor" not in s.get("args", ())]
    assert len(frames) == sent
    assert all(s["parent_id"] == call["span_id"] for s in frames)
    answers = [s for s in theirs if s["name"] == "rpc/server/encode"
               and "version" not in s.get("args", ())]
    assert [by_id[s["parent_id"]]["name"] for s in answers] == \
        ["rpc/server/PushPullStream"] + ["ps/serve"] * (received - 1)
    build, = [s for s in theirs if s["name"] == "rpc/server/encode"
              and "version" in s.get("args", ())]
    assert by_id[build["parent_id"]]["name"] == "ps/serve"
    # the response is consumed as it arrives: each frame that leaves the
    # ring is decoded (and its chunk converted) before the next is read,
    # so the decode spans lie between the ring reads of the response and
    # begin before the server's serve leg is over (the last frame does not
    # fit the ring, so the server cannot finish before the worker asks
    # for it)
    decodes = [s for s in mine if s["name"] == "rpc/client/decode"
               and "bytes" in s["args"]]
    reads = sorted((s for s in mine if s["name"] == "rpc/shm/copy"),
                   key=lambda s: s["ts"])[-(received + 1):]
    for decode, read, after in zip(decodes, reads, reads[1:]):
        assert read["args"]["bytes"] == decode["args"]["bytes"]
        assert read["ts"] + read["dur"] - 2e-4 <= decode["ts"]
        assert decode["ts"] + decode["dur"] <= after["ts"] + 2e-4
    converts = [s for s in mine if s["name"] == "rpc/client/decode"
                and "tensors" in s["args"]]
    assert converts[0]["ts"] + converts[0]["dur"] <= reads[2]["ts"] + 2e-4
    serve, = [s for s in theirs if s["name"] == "ps/serve"]
    assert reads[-2]["args"]["bytes"] > 8 << 10
    assert decodes[-2]["ts"] < serve["ts"] + serve["dur"]
    # every leg knows its round, the ones opened deep in the rings too
    assert all(s["args"]["iteration"] == 3 for s in mine)
    # the leaves are disjoint (to the clocks' 0.2 ms) and cover the step
    # but for its glue: the batch, the generators, the bookkeeping after
    # the round (a third of a round of milliseconds; under 5% on the chip)
    # (a section's put stands INSIDE the decode leg that landed it)
    leaves = sorted((s for s in mine if s["name"] in WORKER_LEAVES
                     and s not in streamed), key=lambda s: s["ts"])
    converts = [s for s in leaves if s["name"] == "rpc/client/decode"]
    for put in streamed:
        assert any(c["ts"] - 2e-4 <= put["ts"] and put["ts"] + put["dur"]
                   <= c["ts"] + c["dur"] + 2e-4 for c in converts), put
    for a, b in zip(leaves, leaves[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 2e-4, (a, b)
    assert leaves[0]["ts"] >= step["ts"] - 2e-4
    assert leaves[-1]["ts"] + leaves[-1]["dur"] <= \
        step["ts"] + step["dur"] + 2e-4
    covered = sum(s["dur"] for s in leaves) / step["dur"]
    assert 0.66 <= covered <= 1.001, covered


# -------------------------------------------------- (e) names, and no more
def lowered_step(model):
    optimizer = optax.adam(1e-3)
    step = jax.jit(make_train_step(model.loss, optimizer))
    params = model.init_params(0)
    state = TrainState.create(params, optimizer)
    return step.lower(state, jnp.zeros((2, 32), jnp.int32))


def lowered_decode(model):
    params = model.init_params(0)
    cache = generation.init_cache(model, 2, 32, "native")
    return jax.jit(lambda p, t, c, n: generation.decode_block(
        model, p, t, c, lengths=n)).lower(
        params, jnp.zeros((2, 1), jnp.int32), cache,
        jnp.zeros((2,), jnp.int32))


@pytest.mark.parametrize("lower,scopes", [
    (lowered_step, ("embed", "norm", "attn_qkv", "attn", "attn_out", "mlp",
                    "head", "loss", "optimizer")),
    (lowered_decode, ("embed", "attn_qkv", "cache_update", "cache_attn",
                      "attn_out", "mlp", "head")),
], ids=["train_step", "decode_block"])
@pytest.mark.parametrize("layout", ["unrolled", "scan_remat"])
def test_named_scope_changes_metadata_only(monkeypatch, lower, scopes,
                                           layout):
    kw = dict(scan_layers=True, remat=True, loss_chunk=16) \
        if layout == "scan_remat" else {}
    named = lower(tiny(max_seq=32, **kw))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = lower(tiny(max_seq=32, **kw))
    # the program (what the compile-cache key is made from: debug
    # information stripped) is the same text; only the locations differ
    assert named.as_text() == bare.as_text()
    with_names = named.as_text(debug_info=True)
    assert with_names != bare.as_text(debug_info=True)
    # (inside a scan or a checkpoint the paths restart: "checkpoint/mlp/...")
    paths = set(re.findall(r'loc\("([^"]*)"', with_names))
    for scope in scopes:
        assert any(re.search(rf"(^|[/(]){scope}[/)]", p)
                   for p in paths), scope
    matmul = re.compile(r"mlp\)?/dot_general")
    assert matmul.search(with_names)
    assert not matmul.search(bare.as_text(debug_info=True))
