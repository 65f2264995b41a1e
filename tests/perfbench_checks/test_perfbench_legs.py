"""The readers and the tool that PR 24 adds, on hand-made data: the legs of
a PS round from ``obs/trace`` spans, the serving legs from ``obs/stats``
histograms and counters, and ``tools/idle_by_span`` on a hand-made trace.
CPU only, no JAX."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.readers import (counter_delta, hist_percentile_ms,  # noqa: E402
                               span_cover_pct, span_sum_ms_per_round)
from perfbench.tools import idle_by_span  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
WORKER, SERVER = 11, 22
NEW_PS = ["ps.pack_h2d_ms_per_round", "ps.device_wait_ms_per_round",
          "ps.d2h_ms_per_round", "ps.client_encode_ms_per_round",
          "ps.client_decode_ms_per_round", "ps.shm_copy_ms_per_round",
          "ps.shm_wait_ms_per_round", "ps.server_codec_ms_per_round",
          "ps.fold_ms_per_round", "ps.close_ms_per_round",
          "ps.legs_cover_pct"]
NEW_SERVE = ["serve.admit_p50_ms", "serve.admit_device_p50_ms",
             "serve.round_device_p50_ms", "serve.round_host_p50_ms",
             "serve.between_rounds_p99_ms", "serve.between_rounds_share_pct",
             "serve.programs_in_window"]


def span(name, ts, dur, tid=WORKER, **args):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def two_rounds() -> dict:
    """Two rounds of 10 s inside a window of 0..100, and one before it."""
    spans = [span("worker/step", -20.0, 10.0, iteration=0),
             span("worker/pack", -20.0, 1.0, iteration=0)]
    for it, t in ((1, 10.0), (2, 30.0)):
        spans += [
            span("worker/step", t, 10.0, iteration=it),
            span("worker/pack", t, 1.0, iteration=it),
            span("worker/h2d", t + 1.0, 0.5, iteration=it),
            span("worker/d2h", t + 2.0, 0.25, iteration=it, bucket=1),
            span("worker/d2h", t + 3.0, 0.25, iteration=it, bucket=2),
            # the worker's end of the ring, and the server's end of the
            # same ring: with and without the round's number
            span("rpc/shm/wait", t + 4.0, 2.0, iteration=it),
            span("rpc/shm/copy", t + 6.0, 1.0, iteration=it),
            span("rpc/shm/copy", t + 4.0, 3.0, tid=SERVER, iteration=it),
            span("rpc/shm/copy", t + 7.0, 1.0, tid=SERVER),
            span("ps/fold", t + 4.5, 0.5, tid=SERVER, iteration=it),
            # overlaps the ring's copy by half a second: counted once
            span("rpc/client/decode", t + 6.5, 1.5, iteration=it),
        ]
    return {"spans": spans, "window": (0.0, 100.0), "window_s": 100.0,
            "rounds": 2}


@pytest.mark.parametrize("args,expected", [
    ({"spans": ["worker/pack"]}, 1000.0),
    ({"spans": ["worker/pack", "worker/h2d"]}, 1500.0),
    ({"spans": ["worker/d2h"]}, 500.0),
    ({"spans": ["rpc/shm/copy"]}, 5000.0),
    ({"spans": ["rpc/shm/copy"], "thread_of": "worker/step"}, 1000.0),
    ({"spans": ["ps/fold"], "thread_of": "worker/step"}, None),
    ({"spans": ["ps/fold"]}, 500.0),
    ({"spans": ["worker/never"]}, None),
    ({"spans": ["worker/pack"], "thread_of": "worker/never"}, None),
])
def test_span_sum_ms_per_round(args, expected):
    got = span_sum_ms_per_round.read(two_rounds(), **args)
    assert got == (None if expected is None else pytest.approx(expected))


def test_span_sum_needs_rounds():
    observed = dict(two_rounds(), rounds=0)
    assert span_sum_ms_per_round.read(observed, ["worker/pack"]) is None


@pytest.mark.parametrize("children,expected", [
    (["worker/pack"], 10.0),
    (["worker/pack", "worker/h2d", "worker/d2h"], 20.0),
    # the server's copies lie on another thread; the client's decode
    # overlaps the worker's copy by 0.5 s and runs 0 s past the step
    (["rpc/shm/wait", "rpc/shm/copy", "rpc/client/decode"], 40.0),
    (["ps/fold"], 0.0),
    (["worker/never"], None),
])
def test_span_cover_pct(children, expected):
    got = span_cover_pct.read(two_rounds(), "worker/step", children)
    assert got == (None if expected is None else pytest.approx(expected))
    assert span_cover_pct.read(two_rounds(), "worker/never",
                               children) is None


def test_span_cover_clips_children_to_their_parent():
    observed = {"window": (0.0, 10.0), "spans": [
        span("p", 1.0, 4.0), span("c", 0.0, 2.0), span("c", 4.0, 3.0)]}
    assert span_cover_pct.read(observed, "p", ["c"]) == pytest.approx(50.0)


def histogram(values) -> dict:
    from parameter_server_distributed_tpu.obs.stats import Histogram
    hist = Histogram()
    for v in values:
        hist.observe(v)
    return json.loads(json.dumps(hist.snapshot()))   # keys become strings


def registry(histograms=None, counters=None) -> dict:
    return {"histograms": histograms or {}, "counters": counters or {},
            "gauges": {}}


def test_hist_percentile_ms_reads_the_window_only():
    early = [0.5] * 10
    inside = [0.010] * 98 + [2.3, 2.3]      # two stalls among 100 rounds
    observed = {
        "window_s": 60.0,
        "registry_before": registry({"serve.between_rounds_s":
                                     histogram(early)}),
        "registry_after": registry({"serve.between_rounds_s":
                                    histogram(early + inside)})}
    read = hist_percentile_ms.read
    assert read(observed, "serve.between_rounds_s", 50) == pytest.approx(
        10.0, rel=0.09)
    assert read(observed, "serve.between_rounds_s", 99) == pytest.approx(
        2300.0, rel=0.09)
    assert read(observed, "serve.never_s", 99) is None
    observed["registry_before"] = None      # a histogram born in the window
    assert read(observed, "serve.between_rounds_s", 50) == pytest.approx(
        10.0, rel=0.09)


def test_counter_delta_is_zero_where_nothing_was_counted():
    observed = {"registry_before": registry(counters={"serve.programs": 22}),
                "registry_after": registry(counters={"serve.programs": 22})}
    assert counter_delta.read(observed, "serve.programs") == 0
    observed["registry_after"]["counters"]["serve.programs"] = 25
    assert counter_delta.read(observed, "serve.programs") == 3
    assert counter_delta.read(observed, "serve.never") is None


@pytest.mark.parametrize("name", NEW_PS + NEW_SERVE)
def test_a_program_without_the_legs_reports_nothing(name):
    """The parent commit has none of these spans, histograms or counters:
    every new metric's reader then returns None and does not raise."""
    metric = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    cell = "ps_round_gpt2m" if name in NEW_PS else "serve_chat_gpt2m"
    assert metric["workloads"] == [cell]
    spec = harness.load_json(os.path.join(ROOT, "perfbench", "metrics",
                                          f"{name}.json"))
    reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
    old = {"spans": [span("worker/step", 1.0, 10.0, iteration=1),
                     span("worker/compute", 1.0, 2.0, iteration=1),
                     span("ps/apply", 5.0, 2.0, tid=SERVER, iteration=1)],
           "window": (0.0, 20.0), "window_s": 20.0, "rounds": 1,
           "registry_before": registry({"serve.round_s": histogram([0.04])}),
           "registry_after": registry({"serve.round_s":
                                       histogram([0.04, 0.05])})}
    assert reader.read(old, **spec.get("args", {})) is None


def test_the_legs_of_a_round_through_the_harness():
    """The eleven PS metrics through ``harness.read_per_layer`` on the
    hand-made round: each reads what its name says."""
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "ps_round_gpt2m")
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] in NEW_PS])
    got = {k: v["value"] for k, v in harness.read_per_layer(
        only, cell, two_rounds()).items()}
    assert got == pytest.approx({
        "ps.pack_h2d_ms_per_round": 1500.0, "ps.d2h_ms_per_round": 500.0,
        "ps.client_decode_ms_per_round": 1500.0,
        "ps.shm_copy_ms_per_round": 1000.0,
        "ps.shm_wait_ms_per_round": 2000.0, "ps.fold_ms_per_round": 500.0,
        "ps.legs_cover_pct": 60.0})


# ------------------------------------------------------- tools/idle_by_span
def hand_made_trace() -> dict:
    """One device busy 0-1, 4-5 and 9-10; the worker's thread inside
    bench/ps_round with its legs, the server's thread with one span."""
    return {
        "device": {"/device:TPU:0": [("fusion.1", 0.0, 1.0),
                                     ("fusion.2", 4.0, 5.0),
                                     ("copy.3", 9.0, 10.0)]},
        "host": [
            ("bench/ps_round", 0.0, 10.0, "python#0"),
            ("psdt/worker/step", 0.0, 10.0, "python#0"),
            ("psdt/worker/d2h", 0.5, 2.0, "python#0"),
            ("psdt/rpc/shm/copy", 2.0, 8.0, "python#0"),
            ("psdt/rpc/shm/wait", 3.0, 4.5, "python#0"),
            ("psdt/rpc/shm/wait", 6.0, 7.0, "python#0"),
            ("psdt/ps/close", 2.5, 3.5, "python#1"),
        ],
        "ops": {"fusion.1": {"tf_op": "jit(step)/mlp/dot_general"}},
    }


def test_innermost_cuts_nested_events_into_disjoint_pieces():
    events = [("a", 0.0, 10.0), ("b", 2.0, 5.0), ("c", 3.0, 4.0),
              ("d", 12.0, 13.0)]
    assert idle_by_span.innermost(events) == [
        (0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 4.0, "c"), (4.0, 5.0, "b"),
        (5.0, 10.0, "a"), (12.0, 13.0, "d")]


def test_idle_by_span_on_a_hand_made_trace():
    trace = hand_made_trace()
    assert idle_by_span.idle_intervals(trace) == [(1.0, 4.0), (5.0, 9.0)]
    got = idle_by_span.report(trace)
    assert got["idle_s"] == pytest.approx(7.0)
    # reduce.reduce_trace unchanged: a gap takes the name of the innermost
    # span open when it starts
    assert got["idle_gaps"] == [
        ["psdt/rpc/shm/copy_after_fusion.2", pytest.approx(4.0)],
        ["psdt/worker/d2h_after_fusion.1", pytest.approx(3.0)]]
    assert got["gaps_named_by_psdt_pct"] == pytest.approx(100.0)
    # and the same idle time cut at the span boundaries, thread by thread
    assert got["idle_gaps_thread"] == "python#0"
    worker = got["idle_by_thread"]["python#0"]
    assert worker["idle_s"] == pytest.approx({
        "psdt/rpc/shm/copy": 3.0, "psdt/rpc/shm/wait": 2.0,
        "psdt/worker/d2h": 1.0, "psdt/worker/step": 1.0, "none": 0.0})
    assert worker["named_by_psdt_pct"] == pytest.approx(100.0)
    server = got["idle_by_thread"]["python#1"]["idle_s"]
    assert server == pytest.approx({"none": 6.0, "psdt/ps/close": 1.0})
    assert list(got["idle_by_thread"]) == ["python#0", "python#1"]
    assert got["device_ops"][0] == [
        "fusion.1", pytest.approx(1.0),
        {"tf_op": "jit(step)/mlp/dot_general"}]
    assert got["psdt_events"] == {
        "psdt/worker/step": 1, "psdt/worker/d2h": 1, "psdt/rpc/shm/copy": 1,
        "psdt/rpc/shm/wait": 2, "psdt/ps/close": 1}
    assert idle_by_span.report({"device": {}, "host": [], "ops": {}}) is None
