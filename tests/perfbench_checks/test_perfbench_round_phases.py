"""The PS round by its phases (PR 52): the two readers this PR adds
(``span_sum_within_ms_per_round``, ``span_gap_ms_per_round``) on hand-made
spans, and each of the eleven metric files through ``harness.read_per_layer``.
This cell and these metrics only.  CPU only, no JAX."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from perfbench.readers import (span_gap_ms_per_round,  # noqa: E402
                               span_sum_within_ms_per_round)

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "ps_round_gpt2m"
WORKER, SERVER = 11, 22
WAIT = ["rpc/shm/wait"]
# what the hand-made rounds below must read, ms a round (or %)
EXPECTED = {
    "ps.push_ms_per_round": 2000.0,
    "ps.turn_ms_per_round": 1000.0,
    "ps.pull_ms_per_round": 2900.0,
    "ps.phases_cover_pct": 99.0,
    "ps.upload_wait_ms_per_round": 500.0,
    "ps.server_shm_copy_ms_per_round": 1500.0,
    "ps.between_steps_ms_per_round": 10000.0,
    "ps.push_worker_wait_ms_per_round_in_window": 750.0,
    "ps.pull_worker_wait_ms_per_round_in_window": 250.0,
    "ps.push_server_wait_ms_per_round_in_window": 700.0,
    "ps.pull_server_wait_ms_per_round_in_window": 500.0,
}
LAYER = {"ps.upload_wait_ms_per_round": "worker step",
         "ps.between_steps_ms_per_round": "worker step",
         "ps.push_ms_per_round": "transport and PS",
         "ps.turn_ms_per_round": "transport and PS",
         "ps.pull_ms_per_round": "transport and PS",
         "ps.phases_cover_pct": "transport and PS"}
# the spans this PR adds: a program without them has nothing to read here
NEEDS_THE_PHASES = ["ps.push_ms_per_round", "ps.turn_ms_per_round",
                    "ps.pull_ms_per_round", "ps.upload_wait_ms_per_round",
                    "ps.push_worker_wait_ms_per_round_in_window",
                    "ps.pull_worker_wait_ms_per_round_in_window"]


def span(name, ts, dur, tid=WORKER, **args):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def a_round(it, t, waits=True) -> list:
    """One round of 10 s from ``t``: 4 s of compute, then send 2, turn 1,
    receive 2.9 on the worker's thread; the server's handler from t + 4.2,
    its apply at t + 6 and its serve from t + 7."""
    spans = [
        span("worker/step", t, 10.0, iteration=it),
        span("worker/compute", t, 4.0, iteration=it),
        span("worker/device_wait", t + 0.5, 3.5, iteration=it),
        span("worker/device_wait/upload", t + 0.5, 0.5, iteration=it),
        span("rpc/client/PushPullStream", t + 4.0, 5.9, iteration=it),
        span("rpc/round/send", t + 4.0, 2.0, iteration=it),
        span("rpc/round/turn", t + 6.0, 1.0, iteration=it),
        span("rpc/round/receive", t + 7.0, 2.9, iteration=it),
        span("rpc/shm/copy", t + 5.0, 0.5, iteration=it),
        # parked for the round's first frame, and that frame's copy: before
        # the handler's span opens, and of no round
        span("rpc/shm/wait", t - 5.0, 9.2, tid=SERVER),
        span("rpc/shm/copy", t + 4.1, 0.1, tid=SERVER),
        span("rpc/server/PushPullStream", t + 4.2, 5.6, tid=SERVER,
             iteration=it),
        span("rpc/shm/copy", t + 5.0, 1.0, tid=SERVER, iteration=it),
        span("ps/apply", t + 6.0, 0.9, tid=SERVER, iteration=it),
        span("ps/serve", t + 7.0, 2.7, tid=SERVER, iteration=it),
        span("rpc/shm/copy", t + 8.0, 0.5, tid=SERVER, iteration=it),
    ]
    if waits:
        spans += [
            span("rpc/shm/wait", t + 4.5, 0.5, iteration=it),
            # straddles the end of send: a quarter of a second each side
            span("rpc/shm/wait", t + 5.75, 0.5, iteration=it),
            # the wait for the close, inside turn: in neither sum
            span("rpc/shm/wait", t + 6.3, 0.6, iteration=it),
            span("rpc/shm/wait", t + 8.0, 0.25, iteration=it),
            span("rpc/shm/wait", t + 4.4, 0.6, tid=SERVER, iteration=it),
            # straddles the start of serve: a tenth of a second each side
            span("rpc/shm/wait", t + 6.9, 0.2, tid=SERVER, iteration=it),
            span("rpc/shm/wait", t + 7.5, 0.4, tid=SERVER, iteration=it),
        ]
    return spans


def two_rounds(waits=True) -> dict:
    """Two rounds inside a window of 0..100, and one before it."""
    spans = [s for it, t in ((0, -20.0), (1, 10.0), (2, 30.0))
             for s in a_round(it, t, waits)]
    return {"spans": spans, "window": (0.0, 100.0), "window_s": 100.0,
            "rounds": 2}


def parent_round() -> dict:
    """What the parent commit records: no phase, no upload span, and no
    iteration on the server's ring legs outside ``ps/serve``."""
    spans = [s for s in a_round(1, 10.0)
             if not s["name"].startswith(("rpc/round/", "worker/device_wait/"))]
    return {"spans": spans, "window": (0.0, 100.0), "window_s": 100.0,
            "rounds": 1}


# ------------------------------------------- span_sum_within_ms_per_round
@pytest.mark.parametrize("within,outside,expected", [
    # the worker's waits by phase: the straddling wait is clipped, the
    # server's waits at the same times are another thread's
    ("rpc/round/send", None, 750.0),
    ("rpc/round/turn", None, 250.0 + 600.0),
    ("rpc/round/receive", None, 250.0),
    # the server's waits by its own thread's phases: the worker's never
    # count, and the wait for the round's first frame lies before the handler
    ("rpc/server/PushPullStream", None, 1200.0),
    ("rpc/server/PushPullStream", "ps/serve", 700.0),
    ("ps/serve", None, 500.0),
    ("ps/serve", "ps/serve", 0.0),
    ("rpc/round/never", None, None),
])
def test_waits_are_clipped_to_the_phases_of_their_own_thread(
        within, outside, expected):
    got = span_sum_within_ms_per_round.read(two_rounds(), WAIT, within,
                                            outside)
    assert got == (None if expected is None else pytest.approx(expected))


def test_zero_where_the_phases_are_there_and_nothing_waited():
    """A carved leg that never waited leaves no span: 0.0 then, and None
    only where the window holds no phase at all, or no round."""
    read = span_sum_within_ms_per_round.read
    calm = two_rounds(waits=False)
    assert read(calm, WAIT, "rpc/round/send") == 0.0
    assert read(calm, WAIT, "ps/serve") == 0.0
    # the parked wait of no round lies outside every handler span
    assert read(calm, WAIT, "rpc/server/PushPullStream", "ps/serve") == 0.0
    assert read(dict(calm, rounds=0), WAIT, "rpc/round/send") is None
    assert read(dict(calm, spans=[]), WAIT, "rpc/round/send") is None
    assert read({"window": (0.0, 1.0), "rounds": 3}, WAIT, "ps/serve") is None


def test_a_round_before_the_window_is_left_out():
    """Its phases open before the window does: neither they nor the waits
    inside them count, whatever the waits' own times."""
    read = span_sum_within_ms_per_round.read
    early = {"spans": a_round(0, -20.0), "window": (0.0, 100.0),
             "rounds": 1}
    assert read(early, WAIT, "rpc/round/send") is None
    both = two_rounds()
    both["window"] = (25.0, 100.0)          # the round at 10 is out too
    both["rounds"] = 1
    assert read(both, WAIT, "rpc/round/send") == pytest.approx(750.0)
    assert read(both, WAIT, "ps/serve") == pytest.approx(500.0)


def test_several_named_spans_and_overlapping_holes():
    observed = {"window": (0.0, 10.0), "rounds": 1, "spans": [
        span("p", 1.0, 8.0), span("a", 0.0, 2.0), span("b", 8.0, 3.0),
        span("hole", 1.5, 1.0), span("hole", 2.0, 1.0),
        span("a", 3.5, 1.0, tid=SERVER)]}
    read = span_sum_within_ms_per_round.read
    assert read(observed, ["a", "b"], "p") == pytest.approx(2000.0)
    assert read(observed, ["a", "b"], "p", "hole") == pytest.approx(1500.0)


# -------------------------------------------------- span_gap_ms_per_round
def test_the_gap_between_two_steps_of_one_thread():
    read = span_gap_ms_per_round.read
    # 10..20 and 30..40 inside the window; the step before it is left out
    assert read(two_rounds(), "worker/step") == pytest.approx(10000.0)
    three = two_rounds()
    three["spans"] += a_round(3, 41.0)
    assert read(three, "worker/step") == pytest.approx(5500.0)
    # another thread's step in between does not cut the gap
    three["spans"].append(span("worker/step", 22.0, 1.0, tid=SERVER))
    assert read(three, "worker/step") == pytest.approx(5500.0)
    one = {"spans": a_round(1, 10.0), "window": (0.0, 100.0), "rounds": 1}
    assert read(one, "worker/step") is None
    assert read(two_rounds(), "worker/never") is None


# ------------------------------------------- the entries and their files
@pytest.mark.parametrize("name", list(EXPECTED))
def test_the_entry_says_what_the_metric_is(name):
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    cover = name == "ps.phases_cover_pct"
    assert entry["unit"] == ("%" if cover else "ms")
    assert entry["better"] == ("higher" if cover else "lower")
    assert entry["source"] == "program_span"
    assert entry["layer"] == LAYER.get(name, "wire")
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]


@pytest.mark.parametrize("name", list(EXPECTED))
def test_each_metric_file_reads_its_spans_through_the_harness(name):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == name])
    got = harness.read_per_layer(only, cell, two_rounds())
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        {name: EXPECTED[name]})


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_program_without_the_phases_raises_nothing(name):
    """The parent commit: a metric of the new spans reads nothing there;
    one that cuts spans the parent has too reads what those hold (the
    server's copies and waits inside ``ps/serve``, which names its round
    itself; the steps' gap; the cover of ``worker/compute`` alone)."""
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == name])
    got = harness.read_per_layer(only, cell, parent_round())
    if name in NEEDS_THE_PHASES:
        assert got == {}
    elif name == "ps.between_steps_ms_per_round":
        assert got == {}                    # one step: no gap
    else:
        assert set(got) == {name}
