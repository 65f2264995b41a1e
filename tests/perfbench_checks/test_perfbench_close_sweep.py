"""``ps.close_fresh_mb_per_round_in_window`` and ``ps.close_parallelism``
(PR 39): their entries, their files, and what their readers make of a
program with and without the counter and the gauge.  The first name ends in
``_in_window`` because it counts what should not happen there in steady
state and may read 0, which the runner's rehearsal allows only of such a
name.  CPU only, no JAX."""

import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

FRESH = "ps.close_fresh_mb_per_round_in_window"
WIDE = "ps.close_parallelism"
COUNTER = "ps.close.fresh_bytes"
GAUGE = "ps.apply.parallelism"
CELL = "ps_round_gpt2m"
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, gauges, rounds=5):
    return {"rounds": rounds, "window": (0.0, 60.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": gauges,
                               "histograms": {}}}


@pytest.mark.parametrize("name,unit,better,spec", [
    (FRESH, "MB", "lower", {"reader": "counter_mb_per_round",
                            "args": {"counters": [COUNTER]}}),
    (WIDE, "ratio", "higher", {"reader": "gauge",
                               "args": {"gauge": GAUGE}}),
], ids=["fresh", "parallelism"])
def test_the_entry_and_the_file_say_what_the_metric_is(name, unit, better,
                                                       spec):
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["unit"] == unit and entry["better"] == better
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "transport and PS"
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{name}.json")) == spec


@pytest.mark.parametrize("before,after,gauges,expected", [
    # the window's first two closes found the stores of versions 0 and 1
    # still held and allocated a store each (1,625 MB); three did not
    ({COUNTER: 1_625_000_000}, {COUNTER: 3 * 1_625_000_000},
     {GAUGE: 11.4}, {FRESH: 650.0, WIDE: 11.4}),
    # steady state: every close wrote over the store of two versions ago
    ({COUNTER: 9}, {COUNTER: 9}, {GAUGE: 12.0}, {FRESH: 0.0, WIDE: 12.0}),
    # a program without the counter whose close is cut by name (the
    # parent): the gauge it has always set, and no error for the counter
    ({"rpc.shm.bytes": 1}, {"rpc.shm.bytes": 9}, {GAUGE: 2.21},
     {WIDE: 2.21}),
    # neither (PSDT_STRIPES=1 never sets the gauge): nothing
    ({}, {}, {}, {}),
], ids=["two_closes_allocate", "steady", "parent", "neither"])
def test_reads_both_through_the_harness(before, after, gauges, expected):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] in (FRESH, WIDE)])
    got = harness.read_per_layer(only, cell,
                                 observed(before, after, gauges))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(got[k]["unit"] == ("MB" if k == FRESH else "ratio")
               for k in got)


def test_the_program_counts_and_gauges_under_those_names():
    """One range-cut close moves the counter by the bytes it had to
    allocate and sets the gauge the file names (imports no JAX)."""
    ps_core = importlib.import_module(
        "parameter_server_distributed_tpu.core.ps_core")
    optimizer = importlib.import_module(
        "parameter_server_distributed_tpu.core.optimizer")
    stats = importlib.import_module(
        "parameter_server_distributed_tpu.obs.stats")
    core = ps_core.ParameterServerCore(
        total_workers=1, optimizer=optimizer.Adam(0.01), stripes=3)
    store = {"w": np.ones((5, 7), np.float32), "b": np.ones(3, np.float32)}
    core.initialize_parameters(store)
    counter, gauge = stats.counter(COUNTER), stats.gauge(GAUGE)
    before = counter.value
    gauge.set(0.0)
    assert core.receive_gradients(0, 1, dict(store)).aggregation_complete
    assert counter.value - before == 4 * (35 + 3)
    assert gauge.value > 0
