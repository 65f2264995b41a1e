"""``ps.encode_fresh_mb_per_round_in_window`` (PR 36): its entry, its file,
and what its reader makes of a program with and without the counter.  The
name ends in ``_in_window`` because it counts what should not happen there
and reads 0 when all is well, which the runner's rehearsal allows only of
such a name.  CPU only, no JAX."""

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "ps.encode_fresh_mb_per_round_in_window"
COUNTER = "rpc.wire.fresh_bytes"
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=3):
    return {"rounds": rounds, "window": (0.0, 60.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "histograms": {}}}


def test_the_entry_and_the_file_say_what_the_metric_is():
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "MB" and entry["better"] == "lower"
    assert entry["source"] == "program_counter" and entry["layer"] == "wire"
    assert entry["moves"] == "ps_tokens_per_s"
    assert "ps_round_gpt2m" in entry["workloads"]
    spec = harness.load_json(os.path.join(ROOT, "perfbench", "metrics",
                                          f"{NAME}.json"))
    assert spec == {"reader": "counter_mb_per_round",
                    "args": {"counters": [COUNTER]}}


@pytest.mark.parametrize("before,after,expected", [
    # three frame-sized allocations of 1,625 MB a round, as the parent's
    # code would count them if it had the counter
    ({COUNTER: 7_000_000}, {COUNTER: 7_000_000 + 3 * 4_875_000_000}, 4875.0),
    # every message went to a ring or to a buffer that was there
    ({COUNTER: 7_000_000}, {COUNTER: 7_000_000}, 0.0),
    # a program without the counter (the parent): nothing, and no error
    ({"rpc.shm.bytes": 1}, {"rpc.shm.bytes": 9}, None),
], ids=["three_allocations_a_round", "none", "no_such_counter"])
def test_reads_new_memory_a_round_through_the_harness(before, after,
                                                      expected):
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "ps_round_gpt2m")
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == NAME])
    got = harness.read_per_layer(only, cell, observed(before, after))
    if expected is None:
        assert got == {}
    else:
        assert got == {NAME: {"value": pytest.approx(expected),
                              "unit": "MB"}}


def test_the_program_counts_under_that_name():
    """The counter the file names is the one the encoder's allocator
    adds to (imports no JAX)."""
    wire = importlib.import_module(
        "parameter_server_distributed_tpu.rpc.wire")
    stats = importlib.import_module(
        "parameter_server_distributed_tpu.obs.stats")
    counter = stats.counter(COUNTER)
    before = counter.value
    assert len(wire.encode_fresh(12345, lambda w: w.write(bytes(12345)))) \
        == 12345
    assert counter.value - before == 12345
