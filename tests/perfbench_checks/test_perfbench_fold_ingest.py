"""``ps.fold_fresh_mb_per_round_in_window`` and
``ps.decode_copied_mb_per_round_in_window`` (PR 41): their entries, their files, and
what their reader makes of a program with and without the two counters.
Both count what should not happen in the cell in steady state and read 0
there; the parent, which has neither counter, leaves them out.  CPU only,
no JAX."""

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

FRESH = "ps.fold_fresh_mb_per_round_in_window"
COPIED = "ps.decode_copied_mb_per_round_in_window"
FOLD_COUNTER = "ps.fold.fresh_bytes"
DECODE_COUNTER = "rpc.server.decode.copied_bytes"
CELL = "ps_round_gpt2m"
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=8):
    return {"rounds": rounds, "window": (0.0, 60.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": {},
                               "histograms": {}}}


@pytest.mark.parametrize("name,counter", [(FRESH, FOLD_COUNTER),
                                          (COPIED, DECODE_COUNTER)],
                         ids=["fold_fresh", "decode_copied"])
def test_the_entry_and_the_file_say_what_the_metric_is(name, counter):
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["unit"] == "MB" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "transport and PS"
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{name}.json")) == {
            "reader": "counter_mb_per_round", "args": {"counters": [counter]}}


@pytest.mark.parametrize("before,after,expected", [
    # steady state: every seed found the last round's buffer, every
    # tensor was folded where its frame lay
    ({FOLD_COUNTER: 1_625_000_000, DECODE_COUNTER: 0},
     {FOLD_COUNTER: 1_625_000_000, DECODE_COUNTER: 0},
     {FRESH: 0.0, COPIED: 0.0}),
    # somebody kept a sum for two of eight rounds; a buffered sink's
    # decode copied every round's push out of its frames
    ({FOLD_COUNTER: 0, DECODE_COUNTER: 0},
     {FOLD_COUNTER: 2 * 1_600_000_000, DECODE_COUNTER: 8 * 1_600_000_000},
     {FRESH: 400.0, COPIED: 1600.0}),
    # the parent has neither counter: nothing, and no error
    ({"rpc.shm.bytes": 1}, {"rpc.shm.bytes": 9}, {}),
], ids=["steady", "held_and_owned", "parent"])
def test_reads_both_through_the_harness(before, after, expected):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] in (FRESH, COPIED)])
    got = harness.read_per_layer(only, cell, observed(before, after))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(v["unit"] == "MB" for v in got.values())


def test_the_program_counts_under_those_names():
    """One streamed round moves the fold's counter by the accumulator it
    had to allocate and the decode's by nothing; an owned decode of the
    same frames moves the decode's by their payload (imports no JAX)."""
    ps_core = importlib.import_module(
        "parameter_server_distributed_tpu.core.ps_core")
    data_plane = importlib.import_module(
        "parameter_server_distributed_tpu.rpc.data_plane")
    messages = importlib.import_module(
        "parameter_server_distributed_tpu.rpc.messages")
    stats = importlib.import_module(
        "parameter_server_distributed_tpu.obs.stats")
    core = ps_core.ParameterServerCore(total_workers=1, stripes=3)
    store = {"w": np.ones((5, 7), np.float32), "b": np.ones(3, np.float32)}
    core.initialize_parameters(store)
    fold, decode = stats.counter(FOLD_COUNTER), stats.counter(DECODE_COUNTER)
    chunk = messages.GradientUpdate.decode(messages.GradientUpdate(
        worker_id=0, iteration=1, gradients=[
            messages.Tensor.from_array(k, v) for k, v in store.items()]
    ).encode())
    fold_before, decode_before = fold.value, decode.value
    sink = core.begin_push(0, 1)
    sink.fold(data_plane.decode_gradients(chunk.gradients,
                                          borrow=sink.folds_at_once))
    assert sink.commit().aggregation_complete
    assert fold.value - fold_before == 4 * (35 + 3)
    assert decode.value == decode_before
    data_plane.decode_gradients(chunk.gradients)
    assert decode.value - decode_before == 4 * (35 + 3)
