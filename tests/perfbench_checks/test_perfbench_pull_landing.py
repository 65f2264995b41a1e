"""``ps.pull_fresh_mb_per_round_in_window`` and
``ps.pack_copied_mb_per_round_in_window`` (PR 44): their entries, their
files, and what their reader makes of a program with and without the two
counters.  Both count what should not happen in the cell in steady state
(a served tensor that went to new memory, a slot the pack had to copy) and
read 0 there; the parent, which has neither counter, leaves them out.
CPU only; the last test alone imports JAX."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

FRESH = "ps.pull_fresh_mb_per_round_in_window"
COPIED = "ps.pack_copied_mb_per_round_in_window"
PULL_COUNTER = "worker.pull.fresh_bytes"
PACK_COUNTER = "worker.pack.copied_bytes"
CELL = "ps_round_gpt2m"
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=13):
    return {"rounds": rounds, "window": (0.0, 60.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": {},
                               "histograms": {}}}


@pytest.mark.parametrize("name,counter,layer", [
    (FRESH, PULL_COUNTER, "wire"), (COPIED, PACK_COUNTER, "worker step")],
    ids=["pull_fresh", "pack_copied"])
def test_the_entry_and_the_file_say_what_the_metric_is(name, counter, layer):
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["unit"] == "MB" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == layer
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{name}.json")) == {
            "reader": "counter_mb_per_round", "args": {"counters": [counter]}}


@pytest.mark.parametrize("before,after,expected", [
    # steady state: every served tensor landed in the buffer the step
    # uploads, and the pack found every slot in place
    ({PULL_COUNTER: 3_250_000_000, PACK_COUNTER: 0},
     {PULL_COUNTER: 3_250_000_000, PACK_COUNTER: 0},
     {FRESH: 0.0, COPIED: 0.0}),
    # somebody kept a pulled store for one of thirteen rounds; a trainer
    # fed stores made elsewhere copied every round's
    ({PULL_COUNTER: 0, PACK_COUNTER: 0},
     {PULL_COUNTER: 1_625_000_000, PACK_COUNTER: 13 * 1_625_000_000},
     {FRESH: 125.0, COPIED: 1625.0}),
    # the parent has neither counter: nothing, and no error
    ({"rpc.shm.bytes": 1}, {"rpc.shm.bytes": 9}, {}),
], ids=["steady", "held_and_foreign", "parent"])
def test_reads_both_through_the_harness(before, after, expected):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] in (FRESH, COPIED)])
    got = harness.read_per_layer(only, cell, observed(before, after))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(v["unit"] == "MB" for v in got.values())


def test_the_program_counts_under_those_names():
    """A pull landed in the trainer's loan moves the pull's counter by the
    buffer the trainer had to allocate and the pack's by nothing; a store
    made elsewhere moves the pack's by its payload."""
    from parameter_server_distributed_tpu.config import WorkerConfig
    from parameter_server_distributed_tpu.obs import stats
    from parameter_server_distributed_tpu.rpc import messages
    from parameter_server_distributed_tpu.worker.trainer import Trainer
    from parameter_server_distributed_tpu.worker.worker import Worker

    class Model:
        @staticmethod
        def init_params(seed):
            return {"w": np.ones((5, 7), np.float32),
                    "b": np.ones(3, np.float32)}

    store = Model.init_params(0)
    trainer = Trainer(Model())
    worker = Worker(WorkerConfig(), trainer=trainer, batches=iter(()),
                    start_heartbeat=False)
    pull, pack = stats.counter(PULL_COUNTER), stats.counter(PACK_COUNTER)
    served = messages.ParameterUpdate.decode(messages.ParameterUpdate(
        iteration=1, ready=True, parameters=[
            messages.Tensor.from_array(k, v) for k, v in store.items()]
    ).encode()).parameters
    pull_before, pack_before = pull.value, pack.value
    landed = {}
    worker._chunk_converter(landed)(served)
    trainer._pack(landed)
    assert pull.value - pull_before == 4 * (35 + 3)
    assert pack.value == pack_before
    trainer._pack(store)
    assert pack.value - pack_before == 4 * (35 + 3)
