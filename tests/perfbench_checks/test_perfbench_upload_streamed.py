"""``ps.upload_streamed_pct_in_window`` (PR 53): its entry, its file, what
its reader makes of a program with and without the two counters, and the
program counting under those names.  The share of the bytes a step's input
uploaded (``worker.upload.bytes``) that was on its way to the device before
the step was asked for (``worker.upload.streamed_bytes``: sections a pull
landed and the loan put as they landed): 98 or more on the chip, where
every round's store is a fused pull landed in place; the parent, which has
neither counter, leaves it out.  This cell and this metric only.  CPU only;
the last test alone imports JAX."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "ps.upload_streamed_pct_in_window"
STREAMED, UPLOADED = "worker.upload.streamed_bytes", "worker.upload.bytes"
CELL = "ps_round_gpt2m"
STORE = 1_625_000_000
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=19):
    return {"rounds": rounds, "window": (0.0, 51.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": {},
                               "histograms": {}}}


def test_the_entry_and_the_file_say_what_the_metric_is():
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "worker step"
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{NAME}.json")) == {
            "reader": "counter_ratio", "args": {
                "numerator": STREAMED, "denominator": UPLOADED,
                "scale": 100.0}}


@pytest.mark.parametrize("before,after,expected", [
    # the chip's window: every round's store landed in place, whole
    ({STREAMED: 2 * STORE, UPLOADED: 3 * STORE},
     {STREAMED: 21 * STORE, UPLOADED: 22 * STORE}, {NAME: 100.0}),
    # one round of nineteen took a store made elsewhere (a re-pull that
    # fell back, a check): that one went up at dispatch
    ({STREAMED: 0, UPLOADED: 0},
     {STREAMED: 18 * STORE, UPLOADED: 19 * STORE}, {NAME: 100 * 18 / 19}),
    # nothing landed through a loan (a packed wire over TCP with deltas)
    ({STREAMED: 0, UPLOADED: STORE},
     {STREAMED: 0, UPLOADED: 20 * STORE}, {NAME: 0.0}),
    # the parent has neither counter: nothing, and no error
    ({}, {}, {}),
    # no step was dispatched in the window: nothing
    ({STREAMED: 5, UPLOADED: 7}, {STREAMED: 5, UPLOADED: 7}, {}),
], ids=["chip", "one_round_late", "never", "parent", "no_step"])
def test_reads_the_share_through_the_harness(before, after, expected):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == NAME])
    got = harness.read_per_layer(only, cell, observed(before, after))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(v["unit"] == "%" for v in got.values())


def test_the_program_counts_under_those_names():
    """A step fed a store landed through the trainer's loan moves both
    counters by its input; a step fed a store made elsewhere moves the
    denominator alone.  Both are made with the module, so a program that
    streamed nothing reads 0, not nothing."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.obs import stats
    from parameter_server_distributed_tpu.worker.trainer import Trainer

    class Model:
        @staticmethod
        def init_params(seed):
            return {"w": np.full((6, 4), seed, np.float32)}

        @staticmethod
        def loss(params, batch):
            return jnp.mean((batch @ params["w"]) ** 2)

    counters = stats.REGISTRY.snapshot()["counters"]
    assert STREAMED in counters and UPLOADED in counters
    trainer = Trainer(Model())
    batch = np.ones((8, 6), np.float32)
    store = {"w": np.arange(24, dtype=np.float32).reshape(6, 4)}
    streamed, uploaded = stats.counter(STREAMED), stats.counter(UPLOADED)
    before = streamed.value, uploaded.value
    landed = {"w": trainer.lend_store().land("w", store["w"])}
    grads, loss = trainer.compute_gradients(landed, batch)
    assert (streamed.value - before[0], uploaded.value - before[1]) == (96, 96)
    again, loss_again = trainer.compute_gradients(store, batch)
    assert (streamed.value - before[0], uploaded.value - before[1]) == (96, 192)
    assert loss == loss_again
    assert grads["w"].tobytes() == again["w"].tobytes()
