"""``serve.full_positions_read_pct`` (PR 55): its entry, its file, what its
reader makes of a program with and without the two counters, and the
program counting under those names.  The share of a full softmax layer's
cached positions (``serve.full.positions_cached``: slots x ``max_len`` a
layer and round) that the round's arm FETCHED
(``serve.full.positions_read``): 100 where the einsums read the parts
whole, every lane's length rounded up to whole blocks where the kernel of
ops/pallas/full_decode.py runs; a model with no full layer, and the parent,
which has no such counter, leave it out.  This metric only.  CPU only; the
last tests alone import JAX."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "serve.full_positions_read_pct"
READ, CACHED = "serve.full.positions_read", "serve.full.positions_cached"
CELLS = ("serve_reasoning_olmo_hybrid", "serve_chat_k_exaone_ep8",
         "serve_docs_chat_smallthinker", "serve_manychat_lfm2_24b_a2b",
         "serve_chat_gpt2m")
PART = 4 * 12 * 4096          # Olmo's four full layers x slots x max_len
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=2900):
    return {"rounds": rounds, "window": (0.0, 51.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": {},
                               "histograms": {}}}


def test_the_entry_and_the_file_say_what_the_metric_is():
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "%" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "decode step"
    assert entry["moves"] == "itl_p95_ms"
    assert set(CELLS) <= set(entry["workloads"])
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{NAME}.json")) == {
            "reader": "counter_ratio", "args": {
                "numerator": READ, "denominator": CACHED, "scale": 100.0}}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("before,after,expected", [
    # the einsums: every round reads the parts whole
    ({READ: 7 * PART, CACHED: 7 * PART},
     {READ: 2907 * PART, CACHED: 2907 * PART}, {NAME: 100.0}),
    # the kernel: lanes 39% full fetch 47% of the parts in blocks of 512
    ({READ: 0, CACHED: 0},
     {READ: 2900 * 4 * 45 * 512, CACHED: 2900 * PART},
     {NAME: 100 * 45 * 512 / (12 * 4096)}),
    # the parent counts the cached positions and not the read ones, and a
    # model with no full layer counts neither: nothing, and no error
    ({CACHED: 7 * PART}, {CACHED: 2907 * PART}, {}),
    ({}, {}, {}),
    # no round in the window: nothing
    ({READ: 5, CACHED: 7}, {READ: 5, CACHED: 7}, {}),
], ids=["dense", "kernel", "parent", "no_full_layer", "no_round"])
def test_reads_the_share_through_the_harness(cell, before, after, expected):
    workload = next(w for w in BENCHMARK["workloads"] if w["name"] == cell)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == NAME])
    got = harness.read_per_layer(only, workload, observed(before, after))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(v["unit"] == "%" for v in got.values())


def _moved(serve):
    from parameter_server_distributed_tpu.obs import stats

    def counters():
        held = stats.REGISTRY.snapshot()["counters"]
        return {name: held.get(name, 0) for name in (READ, CACHED)}

    before = counters()
    rounds = serve()
    return {name: value - before[name]
            for name, value in counters().items()}, rounds


@pytest.mark.parametrize("arm", ["dense", "kernel"])
def test_the_program_counts_under_those_names(monkeypatch, arm):
    """A server of two full layers over three lanes of 256 positions, one
    request of 120 tokens decoding 12 more: through the einsums every round
    reads both parts whole; through the kernel (forced here, interpreted,
    blocks of 128) the live lane's blocks and one block an idle lane."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models import serving, transformer
    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, Transformer, TransformerConfig)
    from parameter_server_distributed_tpu.ops.pallas import full_decode

    monkeypatch.setattr(full_decode, "LARGEST_BLOCK", 128)
    monkeypatch.setattr(transformer, "_kernel_backend",
                        lambda: arm == "kernel")
    model = Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, head_dim=64,
        n_layers=2, d_ff=48, max_seq=256, dtype=jnp.float32,
        pattern=(LayerSpec(),)))
    srv = serving.DecodeServer(model, model.init_params(0), slots=3,
                               max_len=256)

    def serve():
        srv.submit(np.arange(120) % 64, max_new_tokens=12)
        srv.run_to_completion()
        return srv.stats["steps"]

    moved, rounds = _moved(serve)
    assert rounds >= 11 and moved[CACHED] == rounds * 2 * 3 * 256
    if arm == "dense":
        assert moved[READ] == moved[CACHED]
    else:
        # round r holds 120 + r + 1 positions in its live lane: one block
        # up to 128, two beyond; an idle lane's one position is a block
        assert moved[READ] == 2 * 128 * sum(
            (1 if 120 + r + 1 <= 128 else 2) + 2 for r in range(rounds))
        assert moved[READ] < 0.6 * moved[CACHED]


def test_a_model_with_no_full_layer_counts_nothing():
    """Rings alone (every layer a window shorter than the lane): neither
    counter moves, so the metric is absent."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models import serving
    from parameter_server_distributed_tpu.models.transformer import (
        LayerSpec, Transformer, TransformerConfig)

    model = Transformer(TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_seq=64,
        dtype=jnp.float32, pattern=(LayerSpec(window=8),)))
    srv = serving.DecodeServer(model, model.init_params(0), slots=2,
                               max_len=64)

    def serve():
        srv.submit(np.arange(1, 11), max_new_tokens=4)
        srv.run_to_completion()
        return srv.stats["steps"]

    moved, rounds = _moved(serve)
    assert rounds >= 3 and moved == {READ: 0, CACHED: 0}
