"""The eleven metrics of PR 38: an admission by its five legs (a median
each, and the first token's 95th percentile) and the slow legs' five
counters.  Each has its entry, its file, and a reading through
``harness.read_per_layer`` of a made-up registry.  The five counters'
names end in ``_in_window`` because they count what should not happen and
read 0 when all is well (the runner's rehearsal allows a 0 only of such a
name); none of them may be absent where the program has the counter.
Asserts on these metrics and the lists they are IN, never on how many
metrics or cells there are.  CPU only, no JAX."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
SERVING_CELLS = ("serve_chat_gpt2m", "serve_docs_chat_smallthinker",
                 "serve_longdocs_chat_minicpm_sala",
                 "serve_manychat_lfm2_24b_a2b")
ADMISSION, DECODE = "admission and prefix cache", "decode step"

# name: (unit, layer, the file's content)
LEGS = {
    f"serve.admit_{leg}_p50_ms": ("ms", ADMISSION, {
        "reader": "hist_p50_ms",
        "args": {"histogram": f"serve.admit_{leg}_s"}})
    for leg in ("lookup", "forward", "tree", "first_token", "splice")}
LEGS["serve.admit_first_token_p95_ms"] = ("ms", ADMISSION, {
    "reader": "hist_percentile_ms",
    "args": {"histogram": "serve.admit_first_token_s", "q": 95}})
COUNTS = {
    f"{counter}_in_window": ("count" if counter.endswith("legs") else "s",
                             DECODE, {"reader": "counter_delta",
                                      "args": {"counter": counter}})
    for counter in ("serve.slow_legs", "serve.slow_leg_s",
                    "serve.slow_leg_cpu_s", "serve.slow_leg_gc_s",
                    "serve.slow_leg_device_wait_s")}
METRICS = {**LEGS, **COUNTS}


def only(name):
    """The benchmark with this one per-layer metric, and a cell in it."""
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "serve_docs_chat_smallthinker")
    return dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == name]), cell


def registry(counters=None, histograms=None):
    return {"counters": counters or {}, "histograms": histograms or {},
            "gauges": {}}


def histogram(bucket_counts: dict, total: float) -> dict:
    """A snapshot as ``obs.stats.Histogram.snapshot`` gives it: bucket i
    spans (2**((i-1)/4), 2**(i/4)] seconds."""
    count = sum(bucket_counts.values())
    return {"count": count, "sum": total, "zeros": 0, "min": 1e-6,
            "max": 10.0, "buckets": dict(bucket_counts)}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_and_the_file_say_what_the_metric_is(name):
    unit, layer, spec = METRICS[name]
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["unit"] == unit and entry["better"] == "lower"
    assert entry["source"] == "program_counter" and entry["layer"] == layer
    assert entry["moves"] == "itl_p95_ms"
    # every serving cell, and each of them reports the metric it moves
    assert set(SERVING_CELLS) <= set(entry["workloads"])
    moved, = [m for m in BENCHMARK["end_to_end"]
              if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{name}.json")) == spec
    assert os.path.exists(os.path.join(
        ROOT, "perfbench", "readers", f"{spec['reader']}.py"))


@pytest.mark.parametrize("name", sorted(LEGS))
def test_a_leg_reads_what_its_histogram_took_inside_the_window(name):
    source = LEGS[name][2]["args"]["histogram"]
    # before the window: 50 admissions of 1 ms (warm-up; bucket -40 ends
    # at 2**-10 s).  Inside it: 90 of 7-8 ms (bucket -28 ends at 2**-7 s)
    # and 10 of 53-62 ms (bucket -16 ends at 2**-4 s); a percentile is
    # its bucket's midpoint
    before = histogram({-40: 50}, 0.05)
    after = histogram({-40: 50, -28: 90, -16: 10}, 0.05 + 0.72 + 0.64)
    benchmark, cell = only(name)
    observed = {"registry_before": registry(histograms={source: before}),
                "registry_after": registry(histograms={source: after})}
    got = harness.read_per_layer(benchmark, cell, observed)
    bucket = -16 if name.endswith("p95_ms") else -28
    assert got == {name: {"unit": "ms", "value": pytest.approx(
        1e3 * 2.0 ** ((bucket - 0.5) / 4))}}
    # no admission inside the window, or a program without the leg (the
    # parent): nothing, and no error
    observed["registry_after"] = observed["registry_before"]
    assert harness.read_per_layer(benchmark, cell, observed) == {}
    observed = {"registry_before": registry(), "registry_after": registry()}
    assert harness.read_per_layer(benchmark, cell, observed) == {}


@pytest.mark.parametrize("name", sorted(COUNTS))
@pytest.mark.parametrize("before,after,expected", [
    (0.75, 1.125, 0.375),     # the warm-up's compiles, then a stall
    (0.75, 0.75, 0.0),        # all is well: 0, and never absent
    (None, None, None),       # a program without the counter (the parent)
], ids=["a_slow_leg", "none", "no_such_counter"])
def test_a_count_reads_its_counters_growth_and_never_none(
        name, before, after, expected):
    unit, _, spec = COUNTS[name]
    counter = spec["args"]["counter"]
    benchmark, cell = only(name)
    observed = {
        "registry_before": registry(
            counters={} if before is None else {counter: before}),
        "registry_after": registry(
            counters={} if after is None else {counter: after})}
    got = harness.read_per_layer(benchmark, cell, observed)
    if expected is None:
        assert got == {}
    else:
        assert got == {name: {"value": pytest.approx(expected),
                              "unit": unit}}


def test_the_program_makes_what_the_files_name():
    """The histograms and counters the eleven files name are the ones
    ``DecodeServer.__init__`` and ``obs/legs.py`` make (read off the
    source: this file imports no JAX)."""
    package = os.path.join(ROOT, "parameter_server_distributed_tpu")
    with open(os.path.join(package, "obs", "legs.py")) as f:
        legs = f.read()
    with open(os.path.join(package, "models", "serving.py")) as f:
        serving = f.read()
    for _, _, spec in COUNTS.values():
        assert f'"{spec["args"]["counter"]}"' in legs
    assert 'obs_stats.histogram(f"serve.admit_{name}_s")' in serving
    for _, _, spec in LEGS.values():
        leg = spec["args"]["histogram"][len("serve.admit_"):-len("_s")]
        assert f'"{leg}"' in serving
        assert f'"serve/admit/{leg}"' in serving
