"""The SmallThinker family and its cell: the family module held to the list
the benchmark calls, its counts against numbers worked out by hand, the
configuration file against the published row, a traced rehearsal of
``serve_docs_chat_smallthinker`` held to every new per-layer metric a CPU
can read, and the three readers the cell brought.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import families, harness  # noqa: E402
from perfbench.families import smallthinker  # noqa: E402
from perfbench.readers import (counter_ratio, gauge,  # noqa: E402
                               scope_bytes_roofline_pct, scopes_share_pct)

CELL = "serve_docs_chat_smallthinker"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", "smallthinker-21b-a3b-8l.json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW_METRICS = {
    "serve.moe_share_pct", "serve.attn_window_share_pct",
    "serve.attn_full_share_pct", "serve.experts_touched_pct",
    "serve.expert_load_max_over_mean", "serve.cache_window_gb",
    "serve.cache_full_gb", "serve.moe_experts_roofline_pct"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = {"serve.moe_share_pct", "serve.attn_window_share_pct",
                  "serve.attn_full_share_pct",
                  "serve.moe_experts_roofline_pct"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is smallthinker
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(smallthinker, name)), name
        assert name in families.__doc__
    assert set(smallthinker.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert smallthinker.vocab_size(CONFIG) == 151_936
    assert smallthinker.max_context(CONFIG) == 16_384


def test_counts_against_numbers_worked_out_by_hand():
    # one layer: q 2560 x 3584 + k, v 2 x (2560 x 512) + o 3584 x 2560
    attention = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560
    assert attention == 20_971_520
    experts = 64 * 3 * 2560 * 768
    assert experts == 377_487_360
    layer = attention + 2560 * 64 + 2 * 2560 + experts
    assert layer == 398_627_840
    assert smallthinker.layer_params(CONFIG) == layer
    assert smallthinker.param_count(CONFIG) == (
        8 * layer + 2 * 151_936 * 2560 + 2560) == 3_966_937_600
    assert CONFIG["parameters"] == 3_966_937_600
    # the program's store holds exactly these
    model = smallthinker.model(CONFIG)
    assert model.num_params() == 3_966_937_600
    # a token meets 6 of 64 experts, the router and the head
    active = 8 * (attention + 2560 * 64 + 6 * 3 * 2560 * 768) \
        + 151_936 * 2560
    assert smallthinker.active_matmul_params(CONFIG) == active
    # 2 full layers see every key, 6 window layers at most 4,096
    assert smallthinker.train_flops_per_token(CONFIG, 1024) == \
        6 * active + 12 * 3584 * 8 * 1024
    assert smallthinker.train_flops_per_token(CONFIG, 16_384) == \
        6 * active + 12 * 3584 * (2 * 16_384 + 6 * 4096)
    # the program's own count of an active-experts step agrees on the
    # parameter term (its attention term counts d_model, not the heads')
    assert model.flops_per_sample() > 0


def test_cache_bytes_by_kind_against_the_issue():
    # a position is 2 x 4 x 128 x 2 B a layer
    assert smallthinker.kv_bytes_per_position(CONFIG) == 2048
    slot = smallthinker.slot_bytes(CONFIG, 16_384)
    assert slot == {"full": 2 * 16_384 * 2048, "window": 6 * 4096 * 2048}
    assert round(sum(slot.values()) / 1e6, 1) == 117.4
    assert round(16 * sum(slot.values()) / 1e9, 2) == 1.88
    # one array for all layers would be 268.4 MB a slot
    assert round(8 * 16_384 * 2048 / 1e6, 1) == 268.4
    # a context shorter than the window holds no ring
    assert smallthinker.slot_bytes(CONFIG, 1024) == {
        "full": 2 * 1024 * 2048, "window": 6 * 1024 * 2048}


def test_expert_bytes_are_the_touched_experts_and_the_rows():
    one_expert = 3 * 2560 * 768 * 2
    assert smallthinker.moe_experts_bytes(CONFIG, 1, 0) == one_expert
    row = 2 * 2560 * 2 + 4 * 768 * 2 + 2 * 768 * 2 + 2560 * 4
    assert smallthinker.moe_experts_bytes(CONFIG, 0, 1) == row
    # a round of 16 tokens: 96 rows over about 51 experts of each of 8
    # layers is the weights' to within a hundredth
    moved = smallthinker.moe_experts_bytes(CONFIG, 8 * 51, 8 * 96)
    assert 0.99 < 8 * 51 * one_expert / moved < 1.0


def test_the_configuration_is_the_published_row_cut_in_depth_only():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "rope_layout",
                                 "sliding_window_layout"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 8
    assert CONFIG["rope_layout"] == row["config"]["rope_layout"][:8]
    assert CONFIG["sliding_window_layout"] == \
        row["config"]["sliding_window_layout"][:8]
    assert smallthinker.layer_period(CONFIG) == [(0, 0), (1, 1), (1, 1),
                                                 (1, 1)]
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "smallthinker-21b-a3b-8l")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cell_is_what_the_issue_asks():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "docs_and_chat"
    traffic = harness.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "docs_and_chat.json"))
    assert traffic["sessions"]["system_prompts"] == [256, 1024, 6144, 12288]
    assert traffic["sessions"]["popularity"] == [1, 1, 1, 1]
    assert (traffic["user_tokens"]["median"], traffic["user_tokens"]["min"],
            traffic["user_tokens"]["max"]) == (64, 8, 512)
    assert (traffic["output_tokens"]["median"],
            traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (96, 8, 384)
    assert traffic["server"]["max_len"] == 16_384
    assert traffic["server"]["prompt_cache"] == 8
    assert traffic["server"]["prefix_cache_bytes"] == 1 << 30
    check = traffic["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 6144, 16)
    rate = traffic["arrivals"]
    assert rate["rate_per_s"] == pytest.approx(0.8 * rate["knee_per_s"])
    mine = {m["name"] for m in harness.metrics_of(BENCHMARK, cell,
                                                  "per_layer")}
    assert NEW_METRICS <= mine
    for m in BENCHMARK["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    assert {m["name"] for m in harness.metrics_of(
        BENCHMARK, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}


def test_request_zero_carries_the_longest_prefix_and_its_check_fits():
    """``jobs/serve.py`` replays request 0 through the live server for
    ``served_ok``: it carries the 12,288-token document, as ISSUE 27 asks,
    so the rings have wrapped twice and the row sits in the 12,288 bucket.
    The reference's logits over the whole sequence (7.5 GB beside 7.9 GB
    of weights) go to the host's memory a block of rows at a time, which
    the chip's compiler takes where S is a multiple of 8, so S is one."""
    from perfbench import traffic_gen

    traffic = harness.load_json(os.path.join(
        ROOT, "perfbench", "traffic", "docs_and_chat.json"))
    shape = traffic_gen.serve_shape(traffic, BENCHMARK["run_seconds"])
    prefix = traffic["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 12288 == max(traffic["sessions"]["system_prompts"])
    assert prefix > 2 * CONFIG["sliding_window_size"]
    served = prefix + int(shape["user_len"][0]) + 1 \
        + traffic["check"]["served_tokens"]
    assert served % 8 == 0
    # every prefix is used, in about equal shares
    counts = [int((shape["system"] == i).sum()) for i in range(4)]
    assert min(counts) > 0.15 * shape["n"]


def test_the_entry_points_resolve_it_from_the_registry():
    """Registered under its own name as any configuration is; the entry
    points' overrides (dtype, remat, scan_layers) reach the family."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.registry import (
        REGISTRY, get_model_and_batches)
    from perfbench import program, traffic_gen

    tiny = smallthinker.tiny(CONFIG)
    name = program.register_model(
        tiny, lambda batch, seed: traffic_gen.token_batches(
            batch, 32, tiny["vocab_size"], seed))
    try:
        assert name == "smallthinker-21b-a3b-8l-transformer-lm"
        model, batches = get_model_and_batches(name, 2, dtype="f32",
                                               scan=True)
        assert model.config.scan_layers and model.config.dtype == jnp.float32
        assert [s.window for s in model.config.period] == [0, 16, 16, 16]
        assert next(batches).shape == (2, 32)
    finally:
        del REGISTRY[name]


# ------------------------------------------------------------- rehearsal
def test_a_traced_rehearsal_reads_every_new_metric_a_cpu_can():
    """Two periods and a window (16) shorter than the rehearsal's prompts
    (20..32 + a turn): the rings wrap, the prefix comes from the tree."""
    tiny = smallthinker.tiny(CONFIG)
    assert tiny["num_hidden_layers"] == 8
    assert tiny["sliding_window_size"] == 16 < 20
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    line = lines[-1]["not_a_result"]
    assert line["correct"] is True and line["failed"] == 0
    checks = next(l for l in lines if l.get("detail") == "checks")
    assert checks["served_ok"] and checks["logits"]["ok"]
    assert checks["logits"]["logits_rms_error_std"] < 1e-4
    metrics = line["metrics"]
    assert NEW_METRICS - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert FROM_THE_TRACE <= set(absent["names"])
    assert 0 < metrics["serve.experts_touched_pct"]["value"] <= 100
    assert metrics["serve.expert_load_max_over_mean"]["value"] >= 1
    # 4 slots of 128 positions: 2 full layers whole, 6 rings of 16
    position = 2 * 2 * 16 * 4
    assert metrics["serve.cache_full_gb"]["value"] == pytest.approx(
        4 * 2 * 128 * position / 1e9)
    assert metrics["serve.cache_window_gb"]["value"] == pytest.approx(
        4 * 6 * 16 * position / 1e9)
    assert metrics["serve.prefix_hit_pct"]["value"] > 50


# --------------------------------------------------------------- readers
def _observed(before, after, gauges=None, trace=None, window_s=50.0):
    return {"registry_before": {"counters": before},
            "registry_after": {"counters": after, "gauges": gauges or {}},
            "trace": trace, "window_s": window_s}


def test_counter_ratio_reads_the_window_only():
    observed = _observed({"a": 10, "b": 4}, {"a": 40, "b": 10})
    assert counter_ratio.read(observed, "a", "b") == 5.0
    assert counter_ratio.read(observed, "a", "b", scale=100.0) == 500.0
    # nothing counted, or no such counter: nothing to report
    assert counter_ratio.read(_observed({"a": 1, "b": 4}, {"a": 9, "b": 4}),
                              "a", "b") is None
    assert counter_ratio.read(observed, "a", "missing") is None
    assert counter_ratio.read(observed, "missing", "b") is None


def test_gauge_reads_what_stood_at_the_close():
    observed = _observed({}, {}, gauges={"serve.cache.full_bytes": 2e9})
    assert gauge.read(observed, "serve.cache.full_bytes", 1e-9) == 2.0
    assert gauge.read(observed, "serve.cache.window_bytes") is None
    assert gauge.read({"registry_after": {"counters": {}}}, "x") is None


def test_roofline_share_scales_the_bytes_to_the_traced_part(monkeypatch):
    import jax

    class Chip:
        device_kind = "TPU v5e"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    args = {"scopes": ["moe/experts", "ragged-dot-none:"],
            "config": "smallthinker-21b-a3b-8l",
            "bytes": "moe_experts_bytes",
            "counters": {"experts_touched": ["t", "admit_t"],
                         "assignments": ["a"]}}
    # the chip's grouped matmul carries no path but its own name
    trace = {"window_s": 5.0, "busy_s": 4.0, "by_scope": {
        "ragged-dot-none:": 1.5,
        "jit(run)/moe/router/sort:": 0.5,
        "jit(run)/layer/moe/experts/mul:": 0.5}}
    assert scopes_share_pct.read(
        {"trace": trace}, ["moe", "ragged-dot-none:"]) == 62.5
    assert scopes_share_pct.read({"trace": trace}, ["moe"]) == 25.0
    assert scopes_share_pct.read({"trace": None}, ["moe"]) is None
    observed = _observed({"t": 0, "a": 0}, {"t": 4000, "admit_t": 96,
                                            "a": 9000}, trace=trace)
    moved = smallthinker.moe_experts_bytes(CONFIG, 4096, 9000)
    want = 100.0 * (moved * 5.0 / 50.0 / 819e9) / 2.0
    assert scope_bytes_roofline_pct.read(observed, **args) == \
        pytest.approx(want)
    # a parent without the counters, a run without a trace, a block that
    # is not in the trace, a device without published peaks: nothing
    assert scope_bytes_roofline_pct.read(
        _observed({}, {"x": 1}, trace=trace), **args) is None
    assert scope_bytes_roofline_pct.read(
        _observed({}, {"t": 1, "a": 1}), **args) is None
    assert scope_bytes_roofline_pct.read(
        _observed({}, {"t": 1, "a": 1}, trace={
            "window_s": 5.0, "busy_s": 1.0, "by_scope": {"attn": 1.0}}),
        **args) is None
    Chip.device_kind = "cpu"
    assert scope_bytes_roofline_pct.read(observed, **args) is None
