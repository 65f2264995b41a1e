"""The LFM2 family and its cell: the family module held to the list the
benchmark calls, its counts against ISSUE 35's arithmetic, the configuration
file against the published row, the cell and its traffic against what the
issue asks, and a traced rehearsal of ``serve_manychat_lfm2_24b_a2b`` held
to every metric of the cell a CPU can read.  By the rule of
``perfbench/README.md``: what is asserted is this cell, its files and the
lists it is IN, on ``BENCHMARK.json`` and on the widened copy.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import families, harness, traffic_gen  # noqa: E402
from perfbench.families import lfm2  # noqa: E402

CELL = "serve_manychat_lfm2_24b_a2b"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", "lfm2-24b-a2b-10l.json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "chat_many_lanes.json"))
NEW_METRICS = {"serve.attn_conv_share_pct"}
# the accepted metrics of a mechanism whose lists this cell joins
JOINED = {
    "gen.late_p95_ms", "serve.prefix_hit_pct", "serve.occupancy_pct",
    "serve.slo_ok_pct", "serve.ttft_p95_ms", "serve.ttft_p50_ms",
    "serve.round_p50_ms", "serve.prefill_share_pct", "device.idle_pct.serve",
    "device.peak_hbm_gb.serve", "serve.cache_update_share_pct",
    "serve.round_chained_pct", "serve.moe_share_pct",
    "serve.experts_touched_pct", "serve.expert_load_max_over_mean",
    "serve.moe_experts_roofline_pct", "serve.attn_full_share_pct",
    "serve.cache_full_gb", "serve.cache_state_gb", "serve.admit_p50_ms",
    "serve.admit_device_p50_ms", "serve.programs_in_window"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = NEW_METRICS | {
    "device.idle_pct.serve", "device.peak_hbm_gb.serve",
    "serve.cache_update_share_pct", "serve.moe_share_pct",
    "serve.moe_experts_roofline_pct", "serve.attn_full_share_pct"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is lfm2
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(lfm2, name)), name
        assert name in families.__doc__
    assert callable(lfm2.moe_experts_bytes)
    # no count of the conv block's bytes: the compiler stages a conv
    # layer's output projection in fast memory with an asynchronous copy,
    # so the time under the block leaves out part of moving them and a
    # roofline share of it read past 100 (PERF.md, PR 35)
    assert not hasattr(lfm2, "conv_bytes")
    assert 0.025 < lfm2.SELECTION_MARGIN < 0.1
    assert set(lfm2.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert lfm2.vocab_size(CONFIG) == 65_536
    assert lfm2.max_context(CONFIG) == 128_000


@pytest.mark.parametrize("what,expected", [
    ("embedding_and_norm", 134_219_776), ("conv_mixer", 16_783_360),
    ("attention_mixer", 10_485_888), ("dense_swiglu", 72_351_744),
    ("one_expert", 9_437_184), ("experts_ffn", 604_110_912),
    ("dense_conv_layer", 89_139_200), ("attention_expert_layer", 614_600_896),
    ("conv_expert_layer", 620_898_368), ("total", 5_267_090_176)])
def test_counts_against_the_issues_arithmetic(what, expected):
    d, dense, expert, experts, kv = 2048, 11_776, 1536, 64, 8 * 64
    counted = {
        "embedding_and_norm": 65_536 * d + d,
        "conv_mixer": d * 3 * d + d * d + 3 * d,
        "attention_mixer": 2 * d * d + 2 * d * kv + 2 * 64,
        "dense_swiglu": 3 * d * dense,
        "one_expert": 3 * d * expert}
    counted["experts_ffn"] = (d * experts + experts
                              + experts * counted["one_expert"])
    counted["dense_conv_layer"] = (counted["conv_mixer"]
                                   + counted["dense_swiglu"] + 2 * d)
    counted["attention_expert_layer"] = (counted["attention_mixer"]
                                         + counted["experts_ffn"] + 2 * d)
    counted["conv_expert_layer"] = (counted["conv_mixer"]
                                    + counted["experts_ffn"] + 2 * d)
    counted["total"] = (counted["embedding_and_norm"]
                        + 2 * counted["dense_conv_layer"]
                        + 2 * counted["attention_expert_layer"]
                        + 6 * counted["conv_expert_layer"])
    assert counted[what] == expected
    assert lfm2.param_count(CONFIG) == 5_267_090_176 == CONFIG["parameters"]
    assert [lfm2.layer_params(CONFIG, i) for i in (0, 2, 3)] == [
        89_139_200, 614_600_896, 620_898_368]


def test_the_programs_store_and_cache_are_the_issues_bytes():
    model = lfm2.model(CONFIG)
    # the tied head is a second matrix in the program's store
    assert model.num_params() == CONFIG["parameters"] + 65_536 * 2048
    c = model.config
    assert [c.layer_spec(i).mixer for i in range(10)] == [
        "conv", "conv", "softmax", "conv", "conv", "conv",
        "softmax", "conv", "conv", "conv"]
    assert [c.layer_spec(i).ffn for i in range(10)] == \
        ["mlp"] * 2 + ["experts"] * 8
    assert (c.d_ff, c.expert_width, c.moe_experts, c.moe_top_k) == (
        11_776, 1536, 64, 4)
    assert (c.n_heads, c.kv_heads, c.head_dim, c.conv_kernel) == (
        32, 8, 64, 3)
    assert (c.rope_theta, c.norm_eps) == (1e6, 1e-5)
    slot = lfm2.slot_bytes(CONFIG, 4096)
    # a position: 2 attention layers x K and V x 8 heads x 64 x 2 B
    assert slot["full"] == 4096 * 4096 == 16_777_216
    assert slot["state"] == 8 * 2 * 2048 * 2 == 8 * 8192
    # every expert of 8 layers touched by 256 rows a layer
    assert lfm2.moe_experts_bytes(CONFIG, 8 * 64, 0) == \
        8 * 64 * 9_437_184 * 2 == 9_663_676_416
    assert lfm2.moe_experts_bytes(CONFIG, 0, 1) == \
        2 * 2048 * 2 + 6 * 1536 * 2 + 2048 * 4
    assert lfm2.active_matmul_params(dict(
        CONFIG, num_hidden_layers=40,
        layer_types=["conv", "conv"] + ["full_attention", "conv", "conv",
                                        "conv"] * 9 + ["full_attention",
                                                       "conv"])) == \
        CONFIG["published"]["active_parameters_per_token"]


def test_the_configuration_is_the_published_row_cut_in_depth_only():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 10
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:10]
    assert CONFIG["layer_types"].count("full_attention") == 2
    # the published whole, by the same count
    assert lfm2.param_count(dict(
        CONFIG, num_hidden_layers=40,
        layer_types=row["config"]["layer_types"])) == \
        CONFIG["published"]["parameters"] == 23_843_661_440
    for item in ("tie_word_embeddings", "expert_bias", "conv_state",
                 "rotary", "router", "weights", "scan_layers", "head_dim"):
        assert item in CONFIG["assumed"], item
    assert CONFIG["assumed"]["scan_layers"] is False
    assert "deployment" in CONFIG
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "lfm2-24b-a2b-10l")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "perfbench/configs/lfm2-24b-a2b-10l.json"


def test_the_stored_bias_moves_a_measurable_share_of_selections():
    """``assumed.expert_bias``: at the router's published width, logits
    normal(0, 1), the bias as make_weights draws it changes the chosen
    experts of a share of tokens that is neither nothing nor nearly all."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models import moe

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4000, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=64) * lfm2.EXPERT_BIAS_STD,
                       jnp.bfloat16)
    _, plain = moe.select_experts(logits, 4, "sigmoid")
    _, chosen = moe.select_experts(logits, 4, "sigmoid", bias)
    moved = np.any(np.sort(plain, -1) != np.sort(chosen, -1), -1).mean()
    assert 0.3 < moved < 0.55
    loads = np.bincount(np.asarray(chosen).ravel(), minlength=64)
    assert loads.min() > 0.3 * loads.mean()


def test_the_cell_is_what_the_issue_asks(checkout):
    """Held on the benchmark as committed and on the widened copy
    (``conftest.py``): what is asked of this cell, its files and the lists
    it is in, whoever else joins them."""
    benchmark = checkout.benchmark
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "chat_many_lanes"
    assert cell["config"] == "lfm2-24b-a2b-10l"
    assert TRAFFIC["job"] == "serve"
    assert TRAFFIC["sessions"]["system_prompts"] == [256, 512, 1024, 2048]
    assert TRAFFIC["sessions"]["popularity"] == [1, 1, 1, 1]
    for key, (median, low, high) in {"user_tokens": (48, 8, 256),
                                     "output_tokens": (128, 8, 512)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert (server["slots"], server["max_len"]) == (64, 4096)
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] == 1 << 30
    assert (TRAFFIC["warmup"]["max_new"], TRAFFIC["trace_seconds"],
            TRAFFIC["drain_seconds"]) == (4, 6, 20)
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 4096, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["process"] == "poisson"
    # four fifths of the swept knee, or two thirds by the issue's rule
    assert rate["rate_per_s"] / rate["knee_per_s"] == pytest.approx(
        0.8) or rate["rate_per_s"] / rate["knee_per_s"] == pytest.approx(
        2 / 3, abs=0.01)
    mine = {m["name"] for m in harness.metrics_of(benchmark, cell,
                                                  "per_layer")}
    assert NEW_METRICS | JOINED <= mine
    for m in benchmark["per_layer"]:
        if m["name"] in NEW_METRICS | JOINED:
            assert CELL in m["workloads"]
            assert os.path.exists(os.path.join(
                checkout.root, "perfbench", "metrics", m["name"] + ".json"))
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "itl_p95_ms" and m["layer"] == "decode step"
            assert m["source"] == "device_trace" and m["unit"] == "%"
    assert {m["name"] for m in harness.metrics_of(
        benchmark, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}
    # the shared roofline of the experts reads THIS family's count
    roofline = harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.moe_experts_roofline_pct.json"))
    assert roofline["args"]["bytes"] == "moe_experts_bytes"
    assert "config" not in roofline["args"]
    share = harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.attn_conv_share_pct.json"))
    assert share == {"reader": "scope_share_pct",
                     "args": {"scope": "attn/conv"}}


def test_request_zero_carries_the_longest_system_prompt():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries
    the 2,048-token system prompt; every prompt is used, in about equal
    shares; the longest request fits a lane."""
    shape = traffic_gen.serve_shape(TRAFFIC, BENCHMARK["run_seconds"])
    prefix = TRAFFIC["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 2048
    counts = [int((shape["system"] == i).sum()) for i in range(4)]
    assert min(counts) > 0.15 * shape["n"]
    longest = 2048 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]
    assert shape["n"] == round(TRAFFIC["arrivals"]["rate_per_s"]
                               * BENCHMARK["run_seconds"])


def test_a_traced_rehearsal_reads_every_metric_a_cpu_can():
    """Six layers of every kind at the tiny size, 4 lanes: the prefixes
    and their conv snapshots come from the tree, every round routes and
    advances five conv states a lane."""
    tiny = lfm2.tiny(CONFIG)
    assert tiny["layer_types"] == ["conv", "conv", "full_attention",
                                   "conv", "conv", "conv"]
    assert tiny["intermediate_size"] != tiny["moe_intermediate_size"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    line = lines[-1]["not_a_result"]
    assert line["correct"] is True and line["failed"] == 0
    checks = next(l for l in lines if l.get("detail") == "checks")
    assert checks["served_ok"] and checks["logits"]["ok"]
    assert checks["logits"]["logits_rms_error_std"] < 1e-4
    selection = [l for l in lines if l.get("detail") == "selection_check"]
    # float32: the program chooses what the reference chooses
    assert selection and all(
        sum(l["tokens_with_another_expert"]) == 0 for l in selection)
    metrics = line["metrics"]
    assert JOINED - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert set(absent["names"]) == FROM_THE_TRACE
    # 4 lanes, 5 conv layers of 2 columns x 64 float32
    assert metrics["serve.cache_state_gb"]["value"] == pytest.approx(
        4 * 5 * 2 * 64 * 4 / 1e9)
    # 4 lanes x 128 positions x K and V x 2 heads x 16 float32, one layer
    assert metrics["serve.cache_full_gb"]["value"] == pytest.approx(
        4 * 128 * 2 * 2 * 16 * 4 / 1e9)
    assert metrics["serve.programs_in_window"]["value"] == 0
    assert 0 < metrics["serve.experts_touched_pct"]["value"] <= 100
    assert metrics["serve.expert_load_max_over_mean"]["value"] >= 1
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
