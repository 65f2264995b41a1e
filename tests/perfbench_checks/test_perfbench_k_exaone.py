"""The K-EXAONE family and its cell: the family module held to the list the
benchmark calls, its counts against ISSUE 40's arithmetic, the configuration
file against the published row and its cut (depth, the experts held, the
vocabulary), the cell and its traffic against what the issue asks, and a
traced rehearsal of ``serve_chat_k_exaone_ep8`` held to every metric of the
cell a CPU can read.  By the rule of ``perfbench/README.md``: what is
asserted is this cell, its files and the lists it is IN, on
``BENCHMARK.json`` and on the widened copy.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import families, harness, traffic_gen  # noqa: E402
from perfbench.families import k_exaone  # noqa: E402

CELL = "serve_chat_k_exaone_ep8"
NAME = "k-exaone-236b-a23b-8l-ep8"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", NAME + ".json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "chat_global_batch.json"))
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size"]
NEW_METRICS = {"serve.moe_held_assignments_pct": "program_counter",
               "serve.moe_shared_share_pct": "device_trace"}
# the accepted metrics of a mechanism whose lists this cell joins
JOINED = {
    "serve.moe_share_pct", "serve.moe_experts_roofline_pct",
    "serve.experts_touched_pct", "serve.expert_load_max_over_mean",
    "serve.attn_window_share_pct", "serve.attn_full_share_pct",
    "serve.cache_window_gb", "serve.cache_full_gb",
    "serve.cache_update_share_pct", "serve.round_chained_pct",
    "serve.round_p50_ms", "serve.prefill_share_pct",
    "serve.programs_in_window", "serve.admit_p50_ms",
    "serve.admit_device_p50_ms", "serve.admit_lookup_p50_ms",
    "serve.admit_forward_p50_ms", "serve.admit_tree_p50_ms",
    "serve.admit_first_token_p50_ms", "serve.admit_first_token_p95_ms",
    "serve.admit_splice_p50_ms", "serve.slow_legs_in_window",
    "serve.slow_leg_s_in_window", "serve.slow_leg_cpu_s_in_window",
    "serve.slow_leg_gc_s_in_window", "serve.slow_leg_device_wait_s_in_window",
    "serve.prefix_hit_pct", "serve.occupancy_pct", "serve.slo_ok_pct",
    "serve.ttft_p50_ms", "serve.ttft_p95_ms", "gen.late_p95_ms",
    "device.idle_pct.serve", "device.peak_hbm_gb.serve"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = {
    "serve.moe_shared_share_pct", "device.idle_pct.serve",
    "device.peak_hbm_gb.serve", "serve.cache_update_share_pct",
    "serve.moe_share_pct", "serve.moe_experts_roofline_pct",
    "serve.attn_full_share_pct", "serve.attn_window_share_pct"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is k_exaone
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(k_exaone, name)), name
        assert name in families.__doc__
    assert callable(k_exaone.moe_experts_bytes)
    assert set(k_exaone.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert 0.01 < k_exaone.SELECTION_MARGIN < 0.2
    assert k_exaone.vocab_size(CONFIG) == 19_200
    assert k_exaone.max_context(CONFIG) == 262_144


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "perfbench", "reference", "k_exaone.py")
    with open(path) as handle:
        source = handle.read()
    imported = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or ".").split(".")[0])
    assert imported == {"__future__", "math", "jax"}
    assert 'default_matmul_precision("highest")' in source
    assert "held" in source and "ragged" not in source.replace(
        "no grouped matmul", "")


@pytest.mark.parametrize("what,expected", [
    ("attention", 113_246_208), ("dense_layer", 452_997_376),
    ("one_expert", 37_748_736), ("expert_layer_held", 755_773_824),
    ("expert_layer_whole", 4_983_632_256),
    ("embedding_head_norm", 235_935_744), ("total", 5_979_349_888)])
def test_counts_against_the_issues_arithmetic(what, expected):
    d, dense, expert, routed, held, vocab = 6144, 18_432, 2048, 128, 16, 19_200
    counted = {"attention": 2 * d * 64 * 128 + 2 * d * 8 * 128,
               "one_expert": 3 * d * expert,
               "embedding_head_norm": 2 * vocab * d + d}
    norms = 2 * 128 + 2 * d
    counted["dense_layer"] = counted["attention"] + 3 * d * dense + norms

    def expert_layer(experts):
        return (counted["attention"] + norms + d * routed + routed
                + (1 + experts) * counted["one_expert"])

    counted["expert_layer_held"] = expert_layer(held)
    counted["expert_layer_whole"] = expert_layer(routed)
    counted["total"] = (counted["dense_layer"]
                        + 7 * counted["expert_layer_held"]
                        + counted["embedding_head_norm"])
    assert counted[what] == expected
    assert k_exaone.param_count(CONFIG) == 5_979_349_888 \
        == CONFIG["parameters"]
    assert [k_exaone.layer_params(CONFIG, i) for i in (0, 1, 3)] == [
        452_997_376, 755_773_824, 755_773_824]
    assert CONFIG["published"]["parameters_per_expert_layer"] == \
        counted["expert_layer_whole"]


def test_the_programs_store_and_cache_are_the_issues_bytes():
    model = k_exaone.model(CONFIG)
    assert model.num_params() == CONFIG["parameters"]
    c = model.config
    assert [c.layer_spec(i).window for i in range(8)] == [
        128, 128, 128, 0, 128, 128, 128, 0]
    assert [c.layer_spec(i).rope for i in range(8)] == [
        True, True, True, False] * 2
    assert [c.layer_spec(i).ffn for i in range(8)] == \
        ["mlp"] + ["experts"] * 7
    # the router's width 128 with 16 held, top-8, one shared expert
    assert (c.moe_experts, c.moe_held, c.moe_top_k,
            c.moe_shared_experts) == (128, (0, 16), 8, 1)
    assert (c.d_model, c.d_ff, c.expert_width) == (6144, 18_432, 2048)
    assert (c.n_heads, c.kv_heads, c.head_dim) == (64, 8, 128)
    assert (c.rope_theta, c.norm_eps, c.moe_route_scale) == (1e6, 1e-5, 2.5)
    assert (c.norm_placement, c.moe_score, c.moe_expert_bias) == (
        "post", "sigmoid", True)
    slot = k_exaone.slot_bytes(CONFIG, 4096)
    # a cached position is 4,096 B a layer: K and V x 8 heads x 128 x 2 B
    assert slot["full"] == 2 * 4096 * 4096
    assert slot["window"] == 6 * 128 * 4096
    assert 32 * slot["full"] == 1_073_741_824
    assert 32 * slot["window"] == 100_663_296
    # one touched expert: 75,497,472 B; one row through it
    assert k_exaone.moe_experts_bytes(CONFIG, 1, 0) == 75_497_472
    assert k_exaone.moe_experts_bytes(CONFIG, 0, 1) == \
        2 * 6144 * 2 + 6 * 2048 * 2 + 6144 * 4
    # a token meets attention, the dense layer, 7 routers, 7 shared experts,
    # ONE held expert a layer on average (8 x 16 / 128) and the head's rows
    assert k_exaone.active_matmul_params(CONFIG) == (
        8 * 113_246_208 + 3 * 6144 * 18_432
        + 7 * (6144 * 128 + 2 * 37_748_736) + 19_200 * 6144)
    flops = k_exaone.train_flops_per_token(CONFIG, 4096)
    assert flops == 6.0 * k_exaone.active_matmul_params(CONFIG) \
        + 12.0 * 8192 * (6 * 128 + 2 * 4096)


def test_the_configuration_is_the_published_row_and_its_cut():
    assert CONFIG["reduced"] == REDUCED
    assert CONFIG["omitted"] == ["mtp"]
    assert CONFIG["expert_parallel"] == {
        "ranks": 8, "rank": 0, "first_expert": 0, "held": 16}
    assert (CONFIG["num_experts"], CONFIG["num_router_experts"]) == (16, 128)
    assert CONFIG["published"]["num_experts"] == 128
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["published"]["vocab_size"] == 153_600 == 8 * 19_200
    assert "236B" in CONFIG["published"]["parameters"]
    assert "deployment" in CONFIG and "8-way" in CONFIG["deployment"]
    # the published widths
    for key, value in {
            "hidden_size": 6144, "num_attention_heads": 64,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 18_432, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "sliding_window": 128,
            "routed_scaling_factor": 2.5, "num_shared_experts": 1,
            "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
            "scoring_func": "sigmoid", "norm_topk_prob": True}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["rope_parameters"]["rope_theta"] == 1_000_000
    # the floors: two whole periods, 7 layers after the dense one, 16
    # experts, an eighth of the vocabulary
    assert CONFIG["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert CONFIG["sliding_windows"] == [128, 128, 128, 0] * 2
    for item in ("norm_placement", "why_norm_placement", "qk_norm",
                 "rotary", "window", "router", "expert_bias", "experts",
                 "held_experts", "weights"):
        assert item in CONFIG["assumed"], item
    assert CONFIG["assumed"]["norm_placement"] == "post"
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert CONFIG[key] == row["config"][key][:8]


def test_the_cell_is_what_the_issue_asks(checkout):
    """Held on the benchmark as committed and on the widened copy."""
    benchmark = checkout.benchmark
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "chat_global_batch"
    assert cell["config"] == NAME and len(cell["why"]) <= 200
    assert TRAFFIC["job"] == "serve"
    assert TRAFFIC["sessions"]["system_prompts"] == [256, 512, 1024, 2048]
    assert TRAFFIC["sessions"]["popularity"] == [1, 1, 1, 1]
    for key, (median, low, high) in {"user_tokens": (96, 8, 384),
                                     "output_tokens": (192, 16, 512)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert (server["slots"], server["max_len"]) == (32, 4096)
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] in (1 << 29, 1 << 28)
    assert (TRAFFIC["warmup"]["max_new"], TRAFFIC["trace_seconds"],
            TRAFFIC["drain_seconds"]) == (4, 6, 20)
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 4096, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["process"] == "poisson"
    # a fifth of the swept knee: four of them unless that lies in the cliff
    fifths = 5 * rate["rate_per_s"] / rate["knee_per_s"]
    assert fifths == pytest.approx(round(fifths)) and 2 <= round(fifths) <= 4
    mine = {m["name"] for m in harness.metrics_of(benchmark, cell,
                                                  "per_layer")}
    assert set(NEW_METRICS) | JOINED <= mine
    for m in benchmark["per_layer"]:
        if m["name"] in set(NEW_METRICS) | JOINED:
            assert CELL in m["workloads"]
            assert os.path.exists(os.path.join(
                checkout.root, "perfbench", "metrics", m["name"] + ".json"))
        if m["name"] in NEW_METRICS:
            assert m["moves"] == "itl_p95_ms" and m["layer"] == "decode step"
            assert m["source"] == NEW_METRICS[m["name"]] and m["unit"] == "%"
            assert m["workloads"][0] == CELL
    assert {m["name"] for m in harness.metrics_of(
        benchmark, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}
    # no new kernel, no new roofline: the shared one reads THIS family's
    # count of the held experts touched and the rows computed
    roofline = harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.moe_experts_roofline_pct.json"))
    assert roofline["args"]["bytes"] == "moe_experts_bytes"
    assert roofline["args"]["counters"]["assignments"] == [
        "serve.moe.assignments"]
    assert harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.moe_held_assignments_pct.json")) == {
        "reader": "counter_ratio", "args": {
            "numerator": "serve.moe.assignments",
            "denominator": "serve.moe.assignments_routed", "scale": 100.0}}
    assert harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.moe_shared_share_pct.json")) == {
        "reader": "scope_share_pct", "args": {"scope": "moe/shared"}}


def test_request_zero_carries_the_longest_system_prompt():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries
    the 2,048-token system prompt; every prompt is used, in about equal
    shares; the longest request fits a lane; a third of the turns are
    longer than the ring."""
    shape = traffic_gen.serve_shape(TRAFFIC, BENCHMARK["run_seconds"])
    prefix = TRAFFIC["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 2048
    counts = [int((shape["system"] == i).sum()) for i in range(4)]
    assert min(counts) > 0.18 * shape["n"]
    longest = 2048 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]
    assert shape["n"] == round(TRAFFIC["arrivals"]["rate_per_s"]
                               * BENCHMARK["run_seconds"])
    past_the_ring = (shape["user_len"] > CONFIG["sliding_window"]).mean()
    assert 0.25 < past_the_ring < 0.5


def test_a_traced_rehearsal_reads_every_metric_a_cpu_can():
    """Six layers at the tiny size (the dense one, three sliding, a full
    one, two sliding more), 4 lanes, a quarter of 16 experts held: the
    prefixes come from the tree, every turn crosses rings of 8."""
    tiny = k_exaone.tiny(CONFIG)
    assert tiny["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 2
    assert (tiny["num_experts"], tiny["num_router_experts"]) == (4, 16)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000023", "--seconds", "2",
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
    assert selection and all(
        sum(l["tokens_with_another_expert"]) == 0 for l in selection)
    metrics = line["metrics"]
    assert (JOINED | set(NEW_METRICS)) - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert set(absent["names"]) == FROM_THE_TRACE
    # 4 lanes: one full layer of 128 positions, five rings of 8; K and V
    # of 2 heads x 16 float32
    assert metrics["serve.cache_full_gb"]["value"] == pytest.approx(
        4 * 128 * 2 * 2 * 16 * 4 / 1e9)
    assert metrics["serve.cache_window_gb"]["value"] == pytest.approx(
        4 * 5 * 8 * 2 * 2 * 16 * 4 / 1e9)
    assert metrics["serve.programs_in_window"]["value"] == 0
    # a quarter of the experts held: about a quarter of the rows computed
    assert 10 < metrics["serve.moe_held_assignments_pct"]["value"] < 45
    assert 0 < metrics["serve.experts_touched_pct"]["value"] <= 100
    assert metrics["serve.expert_load_max_over_mean"]["value"] >= 1
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
