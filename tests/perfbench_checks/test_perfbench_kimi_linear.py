"""The Kimi Linear family and its cell: the family module held to the list the
benchmark calls, its counts against ISSUE 47's arithmetic, the configuration
file against the published row and its cut (depth, the experts held, the
vocabulary), the cell and its traffic against what the issue asks, and a
traced rehearsal of ``serve_agents_kimi_linear_ep8`` held to every metric of
the cell a CPU can read.  By the rule of ``perfbench/README.md``: what is
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
from perfbench.families import kimi_linear  # noqa: E402

CELL = "serve_agents_kimi_linear_ep8"
NAME = "kimi-linear-48b-a3b-12l-ep8"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", NAME + ".json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "agents_long_lanes.json"))
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
NEW_METRICS = {
    "serve.attn_latent_share_pct": ("device_trace", "%", "decode step"),
    "serve.attn_latent_roofline_pct": ("device_trace", "%", "decode step"),
    "serve.cache_latent_gb": ("program_counter", "GB",
                              "admission and prefix cache"),
    "serve.latent_positions_read_pct": ("program_counter", "%",
                                        "decode step")}
# the accepted metrics of a mechanism whose lists this cell joins
JOINED = {
    "serve.attn_linear_share_pct", "serve.attn_linear_roofline_pct",
    "serve.cache_state_gb", "serve.moe_share_pct",
    "serve.moe_experts_roofline_pct", "serve.experts_touched_pct",
    "serve.expert_load_max_over_mean", "serve.moe_held_assignments_pct",
    "serve.moe_shared_share_pct", "serve.cache_update_share_pct",
    "serve.round_chained_pct", "serve.round_p50_ms",
    "serve.prefill_share_pct", "serve.programs_in_window",
    "serve.admit_p50_ms", "serve.admit_device_p50_ms",
    "serve.admit_lookup_p50_ms", "serve.admit_forward_p50_ms",
    "serve.admit_tree_p50_ms", "serve.admit_first_token_p50_ms",
    "serve.admit_first_token_p95_ms", "serve.admit_splice_p50_ms",
    "serve.slow_legs_in_window", "serve.slow_leg_s_in_window",
    "serve.slow_leg_cpu_s_in_window", "serve.slow_leg_gc_s_in_window",
    "serve.slow_leg_device_wait_s_in_window", "serve.prefix_hit_pct",
    "serve.occupancy_pct", "serve.slo_ok_pct", "serve.ttft_p50_ms",
    "serve.ttft_p95_ms", "gen.late_p95_ms", "device.idle_pct.serve",
    "device.peak_hbm_gb.serve"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = {
    "serve.attn_latent_share_pct", "serve.attn_latent_roofline_pct",
    "serve.attn_linear_share_pct", "serve.attn_linear_roofline_pct",
    "serve.moe_share_pct", "serve.moe_experts_roofline_pct",
    "serve.moe_shared_share_pct", "serve.cache_update_share_pct",
    "device.idle_pct.serve", "device.peak_hbm_gb.serve"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is kimi_linear
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(kimi_linear, name)), name
        assert name in families.__doc__
    for name in ("moe_experts_bytes", "linear_attn_bytes",
                 "latent_attn_bytes"):
        assert callable(getattr(kimi_linear, name)), name
    assert set(kimi_linear.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert 0.005 < kimi_linear.SELECTION_MARGIN < 0.2
    assert 0.003 < kimi_linear.STATE_TOLERANCE < 0.012
    assert kimi_linear.EXPERT_BIAS_STD == 0.005
    assert kimi_linear.vocab_size(CONFIG) == 20_480
    assert kimi_linear.max_context(CONFIG) == 1_048_576


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "perfbench", "reference", "kimi_linear.py")
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
    assert "lax.scan" in source and "triangular" not in source
    assert "Departures from the published code" in source


@pytest.mark.parametrize("what,expected", [
    ("one_expert", 7_077_888), ("kda_mixer", 39_514_272),
    ("mla_mixer", 29_114_880), ("router", 590_080),
    ("dense_ffn", 63_700_992), ("total", 3_176_867_744),
    ("published", 49_120_000_000)])
def test_counts_against_the_issues_arithmetic(what, expected):
    d, inner, vocab = 2304, 4096, 20_480
    counted = {
        "one_expert": 3 * d * 1024,
        "kda_mixer": (4 * d * inner + 3 * 4 * inner
                      + 2 * (d * 128 + 128 * inner) + d * 32 + 32 + inner
                      + 128),
        "mla_mixer": (d * 6144 + d * 576 + 512 + 512 * 8192 + inner * d),
        "router": d * 256 + 256, "dense_ffn": 3 * d * 9216}

    def layers(kda, mla, experts, dense=1):
        return ((kda * counted["kda_mixer"] + mla * counted["mla_mixer"])
                + (kda + mla) * 2 * d + dense * counted["dense_ffn"]
                + (kda + mla - dense) * (counted["router"] + (1 + experts)
                                         * counted["one_expert"]))

    counted["total"] = layers(9, 3, 32) + 2 * vocab * d + d
    counted["published"] = layers(20, 7, 256) + 2 * 163_840 * d + d
    if what == "published":
        assert abs(counted[what] - expected) < 0.005e9    # 49.12B
    else:
        assert counted[what] == expected
    assert kimi_linear.param_count(CONFIG) == 3_176_867_744 \
        == CONFIG["parameters"]
    assert [kimi_linear._mixer_params(CONFIG, kind)
            for kind in ("kda", "latent")] == [39_514_272, 29_114_880]


def test_the_programs_store_and_cache_are_the_issues_bytes():
    model = kimi_linear.model(CONFIG)
    assert model.num_params() == CONFIG["parameters"]
    c = model.config
    assert [c.layer_spec(i).mixer for i in range(12)] == [
        "kda", "kda", "kda", "latent"] * 3
    assert [c.layer_spec(i).ffn for i in range(12)] == \
        ["mlp"] + ["experts"] * 11
    assert len(c.prologue) == 1 and len(c.pattern) == 4
    assert [spec.mixer for spec in c.pattern] == [
        "kda", "kda", "latent", "kda"]
    assert (c.moe_experts, c.moe_held, c.moe_top_k,
            c.moe_shared_experts) == (256, (0, 32), 8, 1)
    assert (c.d_model, c.d_ff, c.expert_width) == (2304, 9216, 1024)
    assert (c.n_heads, c.head_dim, c.kv_latent, c.qk_shared,
            c.conv_kernel) == (32, 128, 512, 64, 4)
    assert (c.norm_eps, c.moe_route_scale, c.moe_router_input) == (
        1e-5, 2.446, "ffn")
    assert (c.norm_placement, c.moe_score, c.moe_expert_bias,
            c.mlp_act) == ("pre", "sigmoid", True, "swiglu")
    slot = kimi_linear.slot_bytes(CONFIG, 16_384)
    # nine KDA layers x (2,097,152 + 73,728) B, whatever the length
    assert slot["state"] == 9 * (2_097_152 + 73_728) == 19_537_920
    # three MLA layers x 16,384 rows as stored: 640 lanes of bfloat16
    assert slot["latent"] == 3 * 16_384 * 1280
    assert slot["full"] == slot["window"] == 0
    assert 64 * slot["state"] == 1_250_426_880
    # the least a round needs: 1,152 B a live position, 2 x both states
    assert kimi_linear.latent_attn_bytes(CONFIG, 1) == 1152
    assert kimi_linear.linear_attn_bytes(CONFIG, 1) == 2 * 2_170_880
    # one touched expert: 14,155,776 B; one row through it
    assert kimi_linear.moe_experts_bytes(CONFIG, 1, 0) == 14_155_776
    assert kimi_linear.moe_experts_bytes(CONFIG, 0, 1) == \
        2 * 2304 * 2 + 6 * 1024 * 2 + 2304 * 4
    # a token meets nine KDA and three MLA mixers, the dense layer, 11
    # routers, 11 shared experts, ONE held expert a layer on average
    # (8 x 32 / 256) and the head's rows
    assert kimi_linear.active_matmul_params(CONFIG) == (
        9 * 39_514_272 + 3 * 29_114_880 + 63_700_992
        + 11 * (2304 * 256 + 2 * 7_077_888) + 20_480 * 2304)
    flops = kimi_linear.train_flops_per_token(CONFIG, 4096)
    assert flops == 6.0 * kimi_linear.active_matmul_params(CONFIG) \
        + 3 * 12.0 * 32 * 160 * 4096 + 9 * 18.0 * 32 * 128 * 128


def test_the_configuration_is_the_published_row_and_its_cut():
    assert CONFIG["reduced"] == REDUCED
    assert CONFIG["omitted"] == []
    assert CONFIG["expert_parallel"] == {
        "ranks": 8, "rank": 0, "first_expert": 0, "held": 32}
    assert (CONFIG["num_experts"], CONFIG["num_router_experts"]) == (32, 256)
    assert CONFIG["published"]["num_experts"] == 256
    assert CONFIG["published"]["num_hidden_layers"] == 27
    assert CONFIG["published"]["vocab_size"] == 163_840 == 8 * 20_480
    assert "49.12B" in CONFIG["published"]["parameters"]
    assert "deployment" in CONFIG and "8-way" in CONFIG["deployment"]
    assert "2 stages x 8 ranks" in CONFIG["deployment"]
    # the published widths
    for key, value in {
            "hidden_size": 2304, "num_attention_heads": 32,
            "num_key_value_heads": 32, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 9216,
            "moe_intermediate_size": 1024, "num_experts_per_token": 8,
            "routed_scaling_factor": 2.446, "num_shared_experts": 1,
            "first_k_dense_replace": 1, "num_nextn_predict_layers": 0,
            "moe_router_activation_func": "sigmoid", "mla_use_nope": True,
            "moe_renormalize": True, "q_lora_rank": None}.items():
        assert CONFIG[key] == value, key
    linear = CONFIG["linear_attn_config"]
    assert (linear["head_dim"], linear["num_heads"],
            linear["short_conv_kernel_size"]) == (128, 32, 4)
    # the floors: three whole periods, 11 layers after the dense one, 32
    # experts, an eighth of the vocabulary
    assert linear["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11]
    assert linear["full_attn_layers"] == [4, 8, 12]
    for item in ("gate_rank", "why_gate_rank", "kda_shapes",
                 "kda_equations", "kda_decays", "mla", "router",
                 "expert_bias", "experts", "held_experts", "biases",
                 "weights"):
        assert item in CONFIG["assumed"], item
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isascii()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    published = row["config"]["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert linear[key] == published[key]
    assert linear["kda_layers"] == published["kda_layers"][:9]
    assert linear["full_attn_layers"] == published["full_attn_layers"][:3]


def test_the_cell_is_what_the_issue_asks(checkout):
    """Held on the benchmark as committed and on the widened copy."""
    benchmark = checkout.benchmark
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "agents_long_lanes"
    assert cell["config"] == NAME
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isascii()
    assert TRAFFIC["job"] == "serve"
    assert TRAFFIC["sessions"]["system_prompts"] == [1024, 4096, 8192,
                                                     14336]
    assert TRAFFIC["sessions"]["popularity"] == [1, 1, 1, 1]
    for key, (median, low, high) in {"user_tokens": (64, 16, 256),
                                     "output_tokens": (256, 32, 1024)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert (server["slots"], server["max_len"]) in ((64, 16_384),
                                                    (48, 16_384))
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] == 1 << 30
    assert (TRAFFIC["warmup"]["max_new"], TRAFFIC["trace_seconds"],
            TRAFFIC["drain_seconds"]) == (4, 6, 20)
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 4096, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["process"] == "poisson"
    # four fifths of the swept knee, unless that lies in a cliff
    fifths = 5 * rate["rate_per_s"] / rate["knee_per_s"]
    assert fifths == pytest.approx(round(fifths)) and 2 <= round(fifths) <= 4
    assert "sweep" in rate["why"]
    mine = {m["name"] for m in harness.metrics_of(benchmark, cell,
                                                  "per_layer")}
    assert set(NEW_METRICS) | JOINED <= mine
    for m in benchmark["per_layer"]:
        if m["name"] in set(NEW_METRICS) | JOINED:
            assert CELL in m["workloads"]
            assert os.path.exists(os.path.join(
                checkout.root, "perfbench", "metrics", m["name"] + ".json"))
        if m["name"] in NEW_METRICS:
            source, unit, layer = NEW_METRICS[m["name"]]
            assert m["moves"] == "itl_p95_ms" and m["layer"] == layer
            assert m["source"] == source and m["unit"] == unit
            assert m["workloads"][0] == CELL
    assert {m["name"] for m in harness.metrics_of(
        benchmark, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}
    # no reader is new: the four files name readers that were there
    wanted = {
        "serve.attn_latent_share_pct": {
            "reader": "scope_share_pct", "args": {"scope": "attn/latent"}},
        "serve.attn_latent_roofline_pct": {
            "reader": "scope_bytes_roofline_pct", "args": {
                "scopes": ["attn/latent"], "bytes": "latent_attn_bytes",
                "counters": {"positions_read": [
                    "serve.latent.positions_read"]}}},
        "serve.cache_latent_gb": {"reader": "gauge", "args": {
            "gauge": "serve.cache.latent_bytes", "scale": 1e-09}},
        "serve.latent_positions_read_pct": {
            "reader": "counter_ratio", "args": {
                "numerator": "serve.latent.positions_read",
                "denominator": "serve.latent.positions_cached",
                "scale": 100.0}}}
    for name, body in wanted.items():
        assert harness.load_json(os.path.join(
            checkout.root, "perfbench", "metrics", name + ".json")) == body
    # the shared rooflines read THIS family's counts
    for name, function in (("serve.attn_linear_roofline_pct",
                            "linear_attn_bytes"),
                           ("serve.moe_experts_roofline_pct",
                            "moe_experts_bytes")):
        assert harness.load_json(os.path.join(
            checkout.root, "perfbench", "metrics", name + ".json"))[
            "args"]["bytes"] == function


def test_request_zero_carries_the_8192_token_context():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries
    the 8,192-token context; all four contexts are used, in about equal
    shares; the longest request fits a lane; EVERY answer of the schedule
    ends inside the drain of 20 s even at a mean gap of 30 ms (the chip
    reads 24 to 26); the four contexts are four row buckets, and the
    traffic's five turn buckets are ONE program each (a kda model's
    smallest suffix bucket is 256)."""
    import numpy as np

    seconds = BENCHMARK["run_seconds"]
    shape = traffic_gen.serve_shape(TRAFFIC, seconds)
    prefix = TRAFFIC["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 8192
    counts = [int((shape["system"] == i).sum()) for i in range(4)]
    assert min(counts) > 0.22 * shape["n"]
    longest = 14_336 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]
    assert shape["n"] == round(TRAFFIC["arrivals"]["rate_per_s"] * seconds)
    ends = np.cumsum(shape["gaps"]) + 0.15 + 0.030 * shape["out_len"]
    assert ends.max() < seconds + TRAFFIC["drain_seconds"] - 3
    systems = [list(range(n)) for n in TRAFFIC["sessions"]["system_prompts"]]
    warm = traffic_gen.warmup_requests(TRAFFIC, 20_480, 1, systems)
    assert len(warm) == 4 * 5     # a request a context and turn bucket
    from parameter_server_distributed_tpu.models import serving

    model = kimi_linear.model(CONFIG)
    assert serving._suffix_floor(model) == 256
    assert {serving._bucket(len(r.prompt) - len(systems[r.system]),
                            256) for r in warm} == {256}
    # the replayed request (request 0's prompt + one token) too
    assert shape["user_len"][0] + 1 <= 256
    assert [serving._bucket(n) for n in
            TRAFFIC["sessions"]["system_prompts"]] == [1024, 4096, 8192,
                                                       14336]
    # the 1,024-token context is prefilled whole; the three longer ones
    # in chunks of 4,096, through one program
    assert [serving._prefills_whole(model, n) for n in
            TRAFFIC["sessions"]["system_prompts"]] == [True, False, False,
                                                       False]


def test_a_traced_rehearsal_reads_every_metric_a_cpu_can():
    """Six layers at the tiny size (the dense KDA layer, two KDA, an MLA
    layer, two KDA more), 4 lanes, a quarter of 16 experts held: the
    contexts come from the tree with their rows and their snapshots."""
    tiny = kimi_linear.tiny(CONFIG)
    assert kimi_linear.layer_kinds(tiny) == [
        "kda", "kda", "kda", "latent", "kda", "kda"]
    assert (tiny["num_experts"], tiny["num_router_experts"]) == (4, 16)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000047", "--seconds", "2",
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
    # (the reference's program holds no host callback: no line but these)
    assert not [l for l in lines if l.get("detail") == "selection_check"]
    metrics = line["metrics"]
    assert (JOINED | set(NEW_METRICS)) - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert set(absent["names"]) == FROM_THE_TRACE
    # 4 lanes: one MLA layer's 128 rows of 128 lanes; five KDA layers'
    # registers [3, 192] and matrices [4, 16, 16], float32
    assert metrics["serve.cache_latent_gb"]["value"] == pytest.approx(
        4 * 128 * 128 * 4 / 1e9)
    assert metrics["serve.cache_state_gb"]["value"] == pytest.approx(
        4 * 5 * (3 * 192 + 4 * 256) * 4 / 1e9)
    assert metrics["serve.programs_in_window"]["value"] == 0
    assert 0 < metrics["serve.latent_positions_read_pct"]["value"] < 100
    # a quarter of the experts held: about a quarter of the rows computed
    assert 10 < metrics["serve.moe_held_assignments_pct"]["value"] < 45
    assert 0 < metrics["serve.experts_touched_pct"]["value"] <= 100
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
