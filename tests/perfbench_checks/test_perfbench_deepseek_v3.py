"""The DeepSeek-V3 family and its cell: the family module held to the list the
benchmark calls, its counts against ISSUE 54's bytes table and against the
program's own store and cache, the configuration file against the published
row and its cut (depth, the leading dense layers, the experts held, the
vocabulary), the cell and its traffic against what the issue asks, and a
traced rehearsal of ``serve_docs_deepseek_v3_ep16`` held to every metric of
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
from perfbench.families import deepseek_v3  # noqa: E402

CELL = "serve_docs_deepseek_v3_ep16"
NAME = "deepseek-v3-5l-ep16"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", NAME + ".json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "docs_short_answers.json"))
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"]
NEW_METRICS = {
    "serve.attn_latent_q_share_pct": ("device_trace", "%"),
    "serve.attn_latent_expand_share_pct": ("device_trace", "%"),
    "serve.moe_ranks_per_token": ("program_counter", "ranks"),
    "serve.attn_latent_kernel_roofline_pct": ("device_trace", "%"),
    "serve.attn_latent_kernel_mxu_pct": ("device_trace", "%")}
# the accepted metrics of a mechanism whose lists this cell joins
JOINED = {
    "serve.attn_latent_share_pct", "serve.attn_latent_roofline_pct",
    "serve.cache_latent_gb", "serve.latent_positions_read_pct",
    "serve.moe_share_pct", "serve.moe_experts_roofline_pct",
    "serve.experts_touched_pct", "serve.expert_load_max_over_mean",
    "serve.moe_held_assignments_pct", "serve.moe_shared_share_pct",
    "serve.cache_update_share_pct", "serve.round_chained_pct",
    "serve.round_p50_ms", "serve.prefill_share_pct",
    "serve.programs_in_window", "serve.admit_p50_ms",
    "serve.admit_device_p50_ms", "serve.admit_lookup_p50_ms",
    "serve.admit_forward_p50_ms", "serve.admit_tree_p50_ms",
    "serve.admit_first_token_p50_ms", "serve.admit_first_token_p95_ms",
    "serve.admit_splice_p50_ms", "serve.slow_legs_in_window",
    "serve.slow_leg_s_in_window", "serve.slow_leg_cpu_s_in_window",
    "serve.slow_leg_gc_s_in_window",
    "serve.slow_leg_device_wait_s_in_window", "serve.prefix_hit_pct",
    "serve.occupancy_pct", "serve.slo_ok_pct", "serve.ttft_p50_ms",
    "serve.ttft_p95_ms", "gen.late_p95_ms", "device.idle_pct.serve",
    "device.peak_hbm_gb.serve"}
# the three of linear layers: this model has none
NOT_JOINED = {"serve.attn_linear_share_pct",
              "serve.attn_linear_roofline_pct", "serve.cache_state_gb"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = {
    "serve.attn_latent_share_pct", "serve.attn_latent_roofline_pct",
    "serve.attn_latent_q_share_pct", "serve.attn_latent_expand_share_pct",
    "serve.attn_latent_kernel_roofline_pct",
    "serve.attn_latent_kernel_mxu_pct", "serve.moe_share_pct",
    "serve.moe_experts_roofline_pct", "serve.moe_shared_share_pct",
    "serve.cache_update_share_pct", "device.idle_pct.serve",
    "device.peak_hbm_gb.serve"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is deepseek_v3
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(deepseek_v3, name)), name
        assert name in families.__doc__
    for name in ("moe_experts_bytes", "latent_attn_bytes",
                 "latent_attn_flops"):
        assert callable(getattr(deepseek_v3, name)), name
    assert not hasattr(deepseek_v3, "linear_attn_bytes")
    assert set(deepseek_v3.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert 0.005 < deepseek_v3.SELECTION_MARGIN < 0.2
    assert deepseek_v3.EXPERT_BIAS_STD == 0.005
    assert deepseek_v3.vocab_size(CONFIG) == 16_160
    assert deepseek_v3.max_context(CONFIG) == 163_840
    assert CONFIG["program_name"] == "deepseek-v3-5l-ep16-transformer-lm"


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "perfbench", "reference", "deepseek_v3.py")
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
    assert "Departures from the two public files" in source
    for word in ("pallas", "absorb(", "ragged_dot"):
        assert word not in source, word


@pytest.mark.parametrize("what,expected", [
    ("attention", 187_107_328), ("dense_layer", 583_483_392),
    ("expert_layer_held", 937_640_192), ("one_expert", 44_040_192),
    ("router_and_bias", 1_835_264), ("total", 4_565_721_088),
    ("expert_layer_whole", 11_507_000_000)])
def test_counts_against_the_issues_arithmetic(what, expected):
    d, vocab = 7168, 16_160
    counted = {
        "attention": (d * 1536 + 1536 + 1536 * 24_576 + d * 576 + 512
                      + 512 * 32_768 + 16_384 * d),
        "one_expert": 3 * d * 2048, "router_and_bias": d * 256 + 256}
    counted["dense_layer"] = counted["attention"] + 2 * d + 3 * d * 18_432

    def expert_layer(experts):
        return (counted["attention"] + 2 * d + counted["router_and_bias"]
                + (1 + experts) * counted["one_expert"])

    counted["expert_layer_held"] = expert_layer(16)
    counted["expert_layer_whole"] = expert_layer(256)
    counted["total"] = (counted["dense_layer"] + 4 * expert_layer(16)
                        + 2 * vocab * d + d)
    if what == "expert_layer_whole":
        assert abs(counted[what] - expected) < 0.01e9      # 11.5 B
    else:
        assert counted[what] == expected
    assert deepseek_v3.param_count(CONFIG) == 4_565_721_088 \
        == CONFIG["parameters"]
    assert deepseek_v3.layer_params(CONFIG, 0) == 583_483_392
    assert deepseek_v3.layer_params(CONFIG, 4) == 937_640_192


def test_the_programs_store_and_cache_are_the_issues_bytes():
    model = deepseek_v3.model(CONFIG)
    assert model.num_params() == CONFIG["parameters"]
    assert 2 * model.num_params() == 9_131_442_176          # 9.13 GB
    c = model.config
    assert [c.layer_spec(i).mixer for i in range(5)] == ["latent"] * 5
    assert [c.layer_spec(i).ffn for i in range(5)] == \
        ["mlp"] + ["experts"] * 4
    assert len(c.prologue) == 1 and len(c.pattern) == 1
    assert (c.moe_experts, c.moe_held, c.moe_top_k, c.moe_shared_experts,
            c.moe_groups, c.moe_groups_kept) == (256, (0, 16), 8, 1, 8, 4)
    assert (c.d_model, c.d_ff, c.expert_width) == (7168, 18_432, 2048)
    assert (c.n_heads, c.head_dim, c.kv_latent, c.qk_shared, c.q_latent,
            c.latent_rope) == (128, 128, 512, 64, 1536, True)
    assert (c.norm_eps, c.moe_route_scale, c.moe_router_input) == (
        1e-6, 2.5, "ffn")
    assert (c.norm_placement, c.moe_score, c.moe_expert_bias,
            c.mlp_act) == ("pre", "sigmoid", True, "swiglu")
    scaling = c.rope_scaling
    assert (scaling.factor, scaling.original_max, scaling.beta_fast,
            scaling.beta_slow, scaling.mscale, scaling.mscale_all_dim) == (
        40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert abs(192 ** -0.5 * scaling.softmax_gain - 0.135234) < 1e-6
    slot = deepseek_v3.slot_bytes(CONFIG, 16_384)
    # five layers x 16,384 rows as stored: 640 lanes of bfloat16
    assert slot["latent"] == 5 * 16_384 * 1280 == 16_384 * 6400
    assert slot["full"] == slot["window"] == slot["state"] == 0
    assert 32 * slot["latent"] == 3_355_443_200               # 3.36 GB
    # K and V of 128 heads would be 32,768 B a position a layer
    assert 128 * 128 * 2 == 32_768
    # the four resident rows in the prefix store
    assert sum(TRAFFIC["sessions"]["system_prompts"]) * 6400 == 183_500_800
    # the least a round needs: 1,152 B and 278,528 FLOP a live position
    assert deepseek_v3.latent_attn_bytes(CONFIG, 1) == 1152
    assert deepseek_v3.latent_attn_flops(CONFIG, 1) == 2 * 128 * (576 + 512)
    # one touched expert: 88,080,384 B; one row through it
    assert deepseek_v3.moe_experts_bytes(CONFIG, 1, 0) == 88_080_384
    assert deepseek_v3.moe_experts_bytes(CONFIG, 0, 1) == \
        2 * 7168 * 2 + 6 * 2048 * 2 + 7168 * 4
    # a token meets five attentions, the dense layer, 4 routers, 4 shared
    # experts, HALF a held expert a layer on average (8 x 16 / 256) and
    # the head's rows
    assert deepseek_v3.active_matmul_params(CONFIG) == (
        5 * 187_107_328 + 3 * 7168 * 18_432
        + 4 * (7168 * 256 + 1.5 * 44_040_192) + 16_160 * 7168)
    flops = deepseek_v3.train_flops_per_token(CONFIG, 4096)
    assert flops == 6.0 * deepseek_v3.active_matmul_params(CONFIG) \
        + 5 * 12.0 * 128 * 160 * 4096


def test_the_configuration_is_the_published_row_and_its_cut():
    assert CONFIG["reduced"] == REDUCED
    assert CONFIG["omitted"] == ["mtp"]
    assert CONFIG["expert_parallel"] == {
        "ranks": 16, "rank": 0, "first_expert": 0, "held": 16}
    assert (CONFIG["n_routed_experts"], CONFIG["num_router_experts"]) == (
        16, 256)
    assert CONFIG["published"]["n_routed_experts"] == 256
    assert CONFIG["published"]["num_hidden_layers"] == 61
    assert CONFIG["published"]["first_k_dense_replace"] == 3
    assert CONFIG["published"]["vocab_size"] == 129_280 == 8 * 16_160
    assert "12 stages x 16 ranks" in CONFIG["deployment"]
    assert "16-way" in CONFIG["deployment"]
    # the published widths
    for key, value in {
            "hidden_size": 7168, "num_attention_heads": 128,
            "num_key_value_heads": 128, "kv_lora_rank": 512,
            "q_lora_rank": 1536, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "intermediate_size": 18_432, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
            "routed_scaling_factor": 2.5, "n_shared_experts": 1,
            "num_nextn_predict_layers": 1, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "rms_norm_eps": 1e-6, "rope_theta": 10000}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the floors: a dense layer and four expert layers, 16 experts (8 or
    # more), an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] == 4
    for item in ("rotary_pairs", "yarn", "attention", "router",
                 "expert_bias", "experts", "held_experts", "one_dense_layer",
                 "biases", "weights"):
        assert item in CONFIG["assumed"], item
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == NAME)
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"perfbench/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"]
    for text in (entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_is_what_the_issue_asks(checkout):
    """Held on the benchmark as committed and on the widened copy."""
    benchmark = checkout.benchmark
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "docs_short_answers"
    assert cell["config"] == NAME
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isascii() \
        and cell["why"].isprintable()
    assert "attention 16x" in cell["why"]
    assert TRAFFIC["job"] == "serve"
    assert TRAFFIC["sessions"]["system_prompts"] == [2048, 4096, 8192,
                                                     14336]
    assert TRAFFIC["sessions"]["popularity"] == [2, 2, 1, 1]
    for key, (median, low, high) in {"user_tokens": (64, 16, 256),
                                     "output_tokens": (128, 16, 512)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert (server["slots"], server["max_len"]) in ((32, 16_384),
                                                    (24, 16_384))
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] == 536_870_912
    assert (TRAFFIC["warmup"]["max_new"], TRAFFIC["trace_seconds"],
            TRAFFIC["drain_seconds"]) == (4, 6, 20)
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 4096, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["process"] == "poisson"
    # four fifths of the swept knee, a whole rate
    assert rate["knee_per_s"] == int(rate["knee_per_s"])
    assert rate["rate_per_s"] == pytest.approx(0.8 * rate["knee_per_s"])
    assert "sweep" in rate["why"]
    mine = {m["name"] for m in harness.metrics_of(benchmark, cell,
                                                  "per_layer")}
    assert set(NEW_METRICS) | JOINED <= mine
    assert not NOT_JOINED & mine
    for m in benchmark["per_layer"]:
        if m["name"] in set(NEW_METRICS) | JOINED:
            assert CELL in m["workloads"]
            assert os.path.exists(os.path.join(
                checkout.root, "perfbench", "metrics", m["name"] + ".json"))
        if m["name"] in NEW_METRICS:
            source, unit = NEW_METRICS[m["name"]]
            assert m["moves"] == "itl_p95_ms"
            assert m["layer"] == "decode step"
            assert m["source"] == source and m["unit"] == unit
            assert m["workloads"][0] == CELL
    assert {m["name"] for m in harness.metrics_of(
        benchmark, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}
    kernel = {"scopes": ["attn/latent/cache/attn_kernel"],
              "counters": {"positions_read": [
                  "serve.latent.positions_read"]}}
    wanted = {
        "serve.attn_latent_q_share_pct": {
            "reader": "scope_share_pct", "args": {"scope": "attn/latent/q"}},
        # (the expansion lies inside the blockwise loop's ``while``: its
        # path is attn/latent/while/body/.../expand, and ``expand`` is no
        # other block's name)
        "serve.attn_latent_expand_share_pct": {
            "reader": "scope_share_pct", "args": {"scope": "expand"}},
        "serve.moe_ranks_per_token": {
            "reader": "counter_ratio", "args": {
                "numerator": "serve.moe.rank_places",
                "denominator": "serve.moe.tokens_routed"}},
        "serve.attn_latent_kernel_roofline_pct": {
            "reader": "scope_bytes_roofline_pct",
            "args": dict(kernel, bytes="latent_attn_bytes")},
        "serve.attn_latent_kernel_mxu_pct": {
            "reader": "scope_flops_roofline_pct",
            "args": dict(kernel, flops="latent_attn_flops")}}
    for name, body in wanted.items():
        assert harness.load_json(os.path.join(
            checkout.root, "perfbench", "metrics", name + ".json")) == body
    # the shared rooflines read THIS family's counts
    for name, function in (("serve.attn_latent_roofline_pct",
                            "latent_attn_bytes"),
                           ("serve.moe_experts_roofline_pct",
                            "moe_experts_bytes")):
        assert harness.load_json(os.path.join(
            checkout.root, "perfbench", "metrics", name + ".json"))[
            "args"]["bytes"] == function


def test_the_new_reader_reads_a_share_of_the_matrix_peak(monkeypatch):
    """``scope_flops_roofline_pct`` on a made-up trace: the family's count
    over the published peak over the scope's time; nothing where the trace,
    the counter, the configuration or the family's function is missing."""
    import jax

    from perfbench.readers import scope_flops_roofline_pct as reader

    class Chip:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    args = dict(scopes=["attn/latent/cache/attn_kernel"],
                flops="latent_attn_flops",
                counters={"positions_read": ["serve.latent.positions_read"]})
    path = "jit(run)/cache_attn/attn/latent/cache/attn_kernel/pallas_call"

    def observed(moved=1e9, **trace):
        return {
            "trace": dict({"by_scope": {path: 0.5, "jit(run)/head": 1.0},
                           "busy_s": 2.0, "window_s": 5.0}, **trace),
            "window_s": 50.0, "configuration": CONFIG,
            "registry_before": {"counters": {
                "serve.latent.positions_read": 0.0}},
            "registry_after": {"counters": {
                "serve.latent.positions_read": moved}}}

    # 1e9 positions x 278,528 FLOP, a tenth of the window traced, over
    # 197 TFLOP/s, against half a second under the scope
    want = 100.0 * (1e9 * 278_528 * 0.1 / 197e12) / 0.5
    assert reader.read(observed(), **args) == pytest.approx(want)
    assert reader.read({"trace": None}, **args) is None
    assert reader.read(observed(by_scope={}), **args) is None
    assert reader.read(observed(by_scope={"jit(run)/head": 1.0}),
                       **args) is None
    assert reader.read(dict(observed(), configuration=None), **args) is None
    assert reader.read(observed(), **dict(args, flops="no_such")) is None
    missing = observed()
    missing["registry_before"] = missing["registry_after"] = {"counters": {}}
    assert reader.read(missing, **args) is None


def test_request_zero_carries_the_8192_token_document():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries
    the 8,192-token document; the four buckets are used within 15% of
    2:2:1:1; the longest request fits a lane; EVERY answer of the schedule
    ends inside the drain of 20 s even at a mean gap of 30 ms;
    ``shape_seed`` is the FIRST from 20,540,000 on of which all that
    holds; the four documents are four row buckets, and the traffic's five
    turn buckets are ONE program each (a latent model's smallest suffix
    bucket is 256)."""
    import numpy as np

    seconds = BENCHMARK["run_seconds"]

    def fits(seed):
        shape = traffic_gen.serve_shape(dict(TRAFFIC, shape_seed=seed),
                                        seconds)
        prompts = TRAFFIC["sessions"]["system_prompts"]
        if prompts[shape["system"][0]] != 8192:
            return False
        counts = [int((shape["system"] == i).sum()) for i in range(4)]
        shares = np.asarray(counts) / shape["n"] * 6 / np.asarray([2, 2, 1, 1])
        if np.max(np.abs(shares - 1)) > 0.15:
            return False
        ends = np.cumsum(shape["gaps"]) + 0.15 + 0.030 * shape["out_len"]
        return ends.max() < seconds + TRAFFIC["drain_seconds"] - 3

    assert TRAFFIC["shape_seed"] >= 20_540_000
    assert fits(TRAFFIC["shape_seed"])
    assert not any(fits(seed) for seed in range(20_540_000,
                                                TRAFFIC["shape_seed"]))
    shape = traffic_gen.serve_shape(TRAFFIC, seconds)
    longest = 14_336 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]
    assert shape["n"] == round(TRAFFIC["arrivals"]["rate_per_s"] * seconds)
    systems = [list(range(n)) for n in TRAFFIC["sessions"]["system_prompts"]]
    warm = traffic_gen.warmup_requests(TRAFFIC, 16_160, 1, systems)
    assert len(warm) == 4 * 5     # a request a document and turn bucket
    from parameter_server_distributed_tpu.models import serving

    model = deepseek_v3.model(CONFIG)
    assert serving._suffix_floor(model) == 256
    assert {serving._bucket(len(r.prompt) - len(systems[r.system]),
                            256) for r in warm} == {256}
    # the replayed request (request 0's prompt + one token) too
    assert shape["user_len"][0] + 1 <= 256
    assert [serving._bucket(n) for n in
            TRAFFIC["sessions"]["system_prompts"]] == [2048, 4096, 8192,
                                                       14336]
    # the 2,048-token document is prefilled whole; the three longer ones
    # in chunks of 4,096, through one program
    assert [serving._prefills_whole(model, n) for n in
            TRAFFIC["sessions"]["system_prompts"]] == [True, False, False,
                                                       False]


def test_a_traced_rehearsal_reads_every_metric_a_cpu_can():
    """Three layers at the tiny size (the dense layer and two expert
    layers), 4 lanes, an eighth of 16 experts held under 2 of 4 groups:
    the documents come from the tree with their rotated rows."""
    tiny = deepseek_v3.tiny(CONFIG)
    assert (tiny["n_routed_experts"], tiny["num_router_experts"],
            tiny["n_group"], tiny["topk_group"]) == (2, 16, 4, 2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000054", "--seconds", "2",
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
    metrics = line["metrics"]
    assert (JOINED | set(NEW_METRICS)) - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert set(absent["names"]) == FROM_THE_TRACE
    # 4 lanes: three layers' 128 rows of 128 lanes, float32
    assert metrics["serve.cache_latent_gb"]["value"] == pytest.approx(
        4 * 3 * 128 * 128 * 4 / 1e9)
    assert "serve.cache_state_gb" not in metrics
    assert metrics["serve.programs_in_window"]["value"] == 0
    assert 0 < metrics["serve.latent_positions_read_pct"]["value"] < 100
    # an eighth of the experts held (half of one of four groups): the rows
    # computed are well under half of those routed
    assert 2 < metrics["serve.moe_held_assignments_pct"]["value"] < 45
    # 3 choices a token under 2 of 4 groups of 2 ranks: 1 to 3 ranks
    assert 1 <= metrics["serve.moe_ranks_per_token"]["value"] <= 3
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
