"""The MiniCPM-SALA family and its cell: the family module held to the list
the benchmark calls, its counts against ISSUE 32's arithmetic, the
configuration file against the published row, the cell and its traffic
against what the issue asks, and a traced rehearsal of
``serve_longdocs_chat_minicpm_sala`` held to every new per-layer metric a
CPU can read.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import families, harness, traffic_gen  # noqa: E402
from perfbench.families import minicpm_sala  # noqa: E402

CELL = "serve_longdocs_chat_minicpm_sala"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", "minicpm-sala-12l.json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "longdocs_and_chat.json"))
NEW_METRICS = {
    "serve.attn_sparse_share_pct", "serve.attn_sparse_select_share_pct",
    "serve.attn_linear_share_pct", "serve.cache_state_gb",
    "serve.sparse_positions_read_pct", "serve.attn_sparse_roofline_pct",
    "serve.attn_linear_roofline_pct"}
# what a CPU cannot read: the device's trace has no device plane there
FROM_THE_TRACE = NEW_METRICS - {"serve.cache_state_gb",
                                "serve.sparse_positions_read_pct"}


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is minicpm_sala
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(minicpm_sala, name)), name
        assert name in families.__doc__
    assert set(minicpm_sala.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert 0.0024 < minicpm_sala.SELECTION_MARGIN < 0.0127
    assert minicpm_sala.vocab_size(CONFIG) == 73_448
    assert minicpm_sala.max_context(CONFIG) == 524_288


@pytest.mark.parametrize("what,expected", [
    ("mlp", 201_326_592), ("sparse_attention", 52_428_800),
    ("linear_attention", 83_886_080), ("sparse_layer", 253_755_392),
    ("linear_layer", 285_212_672), ("embedding_and_head", 601_686_016),
    ("matrices", 3_929_866_240)])
def test_counts_against_the_issues_arithmetic(what, expected):
    d, wide, kv = 4096, 16_384, 2 * 128
    counted = {
        "mlp": 3 * d * wide,
        "sparse_attention": 3 * d * d + 2 * d * kv,
        "linear_attention": 5 * d * d,
        "embedding_and_head": 2 * 73_448 * d}
    counted["sparse_layer"] = counted["mlp"] + counted["sparse_attention"]
    counted["linear_layer"] = counted["mlp"] + counted["linear_attention"]
    counted["matrices"] = (3 * counted["sparse_layer"]
                           + 9 * counted["linear_layer"]
                           + counted["embedding_and_head"])
    assert counted[what] == expected
    gains = 12 * (2 * d + 2 * 128) + 9 * 128 + d
    assert minicpm_sala.param_count(CONFIG) == 3_929_866_240 + gains \
        == CONFIG["parameters"]
    assert minicpm_sala.layer_params(CONFIG, "minicpm4") == \
        253_755_392 + 2 * d + 256
    assert minicpm_sala.layer_params(CONFIG, "lightning-attn") == \
        285_212_672 + 2 * d + 256 + 128


def test_the_programs_store_and_cache_are_the_issues_bytes():
    model = minicpm_sala.model(CONFIG)
    assert model.num_params() == CONFIG["parameters"]
    slot = minicpm_sala.slot_bytes(CONFIG, 65_536)
    # a position: 1,024 B of K/V + 32 B of compressed keys a sparse layer
    assert slot["full"] == 3 * 65_536 * (1024 + 32) == 207_618_048
    assert slot["state"] == 9 * 32 * 128 * 128 * 4 == 18_874_368
    assert minicpm_sala.sparse_attn_bytes(CONFIG, 6208, 3838) == \
        6208 * 1024 + 3838 * 512
    assert minicpm_sala.linear_attn_bytes(CONFIG, 16 * 9) == \
        16 * 9 * 2 * 2_097_152
    spec = minicpm_sala.sparse_spec(CONFIG)
    assert (spec.n_selected, spec.n_gathered) == (97, 128)


def test_the_configuration_is_the_published_row_cut_in_depth_only():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiniCPM-SALA")
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "mixer_types"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 12
    assert CONFIG["mixer_types"] == row["config"]["mixer_types"][9:21]
    assert CONFIG["mixer_types"].count("minicpm4") == 3
    for item in ("sparse_config", "selected_blocks", "selection_softmax",
                 "dense_len", "decay", "qkv_activation", "gates", "state",
                 "weights"):
        assert item in CONFIG["assumed"], item
    assert CONFIG["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "init_blocks": 1, "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert "deployment" in CONFIG
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "minicpm-sala-12l")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_cell_is_what_the_issue_asks():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdocs_and_chat"
    assert TRAFFIC["job"] == "serve"
    assert TRAFFIC["sessions"]["system_prompts"] == [2048, 12288, 28672,
                                                     61440]
    assert TRAFFIC["sessions"]["popularity"] == [1, 1, 1, 1]
    for key, (median, low, high) in {"user_tokens": (64, 8, 512),
                                     "output_tokens": (128, 8, 512)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert server["slots"] in (12, 16) and server["max_len"] == 65_536
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] == 1 << 30
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 12_288, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["rate_per_s"] == pytest.approx(0.8 * rate["knee_per_s"])
    mine = {m["name"] for m in harness.metrics_of(BENCHMARK, cell,
                                                  "per_layer")}
    assert NEW_METRICS <= mine
    for m in BENCHMARK["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
            assert os.path.exists(os.path.join(
                ROOT, "perfbench", "metrics", m["name"] + ".json"))
    assert {m["name"] for m in harness.metrics_of(
        BENCHMARK, cell, "end_to_end")} == {"itl_p95_ms", "setup_s"}
    assert len(BENCHMARK["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1


def test_request_zero_carries_a_document_past_dense_len():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries
    the 12,288-token document (192 blocks against 97 selected), the
    longest whose float32 reference logits (3.6 GB) fit the chip beside
    7.9 GB of weights; every prefix is used, in about equal shares."""
    shape = traffic_gen.serve_shape(TRAFFIC, BENCHMARK["run_seconds"])
    prefix = TRAFFIC["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 12_288 > CONFIG["sparse_config"]["dense_len"]
    counts = [int((shape["system"] == i).sum()) for i in range(4)]
    assert min(counts) > 0.15 * shape["n"]
    longest = 61_440 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]


def test_a_traced_rehearsal_reads_every_new_metric_a_cpu_can():
    """Four layers of both kinds and a ``dense_len`` (32) inside the
    rehearsal's prompts (20..32 + a turn + an answer): slots select, the
    prefix and its snapshot come from the tree."""
    tiny = minicpm_sala.tiny(CONFIG)
    assert tiny["mixer_types"].count("minicpm4") == 2
    assert tiny["sparse_config"]["dense_len"] == 32
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
    assert selection and all(
        max(l["queries_with_a_flip_pct"]) == 0.0 for l in selection)
    metrics = line["metrics"]
    assert NEW_METRICS - FROM_THE_TRACE <= set(metrics)
    absent = next(l for l in lines if l.get("detail") == "per_layer_absent")
    assert FROM_THE_TRACE <= set(absent["names"])
    # 4 slots, 2 linear layers of 4 heads x 16 x 16 float32
    assert metrics["serve.cache_state_gb"]["value"] == pytest.approx(
        4 * 2 * 4 * 16 * 16 * 4 / 1e9)
    assert 0 < metrics["serve.sparse_positions_read_pct"]["value"] <= 100
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
