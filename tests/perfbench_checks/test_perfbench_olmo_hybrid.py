"""The Olmo Hybrid family and its cell: the family module held to the list the
benchmark calls, its counts against ISSUE 50's table and the program's own
store and cache, the configuration file against the catalog's row and its cut
(depth alone), the cell and its traffic against what the issue asks, and a
traced rehearsal of ``serve_reasoning_olmo_hybrid`` held to every metric of
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
from perfbench.families import olmo_hybrid  # noqa: E402

CELL = "serve_reasoning_olmo_hybrid"
NAME = "olmo-hybrid-7b-16l"
CONFIG = harness.load_json(os.path.join(
    ROOT, "perfbench", "configs", NAME + ".json"))
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
TRAFFIC = harness.load_json(os.path.join(
    ROOT, "perfbench", "traffic", "reasoning_wide_kv.json"))
REDUCED = ["num_hidden_layers", "layer_types"]
NEW_METRICS = {
    "serve.full_positions_live_pct": ("program_counter", "%",
                                      "admission and prefix cache"),
    "serve.attn_full_roofline_pct": ("device_trace", "%", "decode step"),
    "serve.mlp_share_pct": ("device_trace", "%", "decode step")}
# the accepted metrics of a mechanism whose lists this cell joins
JOINED = {
    "serve.attn_linear_share_pct", "serve.attn_linear_roofline_pct",
    "serve.cache_state_gb", "serve.attn_full_share_pct",
    "serve.cache_full_gb", "serve.cache_update_share_pct",
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
    "serve.attn_full_roofline_pct", "serve.mlp_share_pct",
    "serve.attn_full_share_pct", "serve.attn_linear_share_pct",
    "serve.attn_linear_roofline_pct", "serve.cache_update_share_pct",
    "device.idle_pct.serve", "device.peak_hbm_gb.serve"}


def resident_evictions(traffic: dict, seconds: float) -> tuple[int, int]:
    """The cell's schedule through the server's own ``PrefixTree`` with
    rows of the bytes the program's rows have, in the order ``jobs/serve.py``
    submits (the resident contexts, the warm-up, the window's requests, the
    replayed request 0), by ``DecodeServer._admit``'s steps: (requests that
    found no resident context to extend, evictions).  An admission extends
    the deepest node with a snapshot where it stands, else prefills the
    prompt whole; what it shares with a path that holds no snapshot there
    is forwarded first, as a prompt of its own; every row joins the tree
    and the byte-budget eviction runs.  What a lane's timing changes is
    nothing here: admissions come in arrival order."""
    from parameter_server_distributed_tpu.models import serving
    from parameter_server_distributed_tpu.models.prefix_tree import (
        PrefixTree, RowRef)

    position = olmo_hybrid.slot_bytes(CONFIG, 1)["full"]
    snapshot = olmo_hybrid.slot_bytes(CONFIG, 1)["state"]
    floor = serving._suffix_floor(olmo_hybrid.model(CONFIG))
    tree = PrefixTree(traffic["server"]["prefix_cache_bytes"],
                      snapshots=True)
    lengths = traffic["sessions"]["system_prompts"]
    systems = [tuple(1_000_000 * (i + 1) + j for j in range(n))
               for i, n in enumerate(lengths)]
    missed = 0

    def forward(prompt: tuple) -> None:
        node, matched, _ = tree.lookup(prompt)
        if 0 < matched < len(prompt):
            tree.use(node)
            positions = serving._bucket(matched) + serving._bucket(
                len(prompt) - matched, floor)
        else:
            positions = serving._bucket(len(prompt))
        tree.insert(prompt, object(), RowRef(
            None, positions * position + snapshot, state_at=len(prompt)))
        tree.evict_over_budget()

    def admit(prompt: tuple) -> None:
        nonlocal missed
        matched, shared = tree.lookup(prompt)[1], tree.shared(prompt)
        missed += matched == 0 and len(prompt) > max(lengths[:2])
        if matched + floor <= shared < len(prompt):
            forward(prompt[:shared])
        forward(prompt)

    for tokens in systems:
        admit(tokens)
    fresh = iter(range(1, 1_000_000))
    warm = traffic_gen.warmup_requests(traffic, 100_352, 1,
                                       [list(t) for t in systems])
    for request in warm:
        admit(systems[request.system] + tuple(
            -next(fresh) for _ in range(len(request.prompt)
                                        - lengths[request.system])))
    shape = traffic_gen.serve_shape(traffic, seconds)
    turns = [systems[int(i)] + tuple(-next(fresh) for _ in range(int(n)))
             for i, n in zip(shape["system"], shape["user_len"])]
    for prompt in turns:
        admit(prompt)
    admit(turns[0] + (-next(fresh),))
    return missed, tree.evictions


def test_the_family_answers_the_list_and_is_found_by_the_key():
    assert families.of(CONFIG) is olmo_hybrid
    for name in ("model", "make_weights", "reference_weights",
                 "reference_forward", "reference_loss",
                 "train_flops_per_token", "vocab_size", "max_context",
                 "tiny"):
        assert callable(getattr(olmo_hybrid, name)), name
        assert name in families.__doc__
    for name in ("linear_attn_bytes", "full_attn_bytes"):
        assert callable(getattr(olmo_hybrid, name)), name
    assert set(olmo_hybrid.TOLERANCES) == {
        "logits_rms", "logits_max", "near_tie", "gradient", "loss"}
    assert 0.001 < olmo_hybrid.STATE_TOLERANCE < 0.05
    assert olmo_hybrid.vocab_size(CONFIG) == 100_352
    assert olmo_hybrid.max_context(CONFIG) == 65_536


def test_the_reference_shares_no_code_with_the_program():
    path = os.path.join(ROOT, "perfbench", "reference", "olmo_hybrid.py")
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
    assert "Departures from the two public files" in source


@pytest.mark.parametrize("what,expected", [
    ("linear_mixer", 88_750_332), ("swiglu", 126_812_160),
    ("linear_layer", 215_570_172), ("full_layer", 185_809_920),
    ("total", 4_100_788_944), ("published", 7_430_870_688)])
def test_counts_against_the_issues_table(what, expected):
    d, keys, values, vocab = 3840, 2880, 5760, 100_352
    counted = {
        "linear_mixer": (2 * d * keys + 3 * d * values + 2 * d * 30
                         + 4 * 11_520 + 30 + 30 + 192),
        "swiglu": 3 * d * 11_008}
    counted["linear_layer"] = (counted["linear_mixer"] + counted["swiglu"]
                               + 2 * d)
    counted["full_layer"] = 4 * d * d + 2 * d + counted["swiglu"] + 2 * d
    counted["total"] = (12 * counted["linear_layer"]
                        + 4 * counted["full_layer"] + 2 * vocab * d + d)
    counted["published"] = (24 * counted["linear_layer"]
                            + 8 * counted["full_layer"] + 2 * vocab * d + d)
    assert counted[what] == expected
    assert olmo_hybrid.param_count(CONFIG) == 4_100_788_944 \
        == CONFIG["parameters"]
    assert [olmo_hybrid._mixer_params(CONFIG, kind) for kind in (
        "linear_attention", "full_attention")] == [88_750_332, 58_990_080]


def test_the_programs_store_and_cache_are_the_issues_bytes():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_distributed_tpu.models import generation

    model = olmo_hybrid.model(CONFIG)
    assert model.num_params() == CONFIG["parameters"]
    c = model.config
    assert [c.layer_spec(i).mixer for i in range(16)] == [
        "gdn", "gdn", "gdn", "softmax"] * 4
    assert {c.layer_spec(i).ffn for i in range(16)} == {"mlp"}
    assert c.prologue == () and len(c.pattern) == 4
    full = c.pattern[3]
    assert (full.rope, full.qk_norm, full.window) == (False, "all", 0)
    assert (c.d_model, c.d_ff, c.vocab, c.n_heads, c.kv_heads, c.head_dim,
            c.delta_dims, c.conv_kernel, c.delta_neg_eigval) == (
        3840, 11_008, 100_352, 30, 30, 128, (96, 192), 4, True)
    assert (c.norm_placement, c.norm_eps, c.mlp_act, c.bias) == (
        "post", 1e-6, "swiglu", False)
    assert c.dtype == jnp.bfloat16
    slots, max_len = (TRAFFIC["server"]["slots"],
                      TRAFFIC["server"]["max_len"])
    cache = jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len))

    def held(parts):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(parts))

    kinds = {"full": held(cache.k + cache.v), "state": held(cache.state),
             "window": held(cache.wk + cache.wv)}
    slot = olmo_hybrid.slot_bytes(CONFIG, max_len)
    # four full layers x 15,360 B a position: 61,440 B a position a lane
    assert slot["full"] == 4 * max_len * 15_360 == max_len * 61_440
    assert kinds["full"] == slots * slot["full"]
    # twelve linear layers x (2,211,840 + 69,120) B, whatever the length
    assert slot["state"] == 12 * (2_211_840 + 69_120) == 27_371_520
    assert kinds["state"] == slots * slot["state"]
    assert slot["window"] == slot["latent"] == 0 == kinds["window"]
    if (slots, max_len) == (12, 4096):
        assert kinds["full"] == 3_019_898_880
        assert kinds["state"] == 328_458_240
    # the matrix is stored by head [30, 96, 192]: 2,949,120 B on the device
    assert [(shape, str(dtype)) for shape, dtype in
            generation.state_shape(model)[0]] == [
        ((3, 11_520), "<class 'jax.numpy.bfloat16'>"),
        ((30, 96, 192), "<class 'jax.numpy.float32'>")]
    assert "2,949,120" in CONFIG["assumed"]["matrix_state"]
    # the least a round needs: two states read and written as stored; a
    # live position's K and V
    assert olmo_hybrid.linear_attn_bytes(CONFIG, 1) == 2 * (
        30 * 96 * 256 * 4 + 69_120)
    assert olmo_hybrid.full_attn_bytes(CONFIG, 1) == 15_360
    # a row of the prefix store: 61,440 B a position + the snapshot
    assert 2048 * 61_440 + slot["state"] == 153_200_640
    assert olmo_hybrid.active_matmul_params(CONFIG) == (
        12 * (88_750_332 - 4 * 11_520 - 60 - 192) + 4 * 4 * 3840 * 3840
        + 16 * 126_812_160 + 100_352 * 3840)
    flops = olmo_hybrid.train_flops_per_token(CONFIG, 2048)
    assert flops == 6.0 * olmo_hybrid.active_matmul_params(CONFIG) \
        + 4 * 12.0 * 3840 * 2048 + 12 * 18.0 * 30 * 96 * 192


def test_the_configuration_is_the_catalogs_row_and_its_cut():
    assert CONFIG["reduced"] == REDUCED
    assert CONFIG["omitted"] == []
    assert CONFIG["published"]["num_hidden_layers"] == 32
    assert "7,430,870,688" in CONFIG["published"]["parameters"]
    assert "two-stage pipeline" in CONFIG["deployment"]
    assert "14.86 GB" in CONFIG["deployment"]
    assert "doubles" in CONFIG["deployment"]
    for key, value in {
            "hidden_size": 3840, "num_attention_heads": 30,
            "num_key_value_heads": 30, "intermediate_size": 11_008,
            "linear_num_key_heads": 30, "linear_num_value_heads": 30,
            "linear_key_head_dim": 96, "linear_value_head_dim": 192,
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "vocab_size": 100_352, "tie_word_embeddings": False,
            "rms_norm_eps": 1e-6, "attention_bias": False,
            "max_position_embeddings": 65_536,
            "rope_parameters": {"rope_theta": None}}.items():
        assert CONFIG[key] == value, key
    assert CONFIG["num_hidden_layers"] == 16
    assert CONFIG["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 4
    for item in ("rotary", "block", "qk_norm", "beta", "gate",
                 "linear_shapes", "decays", "biases", "weights",
                 "matrix_state"):
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
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:16]


def test_the_cell_is_what_the_issue_asks(checkout):
    """Held on the benchmark as committed and on the widened copy."""
    benchmark = checkout.benchmark
    cell = next(w for w in benchmark["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reasoning_wide_kv"
    assert cell["config"] == NAME
    assert 1 <= len(cell["why"]) <= 200 and cell["why"].isascii() \
        and cell["why"].isprintable()
    assert TRAFFIC["job"] == "serve"
    # the issue's two contexts, 1:1; two prompts of 16 tokens beside them
    # carry no request (``jobs/serve.py``'s ``shrink`` names four system
    # prompts for every cell's rehearsal, and ``popularity`` has to keep
    # that length)
    assert TRAFFIC["sessions"]["system_prompts"] == [512, 2048, 16, 16]
    assert TRAFFIC["sessions"]["popularity"] == [1, 1, 0, 0]
    for key, (median, low, high) in {"user_tokens": (64, 16, 256),
                                     "output_tokens": (256, 32, 1024)}.items():
        assert (TRAFFIC[key]["median"], TRAFFIC[key]["sigma"],
                TRAFFIC[key]["min"], TRAFFIC[key]["max"]) == (
            median, 0.8, low, high)
    server = TRAFFIC["server"]
    assert (server["slots"], server["max_len"]) in ((12, 4096), (10, 4096))
    assert str(server["slots"]) + " slots" in cell["why"]
    assert server["prompt_cache"] == 8
    assert server["prefix_cache_bytes"] == 536_870_912
    assert (TRAFFIC["warmup"]["max_new"], TRAFFIC["trace_seconds"],
            TRAFFIC["drain_seconds"]) == (4, 6, 20)
    check = TRAFFIC["check"]
    assert (check["sequences"], check["tokens"],
            check["served_tokens"]) == (1, 2048, 16)
    rate = TRAFFIC["arrivals"]
    assert rate["process"] == "poisson"
    # four fifths of the swept knee, a whole or half rate
    assert rate["rate_per_s"] == pytest.approx(0.8 * rate["knee_per_s"])
    assert (2 * rate["knee_per_s"]) % 1 == 0
    assert "sweep" in rate["why"] and "sweep" in TRAFFIC["slo"]["why"]
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
    # no reader is new: the three files name readers that were there
    wanted = {
        "serve.full_positions_live_pct": {
            "reader": "counter_ratio", "args": {
                "numerator": "serve.full.positions_live",
                "denominator": "serve.full.positions_cached",
                "scale": 100.0}},
        "serve.attn_full_roofline_pct": {
            "reader": "scope_bytes_roofline_pct", "args": {
                "scopes": ["attn/full"], "bytes": "full_attn_bytes",
                "counters": {"positions_live": [
                    "serve.full.positions_live"]}}},
        "serve.mlp_share_pct": {"reader": "scope_share_pct",
                                "args": {"scope": "mlp"}}}
    for name, body in wanted.items():
        assert harness.load_json(os.path.join(
            checkout.root, "perfbench", "metrics", name + ".json")) == body
    # the shared roofline reads THIS family's count
    assert harness.load_json(os.path.join(
        checkout.root, "perfbench", "metrics",
        "serve.attn_linear_roofline_pct.json"))["args"]["bytes"] \
        == "linear_attn_bytes"


def test_request_zero_carries_the_2048_token_context():
    """``jobs/serve.py`` replays request 0 for ``served_ok``: it carries the
    2,048-token context; both contexts take 45 to 55% of the requests; the
    longest request fits a lane; EVERY answer of the schedule ends inside
    the drain of 20 s even at a mean gap of 19 ms; the two contexts are two
    row buckets, each prefilled whole, and the traffic's five turn buckets
    are ONE program each (a delta-rule model's smallest suffix bucket is
    256)."""
    import numpy as np

    seconds = BENCHMARK["run_seconds"]
    shape = traffic_gen.serve_shape(TRAFFIC, seconds)
    prefix = TRAFFIC["sessions"]["system_prompts"][shape["system"][0]]
    assert prefix == 2048 and shape["system"][0] == 1
    assert set(shape["system"].tolist()) == {0, 1}
    share = float((shape["system"] == 1).mean())
    assert 0.45 <= share <= 0.55
    longest = 2048 + TRAFFIC["user_tokens"]["max"] \
        + TRAFFIC["output_tokens"]["max"]
    assert longest <= TRAFFIC["server"]["max_len"]
    assert shape["n"] == round(TRAFFIC["arrivals"]["rate_per_s"] * seconds)
    ends = np.cumsum(shape["gaps"]) + 0.3 + 0.019 * shape["out_len"]
    assert ends.max() < seconds + TRAFFIC["drain_seconds"] - 1
    systems = [list(range(n)) for n in TRAFFIC["sessions"]["system_prompts"]]
    warm = traffic_gen.warmup_requests(TRAFFIC, 100_352, 1, systems)
    # a request a context and turn bucket (and the 16-token filler's five)
    assert len(warm) == 3 * 5
    assert [r.system for r in warm] == [0] * 5 + [1] * 5 + [2] * 5
    from parameter_server_distributed_tpu.models import serving

    model = olmo_hybrid.model(CONFIG)
    assert serving._builds_few(model)
    assert serving._suffix_floor(model) == 256
    assert {serving._bucket(len(r.prompt) - len(systems[r.system]),
                            256) for r in warm} == {256}
    # the replayed request (request 0's prompt + one token) too
    assert shape["user_len"][0] + 1 <= 256
    assert [serving._bucket(n) for n in
            TRAFFIC["sessions"]["system_prompts"]] == [512, 2048, 16, 16]
    assert all(serving._prefills_whole(model, n) for n in
               TRAFFIC["sessions"]["system_prompts"])


def meets_the_issues_criteria(shape_seed: int, seconds: float) -> bool:
    """ISSUE 50's three: request 0 carries the 2,048-token context (and its
    replay, one token longer, fits the block of 256), both contexts take 45
    to 55% of the requests, every answer ends inside the drain even at a
    mean gap of 19 ms."""
    import numpy as np

    shape = traffic_gen.serve_shape(dict(TRAFFIC, shape_seed=shape_seed),
                                    seconds)
    ends = np.cumsum(shape["gaps"]) + 0.3 + 0.019 * shape["out_len"]
    return bool(
        shape["system"][0] == 1 and shape["user_len"][0] + 1 <= 256
        and 0.45 <= float((shape["system"] == 1).mean()) <= 0.55
        and ends.max() < seconds + TRAFFIC["drain_seconds"] - 1)


def test_the_shape_seed_is_the_first_that_meets_the_issues_criteria():
    """And nothing else: no schedule is left out for what the store does
    with it (the first draft's seed was; REVIEW of PR 50)."""
    seconds = BENCHMARK["run_seconds"]
    assert TRAFFIC["shape_seed"] == next(
        seed for seed in range(20_500_000, 20_500_100)
        if meets_the_issues_criteria(seed, seconds))


@pytest.mark.parametrize("shape_seed", range(20_500_000, 20_500_016))
def test_no_schedule_loses_a_resident_context(shape_seed):
    """The store's budget binds (512 MiB for residents of 59 + 153 MB, the
    two fillers' 28 + 28 and requests' rows of 75 or 169): five turns of
    more than 73 tokens under the 512-token context with no request of the
    other between them make the 2,048-token CONTEXT the least recently
    touched leaf.  It stays, because requests have started from it and
    none from the turns' rows (``PrefixTree.use``): in sixteen schedules,
    the cell's own and its neighbours, whatever they meet of the issue's
    criteria, every request finds its context.  (By the first draft's
    eviction, recency alone, 20500001 lost its context for good and more
    than ten requests with it.)"""
    missed, evictions = resident_evictions(
        dict(TRAFFIC, shape_seed=shape_seed), BENCHMARK["run_seconds"])
    assert missed == 0 and evictions > 30


def test_a_traced_rehearsal_reads_every_metric_a_cpu_can():
    """Five layers at the tiny size (three gdn layers, a full one, a gdn
    layer more), 4 lanes: the whole cell on the CPU in float32, every
    metric the trace does not have to give."""
    tiny = olmo_hybrid.tiny(CONFIG)
    assert tiny["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention", "linear_attention"]
    assert (tiny["linear_key_head_dim"], tiny["linear_value_head_dim"],
            tiny["hidden_size"] // tiny["num_attention_heads"]) == (8, 16,
                                                                    12)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000050", "--seconds", "2",
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
    # 4 lanes: one full layer's K and V of 128 positions x 48 channels;
    # four gdn layers' registers [3, 128] and matrices [4, 8, 16], float32
    assert metrics["serve.cache_full_gb"]["value"] == pytest.approx(
        4 * 2 * 128 * 48 * 4 / 1e9)
    assert metrics["serve.cache_state_gb"]["value"] == pytest.approx(
        4 * 4 * (3 * 128 + 4 * 128) * 4 / 1e9)
    assert metrics["serve.programs_in_window"]["value"] == 0
    assert 0 < metrics["serve.full_positions_live_pct"]["value"] < 100
    assert metrics["serve.prefix_hit_pct"]["value"] > 50
