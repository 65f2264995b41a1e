"""bench.py contract: one process runs one mode and prints exactly one
parseable JSON line that names the device it ran on; a mode that raises —
or a device mode that finds no TPU — exits non-zero.

The tests pass PSDT_BENCH_PLATFORM=cpu with tiny shapes: they are about the
JSON contract, not the accelerator.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _bench_process(mode: str, extra_env: dict | None = None,
                   timeout: float = 420.0):
    env = dict(os.environ)
    env.update({
        "PSDT_BENCH_MODE": mode,
        "PSDT_BENCH_PLATFORM": "cpu",
        "PSDT_BENCH_STEPS": "2",
    })
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, BENCH], env=env, cwd=REPO,
                          stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                          timeout=timeout)
    lines = [ln for ln in proc.stdout.decode().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one stdout line, got {lines}"
    return proc.returncode, json.loads(lines[0])


def run_bench(mode: str, extra_env: dict | None = None,
              timeout: float = 420.0) -> dict:
    code, result = _bench_process(mode, extra_env, timeout)
    assert code == 0, result
    for key in ("metric", "value", "unit", "vs_baseline", "platform",
                "device_kind", "device_count"):
        assert key in result, f"missing {key}: {result}"
    assert result["platform"] == "cpu"
    return result


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_bench_failing_mode_exits_nonzero():
    """A mode that raises prints a bench_error line and exits non-zero —
    here the cheapest raise there is, an unknown mode name."""
    code, result = _bench_process("no_such_mode")
    assert code != 0
    assert result["metric"] == "bench_error"
    assert "no_such_mode" in result["note"]


def test_bench_device_mode_without_tpu_exits_nonzero():
    """A timing taken on the CPU is not a speed: a device mode on a CPU
    backend fails unless PSDT_BENCH_PLATFORM=cpu is given (the test
    environment pins JAX_PLATFORMS=cpu)."""
    code, result = _bench_process(
        "mfu", extra_env={"PSDT_BENCH_PLATFORM": "", "JAX_PLATFORMS": "cpu"})
    assert code != 0
    assert result["metric"] == "bench_error"
    assert "requested TPU" in result["note"]


def test_peak_for_raises_on_unknown_device_kind():
    bench = _load_bench()
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert bench.peak_for(v5e) == 197e12
    assert bench.peak_for(types.SimpleNamespace(device_kind="TPU v5")) \
        == 459e12
    for kind in ("", "cpu", "Banana 9000"):
        with pytest.raises(ValueError, match="no peak"):
            bench.peak_for(types.SimpleNamespace(device_kind=kind))


@pytest.mark.slow
def test_bench_mfu_cpu_contract():
    result = run_bench("mfu")
    assert result["metric"].startswith("mlp")
    assert result["value"] > 0
    assert result["metric"] != "bench_error"


@pytest.mark.slow
def test_bench_pushpull_contract():
    result = run_bench("pushpull")
    assert result["metric"].startswith("ps_pushpull_p50")
    assert result["value"] > 0


@pytest.mark.slow
def test_bench_codec_contract():
    """codec mode: native-vs-Python encode/decode GB/s per packed wire
    dtype plus the same-host shm-vs-TCP fused-step A/B, all visible in
    the JSON."""
    result = run_bench("codec", extra_env={
        "PSDT_BENCH_PARAMS": "4e5",
        "PSDT_BENCH_STEPS": "2",
    })
    assert result["metric"].startswith("codec_encode_gbps")
    assert result["value"] > 0
    for dtype in ("bf16", "int8", "topk"):
        assert result["encode"][dtype]["python"] > 0
        assert result["decode"][dtype]["python"] > 0
    same_host = result["same_host"]
    assert same_host["tcp"]["p50_ms"] > 0
    assert same_host["shm"]["p50_ms"] > 0
    assert same_host["shm"]["shm_active"] is True
    assert same_host["shm"]["shm_bytes"] > 0
    assert same_host["tcp"]["shm_active"] is False


@pytest.mark.slow
def test_bench_aggregate_contract():
    """aggregate mode: streaming-vs-buffered PS aggregation profile with
    the acceptance properties visible in the JSON — ~1x model peak
    gradient memory and one serve encode per version under streaming."""
    result = run_bench("aggregate", extra_env={
        "PSDT_BENCH_PARAMS": "2e5",
        "PSDT_BENCH_WORKER_COUNTS": "2,4",
        "PSDT_BENCH_STEPS": "2",
    })
    assert result["metric"].startswith("ps_aggregate_barrier_close_ms")
    assert result["value"] > 0
    streaming, buffered = result["streaming"], result["buffered"]
    assert streaming["4"]["peak_grad_buffer_x_model"] <= 1.5
    assert buffered["4"]["peak_grad_buffer_x_model"] >= 3.5
    # one encode per (version, dtype): 2 iterations -> 2 misses for 8 serves
    assert streaming["4"]["serve_encodes"] == 2
    assert streaming["4"]["serves"] == 8


def test_bench_delta_contract():
    """delta mode: per-pull serve bytes through the version-delta chain
    vs the full encode-once serve at varying version locality, for SGD
    and momentum runs, plus the live publication latency — with the
    ISSUE 10 acceptance bound visible in the JSON: delta bytes <= 30%
    of the full serve at locality 1 for BOTH optimizers."""
    result = run_bench("delta", extra_env={
        "PSDT_BENCH_PARAMS": "2e5",
        "PSDT_BENCH_STEPS": "4",
        "PSDT_BENCH_DELTA_LOCALITY": "1,2",
    })
    assert result["metric"] == "ps_delta_serve_ratio_l1"
    assert 0 < result["value"] <= 0.30
    for opt in ("sgd", "momentum"):
        rows = result[opt]
        assert rows["1"]["delta_vs_full_ratio"] <= 0.30, (opt, rows)
        assert rows["1"]["full_fallbacks"] == 0, (opt, rows)
        assert rows["1"]["delta_pulls"] == 4, (opt, rows)
        # a longer hop still beats (or matches) re-shipping the model
        assert rows["2"]["delta_vs_full_ratio"] < 1.0, (opt, rows)
    assert result["publish_samples"] >= 3
    assert result["publish_p50_ms"] > 0


def test_bench_elastic_contract():
    """elastic mode (ISSUE 13): healthy-worker iteration wall p50 under
    a K-of-N quorum vs all-of-N with one netsim-delayed straggler — the
    quorum arm must actually quorum-close (and fold the straggler
    forward), and its p50 must beat the all-of-N arm, which pays the
    straggler's injected delay on every barrier."""
    result = run_bench("elastic", extra_env={
        "PSDT_BENCH_PARAMS": "1e5",
        "PSDT_BENCH_STEPS": "5",
        "PSDT_BENCH_STRAGGLER_MS": "250",
        "PSDT_BENCH_GRACE_MS": "80",
    })
    assert result["metric"] == "ps_elastic_iter_wall_p50_ms_quorum"
    assert result["value"] > 0
    assert result["quorum"]["quorum_closes"] > 0
    assert result["quorum"]["stale_folds"] > 0
    assert result["all_of_n"]["quorum_closes"] == 0
    # the quorum exists to cut the straggler's delay out of the healthy
    # workers' iteration wall: K-of-N p50 strictly under all-of-N p50
    assert (result["quorum"]["iter_wall_p50_ms"]
            < result["all_of_n"]["iter_wall_p50_ms"]), result["note"]


def test_bench_freerun_contract():
    """freerun mode (ISSUE 16): steps/s and time-to-target-loss for the
    barrier-free apply-on-arrival arm vs K-of-N quorum vs all-of-N under
    a heterogeneous-speed netsim profile.  The free-run arm must
    actually run free (applies land, the barriered arms record none)
    and out-rate the all-of-N arm, which pays the slowest worker's
    injected delay on every barrier."""
    result = run_bench("freerun", extra_env={
        "PSDT_BENCH_PARAMS": "1e5",
        "PSDT_BENCH_STEPS": "5",
        "PSDT_BENCH_STRAGGLER_MS": "150",
        "PSDT_BENCH_GRACE_MS": "80",
    })
    assert result["metric"] == "ps_freerun_steps_per_s"
    assert result["value"] > 0
    assert result["freerun"]["freerun_applies"] > 0
    assert result["freerun"]["freerun_publishes"] > 0
    assert result["all_of_n"]["freerun_applies"] == 0
    assert result["quorum"]["freerun_applies"] == 0
    # barrier-free pushes never wait for the straggler: the free-run
    # steps/s rate must beat the all-of-N barrier's
    assert (result["freerun"]["steps_per_s"]
            > result["all_of_n"]["steps_per_s"]), result
    assert result["freerun"]["time_to_target_ms"] is not None


@pytest.mark.slow
def test_bench_fleet_contract():
    """fleet mode (ISSUE 14): streams/s + p99 TTFT vs fleet size under
    an open-loop load generator, each decode server a real pst-serve
    subprocess over loopback gRPC.  Capacity is pinned sleep-bound
    (PSDT_BENCH_ROUND_DELAY_MS) so the control plane's scaling shows
    even on a small CI host: 2 servers must sustain materially more
    streams/s than 1 against the same arrival schedule, with zero
    failed streams either way.  The high-prefix-share arm (ISSUE 20)
    must show the radix cache absorbing the shared system prompt: its
    fleet-wide prefill-token ratio well under the uniform arm's."""
    result = run_bench("fleet", extra_env={
        "PSDT_BENCH_STEPS": "6",
        "PSDT_BENCH_REQUESTS": "16",
        "PSDT_BENCH_FLEET_SIZES": "1,2",
        "PSDT_BENCH_ROUND_DELAY_MS": "25",
    }, timeout=540.0)
    assert result["metric"].startswith("fleet_streams_per_s")
    assert result["value"] > 0
    one, two = result["sizes"]["1"], result["sizes"]["2"]
    assert one["failed"] == 0 and two["failed"] == 0
    assert one["streams"] > 0 and two["streams"] > 0
    assert two["streams_per_s"] > 1.25 * one["streams_per_s"], \
        result["note"]
    prefix = result["sizes"]["prefix_share_x2"]
    assert prefix["failed"] == 0 and prefix["streams"] > 0
    # shared prefixes must not be re-prefilled: most prompt tokens are
    # the 48-token system prompt, forwarded once then served from the
    # radix cache — the ratio collapses vs the unique-prompt arm
    assert prefix["prefill_token_ratio"] < 0.5, result["note"]
    assert (prefix["prefill_token_ratio"]
            < two["prefill_token_ratio"]), result["note"]


@pytest.mark.slow
def test_bench_replicate_contract():
    """replicate mode: barrier-close overhead off/async/sync replication,
    failover wall-clock, and the 2->4 reshard's moved bytes — all
    visible in the JSON."""
    result = run_bench("replicate", extra_env={
        "PSDT_BENCH_PARAMS": "1e5",
        "PSDT_BENCH_STEPS": "2",
    })
    assert result["metric"] == "ps_replicate_close_ms_sync"
    assert result["value"] > 0
    assert set(result["close_ms"]) == {"off", "async", "sync"}
    assert all(v > 0 for v in result["close_ms"].values())
    assert result["failover_s"] > 0
    assert result["reshard_s"] > 0
    assert result["reshard_moved_bytes"] > 0


def test_bench_replicate_sharded_contract():
    """replicate mode, sharded-update sweep (ISSUE 18): close p50 and
    TRUE replication wire bytes/iteration (client-side request+response
    byte counters over the PushReplicaDelta / ShardedApplySlices /
    InstallSlabSlices legs), flat ship vs sharded raw vs sharded
    quantized — with the acceptance visible in the JSON: the measured
    closes really sharded, and both sharded arms move fewer bytes per
    iteration than the flat ship at 2 replicas without a slower close."""
    result = run_bench("replicate", extra_env={
        "PSDT_BENCH_PARAMS": "1e5",
        "PSDT_BENCH_STEPS": "3",
        "PSDT_BENCH_SHARDED_ONLY": "1",
        "PSDT_BENCH_SHARDED_TENSORS": "32",
        "PSDT_BENCH_REPLICA_COUNTS": "1,2",
    })
    assert result["metric"] == "ps_replicate_sharded_bytes_ratio_2r"
    assert 0 < result["value"] < 1.0
    sweep = result["sharded"]
    rows = {(r["replicas"], r["arm"]): r for r in sweep["rows"]}
    # single-replica baseline: no replication traffic at all
    assert rows[(1, "flat")]["bytes_per_iter"] == 0
    flat = rows[(2, "flat")]
    assert flat["bytes_per_iter"] > 0 and flat["sharded_closes"] == 0
    for arm in ("sharded_raw", "sharded_quant"):
        row = rows[(2, arm)]
        # every measured close sharded (the warmup close absorbed the
        # backup's catch-up flat ship)
        assert row["sharded_closes"] == sweep["steps"], row
        assert row["sharded_fallbacks"] == 0, row
        assert 0 < row["bytes_per_iter"] < flat["bytes_per_iter"], row
        # close p50 no worse than the flat ship (generous envelope: tiny
        # shapes on a loaded CI host are noise-dominated)
        assert row["close_p50_ms"] < 2.0 * flat["close_p50_ms"], row
    ratios = sweep["bytes_per_iter_vs_flat"]["2"]
    assert ratios["sharded_quant"] < ratios["sharded_raw"] < 1.0


@pytest.mark.slow
def test_bench_obs_contract():
    """obs mode: flight-recorder event throughput + fused-step overhead
    recorder-on vs -off, with both arms' p50s visible in the JSON (the
    ISSUE 8 '<2% of fused-step p50' acceptance surface)."""
    result = run_bench("obs", extra_env={
        "PSDT_BENCH_PARAMS": "5e4",
        "PSDT_BENCH_STEPS": "3",
    })
    assert result["metric"] == "obs_flight_overhead_pct"
    assert result["events_per_s"] > 10_000
    assert result["ns_per_event"] > 0
    assert result["fused_p50_ms"]["off"] > 0
    assert result["fused_p50_ms"]["on"] > 0
    assert result["events_per_fused_step"] > 0
    # the acceptance bound is generous here (tiny shapes on a loaded CI
    # host are noise-dominated); the real BENCH row runs default shapes
    assert abs(result["value"]) < 50.0


@pytest.mark.slow
def test_bench_apply_contract():
    """apply mode: striped barrier-close profile, serial vs striped side
    by side with the stripe counts visible in the JSON, plus the
    ISSUE 11 device-vs-numpy sweep rows (tiny store here — the real
    32/128/512 MB rows run at default shapes)."""
    result = run_bench("apply", extra_env={
        "PSDT_BENCH_PARAMS": "4e5",
        "PSDT_BENCH_STRIPE_COUNTS": "1,2",
        "PSDT_BENCH_WORKER_COUNTS": "2",
        "PSDT_BENCH_STEPS": "2",
        "PSDT_BENCH_DEVICE_MB": "2",
        "PSDT_BENCH_DEVICE_OPTS": "sgd",
        "PSDT_BENCH_DEVICE_STRIPES": "1,2",
        "PSDT_BENCH_FLAT_TENSORS": "0",  # flat sweep: its own contract
    })
    assert result["metric"] == "ps_apply_close_ms_2stripes_2w"
    assert result["value"] > 0
    assert set(result["by_stripes"]) == {"1", "2"}
    assert result["by_stripes"]["1"]["2"]["barrier_close_ms"] > 0
    # the striped cell reports its achieved apply parallelism
    assert result["by_stripes"]["2"]["2"].get("apply_parallelism", 0) > 0
    # device-vs-numpy rows: every (size, opt, stripes) cell carries both
    # arms' close p50 and the ratio; the best-of-stripes summary keys
    # follow the "<mb>mb_<opt>" convention
    sweep = result["device_vs_numpy"]
    rows = sweep["rows"]
    assert len(rows) == 2  # 1 size x 1 opt x 2 stripe counts
    for row in rows:
        assert row["store_mb"] == 2 and row["opt"] == "sgd"
        assert row["numpy_close_ms"] > 0
        assert row["device_close_ms"] > 0
        assert row["device_vs_numpy"] > 0
    assert "2mb_sgd" in sweep["best_ratio"]
    assert "cpu-jax" in sweep["backend"]


def test_bench_apply_flat_contract():
    """apply mode, flat-arena sweep (ISSUE 15): flat-vs-per-tensor rows
    over a many-small-tensor store, with the acceptance visible in the
    JSON — the flat arm's close dispatches at most stages x stripes
    kernel-library calls (counted by the jit-lowering probe, NOT wall
    clock) while the per-tensor arm's operand count scales O(tensors)."""
    from parameter_server_distributed_tpu.core import arena

    result = run_bench("apply", extra_env={
        "PSDT_BENCH_PARAMS": "1e5",
        "PSDT_BENCH_STRIPE_COUNTS": "1",
        "PSDT_BENCH_WORKER_COUNTS": "2",
        "PSDT_BENCH_STEPS": "2",
        "PSDT_BENCH_DEVICE_MB": "",          # device sweep off
        "PSDT_BENCH_FLAT_TENSORS": "48",
        "PSDT_BENCH_FLAT_KB": "4",
        "PSDT_BENCH_FLAT_BIG_MB": "8",
        "PSDT_BENCH_FLAT_OPTS": "adam",
        "PSDT_BENCH_FLAT_STRIPES": "1,2",
        # shrink the regime bound so the tiny big-store control (8 MB)
        # still exercises the gate row the real sweep sees at 128 MB
        "PSDT_ARENA_MAX_TENSOR_BYTES": "65536",
    })
    sweep = result["flat_arena"]
    rows = sweep["rows"]
    assert len(rows) == 4  # (small, big) x 2 stripe counts
    small = [r for r in rows if r["store"] == "small"]
    assert len(small) == 2
    for row in small:
        assert row["tensors"] == 48 and row["opt"] == "adam"
        assert row["per_tensor_close_ms"] > 0
        assert row["flat_close_ms"] > 0
        assert not row["flat_regime_gated"]
        # THE bound: one kernel per stage per stripe, tensor count
        # notwithstanding (48 tensors here)
        budget = arena.close_dispatch_budget("adam", row["stripes"])
        assert 0 < row["flat_profile"]["stage_calls"] <= budget
        # ... while the per-tensor path's stage operands scale O(tensors)
        assert row["per_tensor_profile"]["operands"] >= row["tensors"]
        assert row["flat_profile"]["operands"] < budget * 4
    big = [r for r in rows if r["store"] == "big"]
    # the big-tensor control rides the mean-tensor-size regime gate
    # (bandwidth-bound: the per-tensor path is the right regime there)
    assert all(r["flat_regime_gated"] for r in big)
    assert "small_adam" in sweep["best_ratio"]


@pytest.mark.slow
def test_bench_tier_contract():
    """tier mode: PS ingress bytes + fused-round wall, flat vs two-tier,
    with the ISSUE 9 acceptance visible in the JSON — at 4 workers in 2
    groups the tier ingress ratio must be <= 0.55 of flat."""
    result = run_bench("tier", extra_env={
        "PSDT_BENCH_PARAMS": "2e5",
        "PSDT_BENCH_WORKER_COUNTS": "4",
        "PSDT_BENCH_STEPS": "2",
    })
    assert result["metric"] == "ps_tier_ingress_ratio_4w"
    assert 0 < result["value"] <= 0.55, result
    row = result["by_workers"]["4"]
    assert row["flat"]["ingress_bytes_per_iter"] > 0
    assert row["tier"]["ingress_bytes_per_iter"] > 0
    assert row["flat"]["round_wall_ms"] > 0
    assert row["tier"]["round_wall_ms"] > 0
    assert result["group_size"] == 2


@pytest.mark.slow
def test_bench_serve_contract():
    """serve mode: continuous-batching sustained tokens/s with the int8
    stack applied; the metric must carry the kv8 suffix."""
    result = run_bench("serve", extra_env={
        "PSDT_BENCH_MODEL": "tiny_lm",
        "PSDT_BENCH_BATCH": "2",
        "PSDT_BENCH_STEPS": "4",
        "PSDT_BENCH_REQUESTS": "4",
        "PSDT_BENCH_QUANT": "int8",
        "PSDT_BENCH_KV_CACHE": "int8",
    })
    assert result["metric"] == "tiny_lm_serve_tokens_per_sec_kv8"
    assert result["value"] > 0


@pytest.mark.slow
def test_bench_generate_int8_ab_contract():
    """generate-mode int8 A/B: metric suffix reflects exactly which of
    weights/cache are quantized, vs_baseline is the measured ratio."""
    result = run_bench("generate", extra_env={
        "PSDT_BENCH_MODEL": "tiny_lm",
        "PSDT_BENCH_BATCH": "2",
        "PSDT_BENCH_STEPS": "8",
        "PSDT_BENCH_QUANT": "int8",
    })
    assert result["metric"] == "tiny_lm_decode_tokens_per_sec_int8"
    assert result["value"] > 0 and result["vs_baseline"] > 0


@pytest.mark.slow
def test_bench_generate_trained_draft_contract():
    """PSDT_BENCH_TRAIN_STEPS fits target+draft on the source-code byte
    corpus before the speculative A/B; the JSON contract must hold and the
    metric must carry the trained suffix."""
    result = run_bench("generate", extra_env={
        "PSDT_BENCH_MODEL": "small_lm",
        "PSDT_BENCH_DRAFT": "tiny_lm",
        "PSDT_BENCH_TRAIN_STEPS": "3",
        "PSDT_BENCH_BATCH": "2",
        "PSDT_BENCH_STEPS": "8",
        "PSDT_BENCH_DRAFT_LEN": "2",
    })
    assert "speculative" in result["metric"]
    assert "_trained3" in result["metric"]
    assert result["value"] > 0
