"""The benchmark's generators, reference, contract and data-driven harness.
CPU only and quick; the end-to-end rehearsals run ``perfbench/run.py`` as the
driver does, in a process of their own."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import correct, harness, program, traffic_gen  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCHMARK = _load("BENCHMARK.json")
CHAT = _load("perfbench", "traffic", "chat_shared_prefix.json")


# ------------------------------------------------------------ generators
def _first(iterator, n):
    return [next(iterator) for _ in range(n)]


def test_token_batches_are_a_function_of_the_seed_alone():
    a = _first(traffic_gen.token_batches(4, 32, 50257, 2147483655), 3)
    b = _first(traffic_gen.token_batches(4, 32, 50257, 2147483655), 3)
    c = _first(traffic_gen.token_batches(4, 32, 50257, 2147483656), 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.int32 and a[0].shape == (4, 32)
    assert 0 <= min(x.min() for x in a) and max(x.max() for x in a) < 50257
    # Zipf: low ids are common
    assert np.mean(np.concatenate(a) < 100) > 0.3


def _schedule(seed, seconds=51.0):
    systems = traffic_gen.system_prompts(CHAT, 50257, seed)
    return systems, traffic_gen.serve_schedule(CHAT, 50257, seed, seconds,
                                               systems)


def test_serve_schedule_is_a_function_of_the_seed_alone():
    _, a = _schedule(11)
    _, b = _schedule(11)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


def test_every_seed_gets_the_same_work_at_the_same_times():
    _, a = _schedule(11)
    _, b = _schedule(3000000019)
    work = lambda s: [  # noqa: E731
        (r.due_s, r.system, len(r.prompt), r.max_new) for r in s]
    assert work(a) == work(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    # arrivals stay inside the window, in order
    assert all(0 < x.due_s < 51.0 for x in a)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


def test_prompts_share_only_their_system_prompt():
    systems, schedule = _schedule(5)
    assert len({int(s[0]) for s in systems}) == len(systems)
    firsts = set()
    for r in schedule:
        sys_len = len(systems[r.system])
        assert np.array_equal(r.prompt[:sys_len], systems[r.system])
        firsts.add((r.system, int(r.prompt[sys_len])))
        assert len(r.prompt) + r.max_new <= CHAT["server"]["max_len"]
    assert len(firsts) == len(schedule)


def test_warmup_covers_every_shape_the_traffic_can_produce():
    systems, schedule = _schedule(5)
    warm = traffic_gen.warmup_requests(CHAT, 50257, 5, systems)
    shape = lambda r: (  # noqa: E731
        traffic_gen._bucket(len(systems[r.system])),
        traffic_gen._bucket(len(r.prompt) - len(systems[r.system])))
    assert {shape(r) for r in schedule} <= {shape(r) for r in warm}


# -------------------------------------------------------------- reference
def test_reference_gpt2_agrees_with_the_program_at_a_tiny_size():
    """float32 on the CPU: the benchmark's plain GPT-2 and
    models/transformer.py differ only by rounding order."""
    import jax

    config = dict(_load("perfbench", "configs", "gpt2-medium.json"),
                  n_embd=64, n_head=4, n_layer=3, n_positions=48,
                  vocab_size=257)
    config["assumed"] = dict(config["assumed"], dtype="float32",
                             loss_chunk=16)
    for scan in (True, False):
        model = program.build_model(config, scan_layers=scan)
        params = program.make_weights(model, seed=3000000019)
        assert np.array_equal(np.asarray(params["lm_head/w"]),
                              np.asarray(params["embed/tok"]).T)
        # biases are zero at init: perturb them so the comparison sees them
        params = {k: (v + 0.05 if k.endswith(("/bq", "/b1", "/bias"))
                      else v) for k, v in params.items()}
        tokens = correct.sample_tokens(config, 7, 2, 48)
        got = jax.jit(model.apply)(params, tokens)
        ref = correct.reference_forward(config)(
            program.reference_weights(params, config["n_layer"]), tokens)
        rms, worst = correct.logits_errors(got, ref)
        assert rms < 1e-4 and worst < 1e-3
    # and a computation in a lower precision fails the stated tolerance
    low = np.asarray(ref, np.float32) + 0.2 * np.std(ref) * np.sign(
        np.sin(np.arange(ref.size).reshape(ref.shape)))
    assert correct.logits_errors(low, ref)[0] > correct.LOGIT_TOLERANCE


def test_reference_backward_agrees_with_the_program_at_a_tiny_size():
    """Loss and gradient of model.loss against the reference's float32
    backward pass, through the comparison the training cells run."""
    config = dict(_load("perfbench", "configs", "gpt2-medium.json"),
                  n_embd=64, n_head=4, n_layer=3, n_positions=48,
                  vocab_size=257)
    config["assumed"] = dict(config["assumed"], dtype="float32",
                             loss_chunk=16)
    out = correct.compare_forward(
        config, program.build_model(config), 3000000019,
        {"sequences": 2, "tokens": 48}, backward=True)
    assert out["ok"] and out["gradient_error"] < 1e-4
    assert out["loss_error"] < 1e-5 and out["gradient_cosine"] > 0.9999
    # a backward pass in a lower precision fails the stated tolerance
    ref = {"w": np.linspace(-1, 1, 4096, dtype=np.float32)}
    low = {"w": ref["w"] * (1 + 0.2 * np.sign(np.sin(np.arange(4096))))}
    assert correct.gradient_errors(low, ref)[0] > correct.GRADIENT_TOLERANCE
    assert correct.gradient_errors(ref, ref) == (0.0, pytest.approx(1.0))


def test_round_checks_hold_a_round_to_its_arithmetic():
    """The PS cell's first round: a lossless wire and float32 Adam pass,
    a bfloat16 wire and a wrong step size do not."""
    import ml_dtypes

    from perfbench.jobs import ps

    rng = np.random.default_rng(7)
    before = {"a": rng.normal(0, 0.02, (64, 32)).astype(np.float32),
              "b": np.ones(32, np.float32)}
    grads = {k: rng.normal(0, 1e-4, v.shape).astype(np.float32)
             for k, v in before.items()}
    optimizer = {"learning_rate": 3e-4, "b1": 0.9, "eps": 1e-8}
    tolerance = {"wire": 1e-5, "close": 1e-3}

    class Trainer:
        def compute_gradients(self, params, batch):
            assert params is before
            return grads, 11.5 if batch == "second" else 12.0

    def after_round(folded, lr):
        after = {k: before[k] - np.float32(lr) * g / (np.abs(g) + 1e-8)
                 for k, g in folded.items()}
        state = {"m": {k: np.float32(0.1) * g for k, g in folded.items()},
                 "step": 1}
        return after, state

    after, state = after_round(grads, 3e-4)
    good = ps.round_checks(Trainer(), ["first", "second"], before, after,
                           state, 11.5, optimizer, tolerance)
    assert good["round_reproduced"] and good["wire_ok"] and good["close_ok"]
    assert good["wire_error"] < 1e-6 and good["close_error"] < 1e-3
    lossy = {k: g.astype(ml_dtypes.bfloat16).astype(np.float32)
             for k, g in grads.items()}
    after, state = after_round(lossy, 3e-4)
    bad = ps.round_checks(Trainer(), ["second"], before, after, state,
                          11.5, optimizer, tolerance)
    assert not bad["wire_ok"] and bad["close_ok"]
    after, state = after_round(grads, 1.5e-4)
    bad = ps.round_checks(Trainer(), ["second"], before, after, state,
                          11.5, optimizer, tolerance)
    assert bad["wire_ok"] and not bad["close_ok"]
    assert ps.round_checks(Trainer(), ["first"], before, after, state, 11.5,
                           optimizer, tolerance) == {
        "round_reproduced": False}


def test_memory_sampler_adds_what_the_runtime_reserves_for_programs():
    class Device:
        readings = [
            {"bytes_in_use": 5, "peak_bytes_in_use": 7, "bytes_reserved": 0,
             "peak_bytes_reserved": 0, "bytes_limit": 16},
            {"bytes_in_use": 4, "peak_bytes_in_use": 7, "bytes_reserved": 6,
             "peak_bytes_reserved": 6, "bytes_limit": 16},
            {"bytes_in_use": 1, "peak_bytes_in_use": 7, "bytes_reserved": 2,
             "peak_bytes_reserved": 6, "bytes_limit": 16}]

        def memory_stats(self):
            return self.readings.pop(0) if len(self.readings) > 1 \
                else self.readings[0]

    sampler = harness.MemorySampler([Device()])
    sampler._sample()
    sampler._sample()
    assert sampler.parts() == {
        "buffers_peak_bytes": 7, "reserved_peak_bytes": 6,
        "together_peak_bytes": 10, "limit_bytes": 16}
    assert sampler.peak() == 10
    # a backend with no statistics (the CPU) reads nothing, not an error

    class Silent:
        def memory_stats(self):
            return None

    assert harness.MemorySampler([Silent()]).peak() == 0


def test_program_seed_fits_the_key():
    assert program.program_seed(3000000019) == 3000000019 % (2 ** 31 - 1)
    assert 0 <= program.program_seed(2 ** 31 + 7) < 2 ** 31 - 1


# --------------------------------------------------------------- contract
def test_benchmark_json_keeps_to_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    # a full check of 24 cells fits the driver's time
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "traffic", f"{w['traffic']}.json"))
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
        assert _load(c["file"])["reduced"] == c["reduced"] == []
    layers = {}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and "\n" not in m["layer"]
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        layers.setdefault(m["layer"], []).append(m["name"])
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "metrics", f"{m['name']}.json"))
    for name, cell in cells.items():
        assert len(harness.metrics_of(b, cell, "end_to_end")) >= 2
        assert len(harness.metrics_of(b, cell, "per_layer")) >= 1
    assert len(json.dumps(b)) < 64 * 1024


def test_four_fifths_of_the_knee():
    arrivals = CHAT["arrivals"]
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"], rel=0.03)


# ------------------------------------------------- harness driven by data
def test_a_cell_added_as_files_is_found_without_an_edit(tmp_path,
                                                        monkeypatch):
    """A later PR's cell: one traffic file, one metric file with a reader
    of its own, and entries in BENCHMARK.json.  No existing file changes."""
    tree = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), tree / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = dict(CHAT, arrivals=dict(CHAT["arrivals"], rate_per_s=1.0))
    (tree / "perfbench" / "traffic" / "chat_trickle.json").write_text(
        json.dumps(traffic))
    (tree / "perfbench" / "metrics" / "serve.rounds.json").write_text(
        json.dumps({"reader": "just_a_count", "args": {"key": "rounds"}}))
    (tree / "perfbench" / "readers" / "just_a_count.py").write_text(
        "def read(observed, key):\n    return observed.get(key)\n")
    benchmark = json.loads(json.dumps(BENCHMARK))
    benchmark["workloads"].append({
        "name": "serve_trickle_gpt2m", "config": "gpt2-medium",
        "traffic": "chat_trickle", "chips": 1, "why": "a test"})
    for m in benchmark["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("serve_trickle_gpt2m")
    # an end-to-end metric of the new cell alone: the job offers it, the
    # entry selects it
    benchmark["end_to_end"].insert(0, {
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["serve_trickle_gpt2m"]})
    benchmark["per_layer"].append({
        "name": "serve.rounds", "unit": "rounds", "better": "lower",
        "source": "program_counter", "layer": "decode step",
        "moves": "itl_p95_ms", "workloads": ["serve_trickle_gpt2m"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(benchmark))
    monkeypatch.setattr(harness, "CHECKOUT", str(tree))
    monkeypatch.setattr(harness, "HERE", str(tree / "perfbench"))
    monkeypatch.syspath_prepend(str(tree))
    for module in [m for m in sys.modules if m.startswith("perfbench.read")]:
        monkeypatch.delitem(sys.modules, module)
    import perfbench
    monkeypatch.setattr(perfbench, "__path__", [str(tree / "perfbench")])
    monkeypatch.delitem(sys.modules, "perfbench.readers", raising=False)

    found, cell, config, got = harness.load_cell("serve_trickle_gpt2m")
    assert got["arrivals"]["rate_per_s"] == 1.0 and got["job"] == "serve"
    assert config["n_embd"] == 1024
    assert [m["name"] for m in harness.metrics_of(
        found, cell, "end_to_end")] == ["ttft_p95_ms", "itl_p95_ms",
                                        "setup_s"]
    assert harness.read_per_layer(found, cell, {"rounds": 41}) == {
        "serve.rounds": {"value": 41.0, "unit": "rounds"}}
    # a reader that finds nothing is left out of the line
    assert harness.read_per_layer(found, cell, {}) == {}
    with pytest.raises(SystemExit):
        harness.load_cell("no_such_cell")


# ------------------------------------------------------------ end to end
def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in done.stdout.splitlines() if l.startswith("{")]
    return done.returncode, [json.loads(l) for l in lines]


def test_no_tpu_is_an_error_and_prints_no_result():
    rc, lines = _run("--workload", "spmd_step_gpt2m", "--seed", "1",
                     "--seconds", "1", "--trace", "0")
    assert rc != 0
    assert not any("metrics" in l or "correct" in l for l in lines)


@pytest.mark.parametrize("workload,devices,trace,expected", [
    ("spmd_step_gpt2m", 1, 0, {"train_tokens_per_s", "setup_s"}),
    ("ps_round_gpt2m", 1, 1, {"ps.compute_share_pct", "ps.apply_p50_ms",
                              "ps.round_host_p50_ms", "ps.serve_p50_ms",
                              "ps.push_wire_mb_per_round",
                              "ps.shm_mb_per_round"}),
    ("serve_chat_gpt2m", 1, 0, {"itl_p95_ms", "setup_s"}),
    ("spmd_step_gpt2l_4chip", 4, 1, {"train.data_share_pct",
                                     "train.dispatch_p50_ms"}),
])
def test_rehearsal_runs_every_cell_through_the_runner(workload, devices,
                                                      trace, expected):
    rc, lines = _run("--workload", workload, "--seed", "3000000019",
                     "--seconds", "2", "--trace", str(trace), "--rehearse",
                     devices=devices)
    assert rc == 0, lines
    last = lines[-1]
    assert last["rehearsal"] is True and "metrics" not in last
    line = last["not_a_result"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert expected <= set(line["metrics"])
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    setup = next(l for l in lines if l.get("detail") == "setup")
    assert setup["programs_in_window"]["programs"] == 0
    assert sum(setup["parts"].values()) == pytest.approx(setup["setup_s"])
