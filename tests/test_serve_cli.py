"""pst-serve (cli/serve_main.py): the JSONL line-protocol serving process.

Driven as a real subprocess — the same way a user (or a transport shim)
would.  Contract: every request's streamed tokens equal its final result,
concurrent requests interleave, errors are per-request, and stdin EOF
drains in-flight work then exits 0.
"""

import json
import os
import subprocess
import sys

import pytest


def run_serve(requests: list[dict], *extra_flags: str,
              timeout: float = 400.0) -> tuple[list[dict], str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m",
         "parameter_server_distributed_tpu.cli.serve_main",
         "--model=tiny_lm", "--slots=2", "--max-len=48", *extra_flags],
        input="\n".join(json.dumps(r) for r in requests) + "\n",
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return ([json.loads(line) for line in proc.stdout.strip().splitlines()],
            proc.stderr)


def test_stream_equals_result_and_errors_are_per_request():
    lines, _ = run_serve([
        {"id": "a", "tokens": [1, 2, 3], "max_new": 4},
        {"id": "b", "tokens": [7, 8], "max_new": 3},
        {"id": "oneshot", "tokens": [4], "max_new": 1},
        {"id": "bad"},
    ])
    streamed: dict = {}
    for line in lines:
        if "token" in line:
            streamed.setdefault(line["id"], []).append(line["token"])
    done = {line["id"]: line for line in lines if line.get("done")}
    assert set(done) == {"a", "b", "oneshot"}
    for rid, expect_n in (("a", 4), ("b", 3), ("oneshot", 1)):
        assert streamed[rid] == done[rid]["tokens"]
        assert len(done[rid]["tokens"]) == expect_n
    errors = [line for line in lines if "error" in line]
    assert len(errors) == 1 and errors[0]["id"] == "bad"


def test_malformed_lines_never_kill_the_server():
    """Type-confused requests, JSON scalars/arrays, and a bare `null`
    (which must not alias the EOF sentinel) all become per-line errors
    while the well-formed request completes."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    raw = "\n".join([
        json.dumps({"id": "t", "tokens": 5}),        # non-iterable tokens
        "42", "[1,2]", "null", "{not json",
        json.dumps({"id": "ok", "tokens": [1], "max_new": 2}),
    ]) + "\n"
    proc = subprocess.run(
        [sys.executable, "-m",
         "parameter_server_distributed_tpu.cli.serve_main",
         "--model=tiny_lm", "--slots=2", "--max-len=48"],
        input=raw, capture_output=True, text=True, timeout=400, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    errors = [line for line in lines if "error" in line]
    assert len(errors) == 5, lines                   # one per bad line
    done = {line["id"]: line for line in lines if line.get("done")}
    assert len(done["ok"]["tokens"]) == 2            # null != EOF sentinel


def test_per_request_temperature_and_stop_fields():
    """Protocol-level pass-through of the per-request sampling knobs: a
    greedy request and a hot-temperature request on the SAME tokens give
    different streams, and "stop" cuts a request short."""
    greedy = {"id": "g", "tokens": [1, 2, 3], "max_new": 8}
    hot = {"id": "h", "tokens": [1, 2, 3], "max_new": 8, "temperature": 9.0}
    lines, _ = run_serve([greedy, hot])
    done = {line["id"]: line for line in lines if line.get("done")}
    assert len(done["g"]["tokens"]) == 8
    assert done["g"]["tokens"] != done["h"]["tokens"]

    # stop at the greedy stream's 3rd token truncates the result there
    stop_tok = done["g"]["tokens"][2]
    lines2, _ = run_serve([dict(greedy, id="s", stop=[stop_tok])])
    done2 = {line["id"]: line for line in lines2 if line.get("done")}
    assert done2["s"]["tokens"] == done["g"]["tokens"][:3]


def test_speculative_serving_protocol_multi_token_rounds():
    """Speculative mode at the protocol level: a SELF-draft at the same
    seed accepts every proposal, so each round commits draft_len+1 tokens
    and requests finish mid-round — the stream must still deliver every
    token exactly once and a done line per request (regression: the drain
    loop once popped a request at its finishing token and crashed on the
    same round's remaining pairs)."""
    lines, _ = run_serve(
        [{"id": "a", "tokens": [1, 2, 3], "max_new": 9},
         {"id": "b", "tokens": [4, 5], "max_new": 7}],
        "--draft-model=tiny_lm", "--draft-seed=0", "--draft-len=4")
    streamed: dict = {}
    for line in lines:
        if "token" in line:
            streamed.setdefault(line["id"], []).append(line["token"])
    done = {line["id"]: line for line in lines if line.get("done")}
    assert set(done) == {"a", "b"}
    for rid, expect_n in (("a", 9), ("b", 7)):
        assert streamed[rid] == done[rid]["tokens"]
        assert len(done[rid]["tokens"]) == expect_n

    # greedy speculative output is token-exact vs the plain server
    plain, _ = run_serve([{"id": "a", "tokens": [1, 2, 3], "max_new": 9}])
    plain_done = next(l for l in plain if l.get("done"))
    assert plain_done["tokens"] == done["a"]["tokens"]


def test_text_mode_round_trip():
    lines, _ = run_serve([{"id": 1, "prompt": "hi", "max_new": 3}])
    done = [line for line in lines if line.get("done")]
    assert len(done) == 1 and isinstance(done[0]["text"], str)


def test_hf_checkpoint_serves(tmp_path):
    """pst-serve --hf-gpt2 drives a local transformers checkout end to
    end (tokens-mode request — save_pretrained writes no tokenizer
    files, so the text path is not exercised here)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(0)
    cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=2)
    transformers.GPT2LMHeadModel(cfg).save_pretrained(tmp_path)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m",
         "parameter_server_distributed_tpu.cli.serve_main",
         f"--hf-gpt2={tmp_path}", "--slots=2", "--max-len=48"],
        input=json.dumps({"id": 1, "tokens": [5, 6, 7],
                          "max_new": 3}) + "\n",
        capture_output=True, text=True, timeout=400, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    done = [line for line in lines if line.get("done")]
    assert len(done) == 1 and len(done[0]["tokens"]) == 3


def test_overflow_request_rejected_not_fatal():
    """A request that cannot fit the cache errors; the server keeps
    serving the others and still exits cleanly."""
    lines, _ = run_serve([
        {"id": "big", "tokens": list(range(40)), "max_new": 20},
        {"id": "ok", "tokens": [1], "max_new": 2},
    ])
    assert any("error" in line and line["id"] == "big" for line in lines)
    done = {line["id"]: line for line in lines if line.get("done")}
    assert len(done["ok"]["tokens"]) == 2


def test_serve_cli_fused_rounds_token_exact():
    """--fused-rounds=N: same token streams as the per-round server
    (step_many is token-exact), just fewer device dispatches."""
    reqs = [{"id": i, "tokens": [3 + i, 7, 11], "max_new": 9}
            for i in range(3)]

    def done_map(lines):
        return {obj["id"]: obj["tokens"] for obj in lines
                if obj.get("done")}

    plain, _ = run_serve(reqs)
    fused, _ = run_serve(reqs, "--fused-rounds=4")
    assert done_map(fused) == done_map(plain)
