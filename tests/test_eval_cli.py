"""pst-eval (cli/eval_main.py): standalone checkpoint evaluation.

Driven as real subprocesses.  Contract: one JSON line with loss +
perplexity (LMs) or loss + accuracy (classifiers); a trained checkpoint
evaluates better than fresh init on its own training data."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


def run_eval(*flags: str, timeout: float = 400.0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m",
         "parameter_server_distributed_tpu.cli.eval_main", *flags],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_lm_perplexity_and_classifier_accuracy():
    out = run_eval("--model=tiny_lm", "--batch=4", "--steps=2")
    assert out["perplexity"] == pytest.approx(np.exp(out["loss"]), rel=1e-4)
    out2 = run_eval("--model=mnist_mlp", "--batch=16", "--steps=2")
    assert 0.0 <= out2["accuracy"] <= 1.0 and "perplexity" not in out2


def test_trained_checkpoint_beats_fresh_init(tmp_path):
    """Train briefly on a corpus, then pst-eval the checkpoint vs fresh
    init on the SAME corpus — the checkpoint must score lower loss."""
    import pathlib

    corpus = tmp_path / "c.txt"
    corpus.write_text((pathlib.Path(__file__).resolve().parents[1]
                       / "parameter_server_distributed_tpu/models/lora.py"
                       ).read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, "-m",
         "parameter_server_distributed_tpu.cli.train_main",
         "--model=tiny_lm", "--batch=8", "--steps=30", f"--data={corpus}",
         "--optimizer=adamw", "--lr=3e-3",
         f"--ckpt-dir={tmp_path}/ckpt", "--ckpt-every=30"],
        check=True, capture_output=True, text=True, timeout=400, env=env)
    trained = run_eval("--model=tiny_lm", f"--data={corpus}",
                       f"--ckpt-dir={tmp_path}/ckpt", "--batch=8",
                       "--steps=4")
    fresh = run_eval("--model=tiny_lm", f"--data={corpus}", "--batch=8",
                     "--steps=4")
    assert trained["loss"] < fresh["loss"]
    assert trained["perplexity"] < fresh["perplexity"]


def test_eval_hf_checkpoint(tmp_path, capsys):
    """pst-eval --hf-gpt2: loss/perplexity of a converted transformers
    checkpoint — the eval leg of the converted-model CLI suite."""
    import torch
    import transformers

    from parameter_server_distributed_tpu.cli.eval_main import main

    torch.manual_seed(0)
    checkout = tmp_path / "hf"
    transformers.GPT2LMHeadModel(transformers.GPT2Config(
        vocab_size=96, n_positions=32, n_embd=32, n_layer=2,
        n_head=2)).save_pretrained(checkout)
    rc = main([f"--hf-gpt2={checkout}", "--batch=4", "--steps=2"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"].startswith("hf-gpt2:")
    assert np.isfinite(out["loss"]) and out["perplexity"] > 1.0

    with pytest.raises(SystemExit, match="defines model"):
        main([f"--hf-gpt2={checkout}", "--model=small_lm"])
    # checkpoint-loading flags are meaningless here — rejected, not
    # silently ignored
    with pytest.raises(SystemExit, match="lora-alpha"):
        main([f"--hf-gpt2={checkout}", "--lora-alpha=16"])
