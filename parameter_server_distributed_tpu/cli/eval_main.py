"""Standalone evaluation CLI (`pst-eval`): loss/perplexity (LMs) or
loss/accuracy (classifiers) of a checkpoint over a dataset — no training
step, no server.

    pst-eval --model=small_lm [--ckpt=... | --ckpt-dir=... [--avg-last=K]
             [--lora-alpha=A]] \\
             [--data=corpus.txt|shard.bin|data.npz] [--batch=32]
             [--steps=16] [--seq=N] [--seed=0] [--dtype=bf16]
             [--scan-layers | --no-scan-layers]
    pst-eval --hf-gpt2=<checkout> [--data=...]   # converted checkpoint

Output is ONE strict-JSON line: ``{"model": ..., "loss": mean,
"perplexity": exp(loss)}`` for token models (perplexity is per-token —
dense LM loss is the mean next-token NLL; for MoE models the loss
includes the load-balance aux term, so perplexity is OMITTED rather
than reported skewed), or ``{"model": ..., "loss": ...,
"accuracy": top1}`` for (x, y) models.  A non-finite loss (diverged
checkpoint) reports ``null``, never a bare NaN token.  ``--data`` takes the same
sources the trainer does (raw .txt byte-tokenized, .bin token shard,
npz x/y); without it the registry's synthetic stream evaluates —
useful only as a smoke check.

The reference has no evaluation path (no model at all — reference
src/worker.cpp:316-329); this completes the CLI suite: train,
generate, serve, status, eval.
"""

from __future__ import annotations

import json
import logging
import sys

from ..config import parse_argv, require_flag_value

KNOWN_FLAGS = frozenset({
    "model", "hf-gpt2", "dtype", "scan-layers", "no-scan-layers", "seed",
    "ckpt", "ckpt-dir", "avg-last", "lora-alpha", "data", "batch", "steps",
    "seq",
})


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s "
                               "%(message)s")
    _, flags = parse_argv(argv)
    if "help" in flags:
        print(__doc__)
        return 0
    require_flag_value(argv, "--lora-alpha",
                       hint="the ALPHA the run trained with")
    unknown = set(flags) - KNOWN_FLAGS
    if unknown:
        raise SystemExit(f"unknown flag(s): {', '.join(sorted(unknown))}; "
                         f"--help lists the accepted flags")

    import jax
    import numpy as np

    from ..models.registry import get_model_and_batches
    from ..models.transformer import Transformer
    from ..utils.compile_cache import enable_compile_cache
    from .generate_main import load_params, match_layout

    enable_compile_cache()
    name = flags.get("model", "small_lm")
    batch = int(flags.get("batch", 32))
    steps = int(flags.get("steps", 16))
    seed = int(flags.get("seed", 0))
    if flags.get("hf-gpt2"):
        # evaluate a converted transformers checkpoint directly (same
        # loader pst-generate/pst-serve use; --seq fixed by n_positions)
        conflicts = {"model", "ckpt", "ckpt-dir", "avg-last",
                     "lora-alpha"} & set(flags)
        if conflicts:
            # avg-last/lora-alpha act during checkpoint LOADING, which
            # the hf branch never does — ignoring them would silently
            # score the raw converted weights
            raise SystemExit(
                "--hf-gpt2 defines model AND weights; drop "
                + "/".join(sorted("--" + c for c in conflicts)))
        if flags.get("seq"):
            raise SystemExit("--hf-gpt2 fixes seq (n_positions); "
                             "drop --seq")
        from ..models.registry import lm_batches
        from .generate_main import load_hf
        model, params, _ = load_hf(flags)
        name = f"hf-gpt2:{flags['hf-gpt2']}"
        source = name
        batches = lm_batches(model, batch, seed=seed + 100_003,
                             data_path=flags.get("data", ""))
    else:
        model, batches = get_model_and_batches(
            name, batch, seed=seed + 100_003,  # held-out stream shift
            data_path=flags.get("data", ""), dtype=flags.get("dtype", ""),
            scan=(False if "no-scan-layers" in flags
                  else True if "scan-layers" in flags else None),
            seq_len=int(flags.get("seq", 0)))
        params, source = load_params(flags, model, seed)
    is_lm = isinstance(model, Transformer)
    if is_lm:
        params = match_layout(model, params)
    print(f"evaluating: {source}", file=sys.stderr)

    if not is_lm and hasattr(model, "apply"):
        # ONE forward serves both metrics: the models' xy losses (MLP /
        # ResNet / ViT) are all plain softmax cross-entropy over apply()
        # logits, so deriving loss from the same logits is exact
        import jax.numpy as jnp

        @jax.jit
        def eval_batch(params, x, y):
            logits = model.apply(params, x)
            logp = jax.nn.log_softmax(logits, axis=-1)
            loss = -jnp.mean(jnp.take_along_axis(
                logp, y[:, None].astype(jnp.int32), axis=-1))
            return loss, jnp.argmax(logits, axis=-1)
    else:
        eval_batch = None
        loss_fn = jax.jit(model.loss)
    total_loss, correct, count = 0.0, 0, 0
    for _ in range(max(1, steps)):
        data = next(batches)
        if eval_batch is not None:
            x, y = data
            loss, pred = eval_batch(params, x, y)
            total_loss += float(loss)
            correct += int((np.asarray(pred) == np.asarray(y)).sum())
            count += len(np.asarray(y))
        else:
            total_loss += float(loss_fn(params, data))
    mean_loss = total_loss / max(1, steps)
    finite = bool(np.isfinite(mean_loss))
    out = {"model": name,
           "loss": round(mean_loss, 6) if finite else None,
           "batches": max(1, steps)}
    if is_lm and finite and model.config.moe_every == 0:
        # cap like train_loop's eval summary: strict-JSON safe
        out["perplexity"] = round(float(np.exp(min(mean_loss, 700.0))), 4)
    elif is_lm and finite:
        out["note"] = ("loss includes the MoE load-balance aux term; "
                       "perplexity omitted")
    if count:
        out["accuracy"] = round(correct / count, 4)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
