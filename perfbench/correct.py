"""The comparison that decides ``correct``: the program's logits, loss and
gradient against the configuration's plain float32 reference, on weights
made from ``--seed`` at the published widths, outside the measured window.
"""

from __future__ import annotations

import importlib

import numpy as np

from . import program

# Logits are compared as the root-mean-square of x - ref over the standard
# deviation of the reference's logits (and the largest single difference is
# reported beside it: over 25 million logits it is an extreme value, five to
# six times the RMS).  The program computes in bfloat16 with float32
# accumulation; on the v5e gpt2-medium reads an RMS of 0.0119 and a largest
# difference of 0.068 in every run (PERF.md, PR 23).  The tolerances are two
# and a half and three times that: an 8-bit float or int8 path, with sixteen
# times bfloat16's rounding step, reads several times the tolerance, and a
# dropped term (a bias, a norm's shift, the 1/sqrt(head size)) reads near 1.
LOGIT_TOLERANCE = 0.03
MAX_TOLERANCE = 0.2
# A served (greedy) token may differ from the reference's argmax only on a
# near-tie: within this many standard deviations of the reference's best
# logit at that position (both the chosen and the best logit carry an
# error that can reach the largest single difference).
NEAR_TIE_TOLERANCE = 2 * MAX_TOLERANCE
# Training cells also differentiate: ``jax.value_and_grad(model.loss)``, the
# function both trainers differentiate, against the reference's float32
# backward pass on the same weights and tokens.  The gradient is compared
# as |g - ref| / |ref| over all parameters at once (and the cosine is
# reported); the loss as a relative difference.  On the v5e gpt2-medium
# reads a gradient error of 0.0138 (cosine 0.99991) and a loss error of
# 4.9e-5, gpt2-large 0.0158 and 4.3e-5 (PERF.md, PR 23).  The tolerances are
# two and a half times the larger gradient error, as for the logits, and
# five times the loss error (a mean over 510 positions moves more with the
# seed than an error summed over 400 million parameters).
GRADIENT_TOLERANCE = 0.04
LOSS_TOLERANCE = 2.5e-4


def reference_forward(config: dict):
    module = importlib.import_module(
        f"perfbench.reference.{config['reference']}")
    import jax

    def run(weights, tokens):
        return module.forward(weights, tokens, n_head=config["n_head"],
                              eps=float(config["layer_norm_epsilon"]))

    return jax.jit(run)


def reference_backward(config: dict):
    """(weights, tokens) -> ((loss, logits), gradient), in float32."""
    module = importlib.import_module(
        f"perfbench.reference.{config['reference']}")
    import jax

    def run(weights, tokens):
        return module.loss(weights, tokens, n_head=config["n_head"],
                           eps=float(config["layer_norm_epsilon"]))

    return jax.jit(jax.value_and_grad(run, has_aux=True))


def sample_tokens(config: dict, seed: int, batch: int, seq: int):
    rng = np.random.default_rng([int(seed), 5])
    return rng.integers(0, config["vocab_size"], (batch, seq)).astype(
        np.int32)


def logits_errors(system_logits, reference_logits) -> tuple[float, float]:
    """(RMS, largest) difference over the standard deviation of the
    reference's logits."""
    ref = np.asarray(reference_logits, np.float32)
    diff = np.asarray(system_logits, np.float32) - ref
    scale = float(np.std(ref))
    return (float(np.sqrt(np.mean(diff ** 2))) / scale,
            float(np.max(np.abs(diff))) / scale)


def gradient_errors(system_grads: dict, reference_grads: dict
                    ) -> tuple[float, float]:
    """(|g - ref| / |ref|, cosine) over every parameter at once; both in
    the reference's names."""
    import jax

    ours, refs = (jax.tree.leaves(g) for g in (system_grads,
                                               reference_grads))
    diff = sum(float(np.sum((np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)) ** 2))
               for a, b in zip(ours, refs))
    dot = sum(float(np.sum(np.asarray(a, np.float32)
                           * np.asarray(b, np.float32)))
              for a, b in zip(ours, refs))
    norm_a = sum(float(np.sum(np.asarray(a, np.float32) ** 2)) for a in ours)
    norm_b = sum(float(np.sum(np.asarray(b, np.float32) ** 2)) for b in refs)
    return ((diff / norm_b) ** 0.5, dot / (norm_a * norm_b) ** 0.5)


def compare_forward(config: dict, model, seed: int, check: dict,
                    backward: bool = False) -> dict:
    """Logits of ``model.apply`` (the program, in its own dtype) against
    the reference on a seeded sample of sequences and, with ``backward``,
    the loss and gradient of ``model.loss`` against the reference's.
    Weights sit on the first device; the reference's float32 copy is made
    after the cell's own state is gone (the callers free it first)."""
    import jax

    params = program.make_weights(model, seed)
    tokens = sample_tokens(config, seed, check["sequences"],
                           check["tokens"])
    got = np.asarray(jax.jit(model.apply)(params, tokens), np.float32)
    weights = program.reference_weights(params, config["n_layer"])
    if backward:
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
        # the tied head is two matrices in the program and in the
        # reference's weights alike, so the two gradients line up by name
        grads = jax.tree.map(
            np.asarray, program.reference_weights(grads, config["n_layer"]))
        del params
        (ref_loss, ref), ref_grads = reference_backward(config)(weights,
                                                                tokens)
    else:
        del params
        ref = reference_forward(config)(weights, tokens)
    error, worst = logits_errors(got, ref)
    out = {"logits_rms_error_std": error, "tolerance": LOGIT_TOLERANCE,
           "logits_max_error_std": worst, "max_tolerance": MAX_TOLERANCE,
           "sequences": int(tokens.shape[0]), "tokens": int(tokens.shape[1]),
           "ok": bool(np.isfinite(worst) and error <= LOGIT_TOLERANCE
                      and worst <= MAX_TOLERANCE)}
    if backward:
        g_error, cosine = gradient_errors(grads, ref_grads)
        loss_error = abs(float(loss) - float(ref_loss)) / float(ref_loss)
        out.update(gradient_error=g_error, gradient_cosine=cosine,
                   gradient_tolerance=GRADIENT_TOLERANCE,
                   loss=float(loss), reference_loss=float(ref_loss),
                   loss_error=loss_error, loss_tolerance=LOSS_TOLERANCE)
        out["ok"] = bool(out["ok"] and g_error <= GRADIENT_TOLERANCE
                         and loss_error <= LOSS_TOLERANCE)
    return out


def served_tokens_margin(config: dict, params: dict, prompt, served) -> float:
    """How far, in standard deviations of the reference's logits, each
    served token is below the reference's best at its position when the
    reference runs the whole sequence at once (prompt + served tokens): 0
    where the token is the argmax.  Returns the worst position."""
    weights = program.reference_weights(params, config["n_layer"])
    sequence = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served, np.int32)])[None]
    logits = np.asarray(reference_forward(config)(weights, sequence))[0]
    # position p predicts token p + 1
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
    chosen = rows[np.arange(len(served)), np.asarray(served)]
    return float(np.max((rows.max(axis=-1) - chosen) / rows.std(axis=-1)))
