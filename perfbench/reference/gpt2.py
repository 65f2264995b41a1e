"""GPT-2 (Radford et al. 2019) forward pass in plain float32 ``jax.numpy``:
no kernels, no cache, no batching tricks, no sharing of code with
``models/transformer.py``.  Follows the published model: learned positions,
pre-LayerNorm blocks (eps from the config), biased projections, ``gelu_new``
(the tanh approximation), scores scaled by 1/sqrt(head size), a causal mask,
the output head tied to the token embedding.  Departure: none; dropout is an
identity at evaluation.

Weights are a dict in this module's own names:
  wte [V, d]  wpe [P, d]  lnf_g lnf_b [d]  head [d, V] (wte.T when tied)
  blocks: each a stacked [L, ...] array --
    ln1_g ln1_b ln2_g ln2_b [L, d]; wq wk wv wo [L, d, d]; bq bk bv bo [L, d]
    w1 [L, d, inner]; b1 [L, inner]; w2 [L, inner, d]; b2 [L, d]
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(h, w, n_head, eps):
    batch, seq, d = h.shape
    x = _layer_norm(h, w["ln1_g"], w["ln1_b"], eps)

    def heads(t):
        return t.reshape(batch, seq, n_head, d // n_head).transpose(0, 2, 1, 3)

    q = heads(x @ w["wq"] + w["bq"])
    k = heads(x @ w["wk"] + w["bk"])
    v = heads(x @ w["wv"] + w["bv"])
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(d // n_head)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    mixed = jax.nn.softmax(scores, axis=-1) @ v
    mixed = mixed.transpose(0, 2, 1, 3).reshape(batch, seq, d)
    h = h + mixed @ w["wo"] + w["bo"]
    x = _layer_norm(h, w["ln2_g"], w["ln2_b"], eps)
    return h + _gelu_new(x @ w["w1"] + w["b1"]) @ w["w2"] + w["b2"]


def forward(weights: dict, tokens, *, n_head: int, eps: float):
    """tokens [B, S] int -> logits [B, S, V] float32, every matmul at the
    highest precision the backend has (a TPU's default float32 matmul is
    not float32)."""
    with jax.default_matmul_precision("highest"):
        seq = tokens.shape[1]
        h = weights["wte"][tokens] + weights["wpe"][:seq]

        def step(h, w):
            return _block(h, w, n_head, eps), None

        h, _ = jax.lax.scan(step, h, weights["blocks"])
        h = _layer_norm(h, weights["lnf_g"], weights["lnf_b"], eps)
        return h @ weights["head"]


def loss(weights: dict, tokens, *, n_head: int, eps: float):
    """(mean next-token cross-entropy, logits): position p predicts token
    p + 1, the last position has no target.  Differentiable, so
    ``jax.value_and_grad(loss, has_aux=True)`` is the reference's
    backward pass."""
    logits = forward(weights, tokens, n_head=n_head, eps=eps)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked), logits
