"""Weight-only int8 serving quantization (models/quant.py).

The contract: a quantized store flows through the existing model code —
forward, both layer layouts, KV-cached decode, sampling — with bounded
numerical error, and the QTensor pytree composes with jit/scan/slicing.
The reference has no quantized path at all (f32 `repeated float` end to
end — reference proto/parameter_server.proto:19-24); these tests pin the
added capability's correctness.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models.generation import (
    generate, prefill, decode_step)
from parameter_server_distributed_tpu.models.quant import (
    QTensor, quantize, quantize_params, store_bytes, wdot)
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)


def tiny(scan_layers=False, kv_heads=None):
    return Transformer(TransformerConfig(
        vocab=96, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq=64, dtype=jnp.float32, scan_layers=scan_layers,
        **({"n_kv_heads": kv_heads} if kv_heads else {})))


def test_quantize_roundtrip_error_bound(rng):
    w = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    qt = quantize(w)
    assert qt.q.dtype == jnp.int8 and qt.scale.shape == (256,)
    # symmetric absmax/127: per-channel error is at most half a step
    step = np.asarray(qt.scale)
    err = np.abs(np.asarray(qt.dequant()) - np.asarray(w))
    assert (err <= step[None, :] * 0.5 + 1e-7).all()


def test_quantize_zero_channel_is_safe():
    w = jnp.zeros((16, 4), jnp.float32)
    qt = quantize(w)
    assert np.asarray(qt.scale).all() > 0  # no div-by-zero sentinel left
    np.testing.assert_array_equal(np.asarray(qt.dequant()), 0.0)


def test_wdot_matches_dequant_dot(rng):
    x = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 64)), jnp.float32)
    qt = quantize(w)
    got = wdot(x, qt)
    want = jnp.dot(x, qt.dequant())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_wdot_passthrough_dense(rng):
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(wdot(x, w)),
                                  np.asarray(jnp.dot(
                                      x, w,
                                      preferred_element_type=jnp.float32)))


def test_qtensor_is_a_pytree_and_slices():
    qt = quantize(jnp.ones((3, 16, 8), jnp.float32))
    leaves = jax.tree_util.tree_leaves(qt)
    assert len(leaves) == 2
    sliced = qt[1]
    assert sliced.q.shape == (16, 8) and sliced.scale.shape == (8,)
    rebuilt = jax.tree_util.tree_map(lambda x: x, qt)
    assert isinstance(rebuilt, QTensor)


@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["unrolled", "scan"])
def test_quantized_logits_track_full_precision(rng, scan_layers):
    model = tiny(scan_layers=scan_layers)
    params = model.init_params(0)
    qparams = quantize_params(params)
    # weight matrices quantized in the right layout, rest untouched
    key = "blocks/attn/wq" if scan_layers else "layer0/attn/wq"
    assert isinstance(qparams[key], QTensor)
    assert not isinstance(qparams["embed/tok"], QTensor)
    assert not isinstance(qparams["final_ln/scale"], QTensor)
    toks = jnp.asarray(rng.integers(0, 96, (2, 16)), jnp.int32)
    lf = model.apply(params, toks)
    lq = model.apply(qparams, toks)
    cos = float(jnp.sum(lf * lq)
                / (jnp.linalg.norm(lf) * jnp.linalg.norm(lq)))
    assert cos > 0.999, cos


def test_quantized_cached_decode_matches_quantized_full_forward(rng):
    """The cache-correctness invariant holds for a quantized store too:
    cached decode must equal the quantized model's full re-forward."""
    model = tiny(scan_layers=True)
    qparams = quantize_params(model.init_params(0))
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    toks = prompt
    expected = []
    for _ in range(5):
        nxt = jnp.argmax(model.apply(qparams, toks)[:, -1], -1)
        expected.append(nxt.astype(jnp.int32))
        toks = jnp.concatenate([toks, nxt[:, None].astype(jnp.int32)], 1)
    got = generate(model, qparams, prompt, 5)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.stack(expected, 1)))


def test_quantized_gqa_decode_runs(rng):
    model = tiny(kv_heads=2)
    qparams = quantize_params(model.init_params(0))
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    logits, cache = prefill(model, qparams, prompt, 32)
    logits2, cache2 = decode_step(
        model, qparams, jnp.argmax(logits, -1).astype(jnp.int32), cache)
    assert bool(jnp.all(jnp.isfinite(logits2)))
    assert int(cache2.length) == 9


def test_int8_kv_cache_decode_tracks_fp_cache(rng):
    """QuantKVCache (generation.py): per-step logits error is bounded and
    prefill logits are bit-identical (the cache isn't read during
    prefill)."""
    from parameter_server_distributed_tpu.models.generation import (
        QuantKVCache)
    model = tiny(scan_layers=True)
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    lf, cf = prefill(model, params, prompt, 32)
    lq, cq = prefill(model, params, prompt, 32, cache_dtype="int8")
    assert isinstance(cq, QuantKVCache)
    assert {part.dtype for part in cq.k + cq.v} == {jnp.dtype(jnp.int8)}
    assert len(cq.k) == len(cq.k_scale) == model.config.n_layers
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lq))
    tok = jnp.argmax(lf, -1).astype(jnp.int32)
    sf, _ = decode_step(model, params, tok, cf)
    sq, cq2 = decode_step(model, params, tok, cq)
    rel = (np.max(np.abs(np.asarray(sf) - np.asarray(sq)))
           / np.max(np.abs(np.asarray(sf))))
    assert rel < 0.05, rel
    assert int(cq2.length) == 9


def test_int8_kv_cache_generate_runs_and_composes_with_weight_quant(rng):
    model = tiny()
    qparams = quantize_params(model.init_params(0))
    prompt = jnp.asarray(rng.integers(0, 96, (2, 8)), jnp.int32)
    out = generate(model, qparams, prompt, 5, cache_dtype="int8")
    assert out.shape == (2, 5)
    assert bool((np.asarray(out) >= 0).all())
    # deterministic: same runner, same inputs
    out2 = generate(model, qparams, prompt, 5, cache_dtype="int8")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_int8_kv_cache_gqa(rng):
    """Value-checks the GQA scale folding: with kv_heads < n_heads the
    k/v scales broadcast over query-head groups — a transposed axis there
    yields finite-but-wrong logits, so bound the per-step error."""
    model = tiny(kv_heads=2)
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 6)), jnp.int32)
    lf, cf = prefill(model, params, prompt, 16)
    lq, cq = prefill(model, params, prompt, 16, cache_dtype="int8")
    tok = jnp.argmax(lf, -1).astype(jnp.int32)
    sf, _ = decode_step(model, params, tok, cf)
    sq, _ = decode_step(model, params, tok, cq)
    rel = (np.max(np.abs(np.asarray(sf) - np.asarray(sq)))
           / np.max(np.abs(np.asarray(sf))))
    assert rel < 0.05, rel
    out_fp = generate(model, params, prompt, 4)
    out_q8 = generate(model, params, prompt, 4, cache_dtype="int8")
    assert out_q8.shape == out_fp.shape


def test_int8_kv_cache_speculative_matches_int8_greedy(rng):
    """Perfect self-draft speculative decoding with int8 caches stays
    token-exact vs int8-cache greedy decoding: K/V depend only on (token,
    position, params), so ragged block writes and single-step writes
    quantize identically."""
    from parameter_server_distributed_tpu.models.generation import (
        speculative_generate_batched)
    model = tiny()
    params = model.init_params(0)
    prompt = jnp.asarray(rng.integers(0, 96, (2, 6)), jnp.int32)
    greedy = generate(model, params, prompt, 6, cache_dtype="int8")
    spec, stats = speculative_generate_batched(
        model, params, model, params, prompt, 6, draft_len=2,
        cache_dtype="int8")
    np.testing.assert_array_equal(np.asarray(spec), np.asarray(greedy))
    assert stats["draft_accept_rate"] == 1.0


def test_store_bytes_reports_shrink():
    model = tiny()
    params = {k: (v.astype(jnp.bfloat16) if v.ndim >= 2 else v)
              for k, v in model.init_params(0).items()}
    as_is, dense = store_bytes(quantize_params(params))
    assert as_is < dense  # int8 + f32 scales < bf16 matrices
