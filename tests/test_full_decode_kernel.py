"""ops/pallas/full_decode.py (interpreted here, at a block of 128
positions) against the plain path a decode round's full layers take
(``generation._dense_cache_attention``): alone over every layout, packing
and group size the serving cells have, and inside ``decode_block`` with
the arm forced to ``kernel``."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models import generation, transformer
from parameter_server_distributed_tpu.models.transformer import (
    LayerSpec, Transformer, TransformerConfig)
from parameter_server_distributed_tpu.ops.pallas import full_decode

BLOCK = 128          # of this file: the module's own interpreted are slow
MAX_LEN = 3 * BLOCK
# one call holds every length that matters: a single position, a block
# but one, a block exactly, a block and one, the whole part, and nothing
LENGTHS = (1, BLOCK - 1, BLOCK, BLOCK + 1, MAX_LEN, 0)
# what tests/ hold the latent kernel to in float32 (test_kimi_linear.py);
# in bfloat16 two roundings of a result of a few units
CLOSE = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2}


@pytest.fixture
def short_blocks(monkeypatch):
    monkeypatch.setattr(full_decode, "LARGEST_BLOCK", BLOCK)


@pytest.mark.parametrize("part,itemsize,by_head,each,block", [
    ((12, 4096, 30, 128), 2, True, 1, 256),       # Olmo Hybrid
    ((32, 4096, 8, 128), 2, False, 8, 256),       # K-EXAONE
    ((16, 16384, 4, 128), 2, False, 7, 512),      # SmallThinker
    ((64, 4096, 4, 128), 2, False, 8, 512),       # LFM2
    ((32, 1024, 8, 128), 2, False, 2, 256),       # GPT-2 medium
    ((32, 1024, 8, 128), 4, False, 2, 128),       # the same in float32
    ((12, 1024, 6, 128), 2, True, 2, 512),        # a served gpt2-small
    ((8, 2048, 1, 128), 2, False, 16, 512),       # one row: the largest
    ((8, 2048, 40, 128), 2, False, 1, 128)])      # many rows: the smallest
def test_a_steps_positions_follow_the_parts_shape(part, itemsize, by_head,
                                                  each, block):
    """``block_positions``: laid by head, 256 positions where a row of
    heads has one query row and 512 otherwise; laid by position, half a
    megabyte of K, from 128 to 512 positions."""
    assert full_decode.block_positions(part, itemsize, by_head, each) == block
    assert part[1] % block == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("by_head", [True, False],
                         ids=["by_head", "by_position"])
@pytest.mark.parametrize("groups,pack", [(1, 1), (4, 1), (7, 1), (1, 2),
                                         (4, 2)])
def test_the_kernel_is_the_dense_attention_over_live_positions(
        monkeypatch, short_blocks, dtype, by_head, groups, pack):
    """Six lanes of unequal length in one call (one of them empty), the
    rows past a lane's length filled with NaN: the kernel's result is the
    dense path's over the live positions, nothing stale reaches it, and a
    lane that holds nothing comes back as zeros."""
    rows, head_dim = 3, 128 // pack
    c = types.SimpleNamespace(
        kv_heads=rows * pack, kv_groups=groups, head_dim=head_dim,
        n_heads=rows * pack * groups, dtype=dtype)
    rng = np.random.default_rng(groups * 10 + pack)
    lanes = len(LENGTHS)
    q = jnp.asarray(rng.normal(size=(lanes, 1, c.n_heads, head_dim)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(lanes, MAX_LEN, rows, 128)), dtype)
            for _ in range(2))
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    assert full_decode.fits((lanes, rows, pack * groups, 128), k.shape)
    live = jnp.arange(MAX_LEN)[None, :] < lengths[:, None]
    want = np.asarray(generation._dense_cache_attention(
        c, q, k, v, live[:, None, None, None, :], None), np.float32)
    stale = live[:, :, None, None]
    monkeypatch.setattr(generation, "_lies_by_head", lambda rows: by_head)
    got = np.asarray(generation._kernel_cache_attention(
        c, q, jnp.where(stale, k, jnp.nan), jnp.where(stale, v, jnp.nan),
        lengths), np.float32)
    assert got.shape == want.shape == (lanes, 1, c.n_heads, head_dim)
    assert np.max(np.abs(got[:-1] - want[:-1])) < CLOSE[dtype]
    assert np.max(np.abs(want[:-1])) > 0.5
    assert not got[-1].any()


@pytest.mark.parametrize("kv_heads,head_dim,groups", [
    (3, 128, 1), (4, 128, 2), (6, 64, 1), (4, 64, 4)],
    ids=["3x128_by_head", "4x128_by_position", "6x64_packed_by_head",
         "4x64_packed_by_position"])
def test_a_round_through_the_kernel_gives_the_dense_rounds_logits(
        monkeypatch, short_blocks, kv_heads, head_dim, groups):
    """``decode_block`` with the arm forced to ``kernel`` (ragged lanes, as
    a server's round, and the scan of ``generate``'s single length) gives
    the logits the ``dense`` arm gives."""
    config = TransformerConfig(
        vocab=64, d_model=32, n_heads=kv_heads * groups, n_kv_heads=kv_heads,
        head_dim=head_dim, n_layers=2, d_ff=48, max_seq=MAX_LEN,
        dtype=jnp.float32, pattern=(LayerSpec(),))
    model = Transformer(config)
    params = model.init_params(3)
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 64, (3, BLOCK + 9)), jnp.int32)
    _, cache = generation.prefill(model, params, tokens[:, :-1], MAX_LEN)
    pack = generation.heads_per_row(kv_heads, head_dim)
    assert cache.k[0].shape == (3, MAX_LEN, kv_heads // pack, 128)

    def rounds():
        ragged = generation.decode_block(
            model, params, tokens[:, -1:], cache,
            lengths=jnp.asarray([BLOCK + 8, 5, BLOCK]))[0]
        return np.asarray(ragged), np.asarray(generation.decode_block(
            model, params, tokens[:, -1:], cache)[0])

    dense = rounds()
    arm = functools.partial(transformer.round_arm, "softmax")
    q_shape = (3, 1, kv_heads * groups, head_dim)
    assert arm(q_shape, cache.k[0].shape, jnp.float32) == "dense"  # no TPU
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert arm(q_shape, cache.k[0].shape, jnp.float32) == "kernel"
    calls = []
    kernel = generation._kernel_cache_attention
    monkeypatch.setattr(
        generation, "_kernel_cache_attention",
        lambda *args: calls.append(1) or kernel(*args))
    for got, want in zip(rounds(), dense):
        assert np.max(np.abs(got - want)) < 1e-5
        assert np.max(np.abs(want)) > 0.1
    assert len(calls) == 2 * config.n_layers
