"""Continuous-batching decode server (models/serving.py).

The invariant everything hangs on: a request decoded through the slot
server — padded bucket prefill, cache splice, ragged shared-batch steps,
slot reuse — produces EXACTLY the tokens of a standalone greedy
``generate`` on the same prompt.  Staggered admission and slot recycling
must not perturb other rows.
"""

import itertools
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models import serving
from parameter_server_distributed_tpu.models.generation import generate
from parameter_server_distributed_tpu.models.serving import (DecodeServer,
                                                             _bucket)
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)
from parameter_server_distributed_tpu.obs import legs as obs_legs
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.obs import trace as obs_trace


def tiny(**kw):
    cfg = dict(vocab=96, d_model=48, n_heads=4, n_layers=2, d_ff=96,
               max_seq=128, dtype=jnp.float32)
    cfg.update(kw)
    return Transformer(TransformerConfig(**cfg))


def reference(model, params, prompt, n):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32), n)
    return list(np.asarray(out)[0])


def test_bucket_rounding():
    assert _bucket(1) == 16 and _bucket(16) == 16 and _bucket(17) == 32


def test_single_request_matches_generate(rng):
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 7))
    srv = DecodeServer(model, params, slots=4, max_len=64)
    rid = srv.submit(prompt, max_new_tokens=6)
    results = srv.run_to_completion()
    assert results[rid] == reference(model, params, prompt, 6)


def test_concurrent_requests_each_match_generate(rng):
    model = tiny()
    params = model.init_params(0)
    prompts = [list(rng.integers(0, 96, n)) for n in (5, 9, 17)]
    srv = DecodeServer(model, params, slots=4, max_len=64)
    rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    results = srv.run_to_completion()
    for rid, p in zip(rids, prompts):
        assert results[rid] == reference(model, params, p, 6)


def test_staggered_admission_does_not_perturb_inflight_rows(rng):
    """Admit B while A is mid-decode: both must still match standalone."""
    model = tiny()
    params = model.init_params(0)
    pa = list(rng.integers(0, 96, 6))
    pb = list(rng.integers(0, 96, 11))
    srv = DecodeServer(model, params, slots=2, max_len=64)
    ra = srv.submit(pa, max_new_tokens=8)
    for _ in range(3):
        srv.step()
    rb = srv.submit(pb, max_new_tokens=5)     # splice mid-flight
    results = srv.run_to_completion()
    assert results[ra] == reference(model, params, pa, 8)
    assert results[rb] == reference(model, params, pb, 5)


def test_slot_reuse_after_completion(rng):
    model = tiny()
    params = model.init_params(0)
    pa = list(rng.integers(0, 96, 20))        # long first tenant
    pb = list(rng.integers(0, 96, 4))         # short second tenant
    srv = DecodeServer(model, params, slots=1, max_len=64)
    ra = srv.submit(pa, max_new_tokens=5)
    assert srv._free_slot() is None
    with pytest.raises(RuntimeError):
        srv.submit(pb)
    first = srv.run_to_completion()
    rb = srv.submit(pb, max_new_tokens=5)     # reuses slot 0
    results = srv.run_to_completion()
    assert first[ra] == reference(model, params, pa, 5)
    assert results[rb] == reference(model, params, pb, 5)


def test_eos_frees_slot_early(rng):
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 5))
    ref = reference(model, params, prompt, 8)
    eos = ref[2]                               # force a stop at token 3
    srv = DecodeServer(model, params, slots=2, max_len=64, eos_id=eos)
    rid = srv.submit(prompt, max_new_tokens=8)
    results = srv.run_to_completion()
    assert results[rid] == ref[:3]
    assert srv._free_slot() is not None


def test_prompt_cache_token_exact_and_lru(rng):
    """A repeated prompt served from the radix cache decodes EXACTLY the
    tokens of an uncached server; the byte budget evicts LRU-style; the
    hit counter surfaces in stats; a negative cap is rejected."""
    model = tiny()
    params = model.init_params(0)
    # distinct first tokens: three independent root edges, so each
    # admission pins exactly one 16-bucket K/V row and the byte-budget
    # arithmetic below is row-exact
    prompts = [[i * 7 + 1] + list(rng.integers(0, 96, n))
               for i, n in enumerate((5, 8, 12))]
    plain = DecodeServer(model, params, slots=2, max_len=64)
    expect = {}
    for i, p in enumerate(prompts):
        rid = plain.submit(p, max_new_tokens=5)
        expect[i] = plain.run_to_completion()[rid]

    srv = DecodeServer(model, params, slots=2, max_len=64, prompt_cache=2)
    srv.submit(prompts[0], max_new_tokens=5)
    srv.run_to_completion()
    row_bytes = srv._prefix_tree.bytes   # one 16-bucket row
    assert row_bytes > 0
    srv._prefix_tree.budget_bytes = 2 * row_bytes   # hold exactly 2 rows
    # each prompt twice: second submit of each must hit the cache
    rid = srv.submit(prompts[0], max_new_tokens=5)
    assert srv.run_to_completion()[rid] == expect[0]
    for _ in range(2):
        rid = srv.submit(prompts[1], max_new_tokens=5)
        assert srv.run_to_completion()[rid] == expect[1]
    assert srv.stats["prompt_cache_hits"] == 2
    # byte budget = 2 rows: admitting a 3rd distinct prompt evicts the
    # least-recently-touched node (prompts[0])
    rid = srv.submit(prompts[2], max_new_tokens=5)
    assert srv.run_to_completion()[rid] == expect[2]
    assert srv._prefix_tree.nodes == 2
    assert srv._prefix_tree.bytes <= srv._prefix_tree.budget_bytes
    assert srv.stats["prefix_evictions"] == 1
    # the evicted prompt misses again (and re-evicts to stay in budget)
    hits_before = srv._prompt_hits
    rid = srv.submit(prompts[0], max_new_tokens=5)
    assert srv.run_to_completion()[rid] == expect[0]
    assert srv._prompt_hits == hits_before
    with pytest.raises(ValueError, match="prompt_cache"):
        DecodeServer(model, params, slots=2, max_len=64, prompt_cache=-1)


@pytest.mark.parametrize("cache_dtype", ["native", "int8"])
def test_prefix_cache_extension_token_exact(rng, cache_dtype):
    """Shared-prefix reuse (fleet/, ISSUE 14): a miss whose prompt
    extends a cached prompt forwards ONLY the suffix, and the resulting
    generation matches standalone generate exactly — and matches what a
    fully-prefilled submission of the same prompt produces."""
    model = tiny()
    params = model.init_params(0)
    base = list(rng.integers(0, 96, 7))
    ext = base + list(rng.integers(0, 96, 4))
    longer = ext + list(rng.integers(0, 96, 3))
    srv = DecodeServer(model, params, slots=4, max_len=96,
                       prompt_cache=4, cache_dtype=cache_dtype)
    rid = srv.submit(base, max_new_tokens=5)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     base, 5)
    rid = srv.submit(ext, max_new_tokens=5)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     ext, 5)
    assert srv.stats["prefix_hits"] == 1
    # the extended prompt is itself cached: the LONGEST prefix wins
    # (ext, not base) when a further extension arrives
    rid = srv.submit(longer, max_new_tokens=5)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     longer, 5)
    assert srv.stats["prefix_hits"] == 2
    # and an exact resubmission is a WHOLE-prompt hit, not an extension
    rid = srv.submit(ext, max_new_tokens=5)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     ext, 5)
    assert srv.stats["prompt_cache_hits"] == 1
    assert srv.stats["prefix_hits"] == 2


def test_prefix_cache_overflow_falls_back_to_full_prefill(rng):
    """A combined prefix+suffix row that would overflow max_len must
    fall back to the ordinary full prefill (still token-exact)."""
    model = tiny()
    params = model.init_params(0)
    base = list(rng.integers(0, 96, 30))     # bucket 32
    ext = base + list(rng.integers(0, 96, 10))  # suffix bucket 16: 48>40
    srv = DecodeServer(model, params, slots=2, max_len=46,
                       prompt_cache=4)
    srv.submit(base, max_new_tokens=3)
    srv.run_to_completion()
    rid = srv.submit(ext, max_new_tokens=3)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     ext, 3)
    assert srv.stats["prefix_hits"] == 0  # fell back, correctly


@pytest.mark.parametrize("cache_dtype", ["native", "int8"])
def test_radix_interior_prefix_reuse(rng, cache_dtype):
    """The radix point (ISSUE 20): a prompt sharing a prefix with the
    INTERIOR of a longer cached prompt — a prefix that was never
    admitted as a complete prompt — still rides the suffix-only path
    (the PR 14 whole-prompt scan missed exactly this), splitting the
    cached edge at the divergence token.  Token-exact vs generate."""
    model = tiny()
    params = model.init_params(0)
    long_prompt = list(rng.integers(0, 96, 20))
    fork = long_prompt[:13] + list(rng.integers(0, 96, 6))
    assert fork[13] != long_prompt[13] or fork.__setitem__(
        13, (long_prompt[13] + 1) % 96) or True
    srv = DecodeServer(model, params, slots=2, max_len=96,
                       prompt_cache=8, cache_dtype=cache_dtype)
    srv.submit(long_prompt, max_new_tokens=4)
    srv.run_to_completion()
    rid = srv.submit(fork, max_new_tokens=4)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     fork, 4)
    assert srv.stats["prefix_hits"] == 1
    assert srv._prefix_tree.splits == 1  # edge split at token 13
    # the split shares the long prompt's row — no extra device bytes
    # beyond the two admitted rows
    assert srv._prefix_tree.nodes == 3


@pytest.mark.parametrize("cache_dtype", ["native", "int8"])
def test_radix_multi_hop_extension_token_exact(rng, cache_dtype):
    """Multi-hop chaining: each admission extends from the DEEPEST
    cached ancestor, whose row is itself extension-built — prefix
    buckets compound (16, 32, 48, 64) and every generation stays
    token-exact vs standalone generate."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 7))
    srv = DecodeServer(model, params, slots=2, max_len=128,
                       prompt_cache=8, cache_dtype=cache_dtype)
    for hop, extra in enumerate((0, 4, 5, 3)):
        prompt = prompt + list(rng.integers(0, 96, extra))
        rid = srv.submit(prompt, max_new_tokens=4)
        assert srv.run_to_completion()[rid] == reference(model, params,
                                                         prompt, 4)
        assert srv.stats["prefix_hits"] == hop
    # each hop's combined row is one suffix bucket wider
    node, matched, partial = srv._prefix_tree.lookup(
        tuple(int(t) for t in prompt))
    assert matched == len(prompt) and not partial
    assert int(node.handle.row[0].shape[1]) == 64  # 16+16+16+16


def test_radix_deepest_common_ancestor_wins(rng):
    """With several cached prefixes of the same prompt, extension seeds
    from the DEEPEST one (most reuse, shortest suffix forward)."""
    model = tiny()
    params = model.init_params(0)
    base = list(rng.integers(0, 96, 6))
    mid = base + list(rng.integers(0, 96, 5))
    srv = DecodeServer(model, params, slots=2, max_len=128,
                       prompt_cache=8)
    for p in (base, mid):
        srv.submit(p, max_new_tokens=3)
        srv.run_to_completion()
    before = srv._prefill_tokens
    longer = mid + list(rng.integers(0, 96, 4))
    rid = srv.submit(longer, max_new_tokens=3)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     longer, 3)
    # only the 4-token suffix past `mid` ran a forward — not the
    # 9-token suffix past `base`
    assert srv._prefill_tokens - before == len(longer) - len(mid)
    assert srv.stats["prefix_hits"] == 2  # mid extended base, longer mid


def test_radix_ancestor_path_touch_protects_shared_prefix(rng):
    """ISSUE 20 satellite: a hit through a descendant touches the WHOLE
    ancestor path, so a hot shared prefix is never the LRU victim while
    its descendants live — the PR 14 cache touched only the source
    entry."""
    model = tiny()
    params = model.init_params(0)
    shared = list(rng.integers(0, 96, 6))
    a = shared + list(rng.integers(0, 96, 4))
    b = shared + [(a[6] + 1) % 96] + list(rng.integers(0, 96, 3))
    other = [(shared[0] + 1) % 96] + list(rng.integers(0, 96, 8))
    srv = DecodeServer(model, params, slots=2, max_len=96,
                       prompt_cache=8)
    for p in (shared, other, a, b):
        srv.submit(p, max_new_tokens=3)
        srv.run_to_completion()
    tree = srv._prefix_tree
    # shared's node is tick-fresher than `other` despite being admitted
    # earlier: a and b both touched their ancestor path through it
    snode, sm, _ = tree.lookup(tuple(shared))
    onode, om, _ = tree.lookup(tuple(other))
    assert sm == len(shared) and om == len(other)
    assert snode.tick > onode.tick
    # evict down to just over two rows: `other` (stale) must go before
    # the shared prefix every descendant rides on
    tree.budget_bytes = tree.bytes - 1
    tree.evict_over_budget()
    onode2, om2, _ = tree.lookup(tuple(other))
    assert om2 < len(other)          # the cold entry was the victim
    snode2, sm2, _ = tree.lookup(tuple(shared))
    assert sm2 == len(shared) and snode2.last is not None


def test_prefix_reuse_in_speculative_mode(rng):
    """ISSUE 20 satellite (the PR 14 leftover closed): a speculative
    admission sharing a cached prefix extends BOTH the target and the
    draft K/V row from the tree node (draft rows are cached alongside),
    so it no longer falls back to full prefill — and greedy speculative
    decode stays token-exact vs the plain greedy server."""
    model = tiny()
    params = model.init_params(0)
    draft = tiny(n_layers=1)
    dparams = draft.init_params(1)
    base = list(rng.integers(0, 96, 6))
    ext = base + list(rng.integers(0, 96, 3))
    srv = DecodeServer(model, params, slots=2, max_len=96,
                       prompt_cache=4, draft=draft, draft_params=dparams,
                       draft_len=2)
    assert srv._k > 0  # speculation armed: the old code full-prefilled
    srv.submit(base, max_new_tokens=4)
    srv.run_to_completion()
    rid = srv.submit(ext, max_new_tokens=4)
    plain = DecodeServer(model, params, slots=2, max_len=96)
    prid = plain.submit(ext, max_new_tokens=4)
    assert (srv.run_to_completion()[rid]
            == plain.run_to_completion()[prid])
    assert srv.stats["prefix_hits"] == 1  # suffix-only, both models


def test_prefix_extension_when_speculation_disabled(rng):
    """ISSUE 15 satellite (the PR 14 leftover's smallest edge): a
    speculative server whose depth controller has speculation OFF
    (k == 0 — no draft row would be seeded anyway) falls back to
    plain-mode shared-prefix extension for the prompt phase, token-exact
    vs standalone generate; re-arming speculation later still works —
    the k==0-era tree nodes carry no draft row, and the radix path
    backfills the draft side with a full draft prefill."""
    model = tiny()
    params = model.init_params(0)
    draft = tiny(n_layers=1)
    dparams = draft.init_params(1)
    base = list(rng.integers(0, 96, 6))
    ext = base + list(rng.integers(0, 96, 3))
    srv = DecodeServer(model, params, slots=2, max_len=96,
                       prompt_cache=4, draft=draft, draft_params=dparams,
                       draft_len=2)
    srv._k = 0  # the adaptive controller concluded the draft cannot pay
    rid = srv.submit(base, max_new_tokens=4)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     base, 4)
    rid = srv.submit(ext, max_new_tokens=4)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     ext, 4)
    assert srv.stats["prefix_hits"] == 1
    # re-arm: the next extending admission still rides the radix path —
    # the k==0-era ancestor carries no draft row, so the draft side
    # (only) full-prefills while the target row suffix-extends
    # (ISSUE 20: the k>0 full-prefill fallback is gone)
    srv._k = 2
    longer = ext + list(rng.integers(0, 96, 3))
    rid = srv.submit(longer, max_new_tokens=4)
    assert srv.run_to_completion()[rid] == reference(model, params,
                                                     longer, 4)
    assert srv.stats["prefix_hits"] == 2


def test_prompt_cache_speculative_and_int8(rng):
    """The cache composes with speculative mode (draft row cached too)
    and the int8 KV cache — hits stay token-exact in both."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 7))
    ref = reference(model, params, prompt, 6)
    srv = DecodeServer(model, params, slots=2, max_len=64,
                       draft=model, draft_params=params, draft_len=2,
                       prompt_cache=4)
    for expect_hits in (0, 1):
        rid = srv.submit(prompt, max_new_tokens=6)
        assert srv.run_to_completion()[rid] == ref
        assert srv._prompt_hits == expect_hits

    q = DecodeServer(model, params, slots=2, max_len=64,
                     cache_dtype="int8", prompt_cache=4)
    first = q.submit(prompt, max_new_tokens=6)
    a = q.run_to_completion()[first]
    second = q.submit(prompt, max_new_tokens=6)
    assert q.run_to_completion()[second] == a
    assert q._prompt_hits == 1


def test_per_request_stop_tokens(rng):
    """submit(stop=...) finishes THAT request at its stop token while a
    concurrent request sails past the same token id."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 5))
    ref = reference(model, params, prompt, 8)
    stop = ref[2]                              # cut request A at token 3
    srv = DecodeServer(model, params, slots=2, max_len=64)
    ra = srv.submit(prompt, max_new_tokens=8, stop=[stop])
    rb = srv.submit(prompt, max_new_tokens=8)  # same prompt, no stop
    results = srv.run_to_completion()
    assert results[ra] == ref[:3]
    assert results[rb] == ref


def test_per_request_temperature_mixed_batch(rng):
    """A greedy request and a sampled request share one batch: the greedy
    row must stay token-exact vs standalone generate (sampling other rows
    may not perturb it), the sampled row must actually differ, and no
    recompile happens per distinct temperature (one step runner)."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 6))
    ref = reference(model, params, prompt, 10)
    srv = DecodeServer(model, params, slots=2, max_len=64, seed=3)
    ra = srv.submit(prompt, max_new_tokens=10)                    # greedy
    rb = srv.submit(prompt, max_new_tokens=10, temperature=5.0)   # hot
    results = srv.run_to_completion()
    assert results[ra] == ref
    assert results[rb] != ref  # temperature 5 on a random-init model

    # default server temperature still applies when submit doesn't set one
    srv2 = DecodeServer(model, params, slots=1, max_len=64,
                        temperature=0.0)
    rc = srv2.submit(prompt, max_new_tokens=10)
    assert srv2.run_to_completion()[rc] == ref


def test_speculative_rejects_per_request_temperature(rng):
    """The speculative accept rule is compiled for the server temperature,
    so submit() must reject a differing per-request value (and accept a
    matching one)."""
    model = tiny()
    draft = tiny(n_layers=1)
    params = model.init_params(0)
    dparams = draft.init_params(1)
    srv = DecodeServer(model, params, slots=2, max_len=64,
                       draft=draft, draft_params=dparams, draft_len=2)
    with pytest.raises(ValueError, match="per-request temperature"):
        srv.submit([1, 2, 3], temperature=0.7)
    rid = srv.submit([1, 2, 3], max_new_tokens=4, temperature=0.0)
    assert rid in srv.run_to_completion()


def test_int8_cache_server_matches_int8_generate(rng):
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 6))
    ref = list(np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32), 5,
        cache_dtype="int8"))[0])
    srv = DecodeServer(model, params, slots=2, max_len=64,
                       cache_dtype="int8")
    rid = srv.submit(prompt, max_new_tokens=5)
    results = srv.run_to_completion()
    assert results[rid] == ref


@pytest.mark.parametrize("cache_dtype", ["native", "int8"])
def test_mesh_tp_serving_token_exact(rng, cache_dtype):
    """Multi-chip serving: the same requests through a data×tensor-sharded
    DecodeServer (params under the Megatron rule, cache batch/heads
    sharded — int8 scale leaves included, GSPMD-partitioned step) produce
    exactly the single-device tokens — staggered admission included."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh

    model = tiny(d_model=64, n_heads=4)   # head_dim 16; tp=2 splits heads
    params = model.init_params(0)
    pa = list(rng.integers(0, 96, 6))
    pb = list(rng.integers(0, 96, 9))

    def drive(srv):
        ra = srv.submit(pa, max_new_tokens=6)
        for _ in range(2):
            srv.step()
        rb = srv.submit(pb, max_new_tokens=4)
        out = srv.run_to_completion()
        return out[ra], out[rb]

    base = drive(DecodeServer(model, params, slots=4, max_len=64,
                              cache_dtype=cache_dtype))
    mesh = build_mesh(MeshConfig(data=2, tensor=2, fsdp=2))
    sharded = drive(DecodeServer(model, params, slots=4, max_len=64,
                                 cache_dtype=cache_dtype, mesh=mesh))
    assert sharded == base


def test_mesh_serving_with_int8_weights_token_exact(rng):
    """The full int8 serving stack over a mesh: QTensor weights placed
    with their scales following the matrix's output sharding, int8 slot
    cache — tokens equal the single-device int8 server's."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models.quant import (
        QTensor, quantize_params)
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh

    model = tiny(d_model=64, n_heads=4)
    qparams = quantize_params(model.init_params(0))
    prompt = list(rng.integers(0, 96, 7))

    def drive(srv):
        rid = srv.submit(prompt, max_new_tokens=5)
        return srv.run_to_completion()[rid]

    base = drive(DecodeServer(model, qparams, slots=2, max_len=64,
                              cache_dtype="int8"))
    mesh = build_mesh(MeshConfig(data=2, tensor=2, fsdp=2))
    srv = DecodeServer(model, qparams, slots=2, max_len=64,
                       cache_dtype="int8", mesh=mesh)
    # scale rides the matrix's output sharding (tensor axis)
    wq = srv.params["layer0/attn/wq"]
    assert isinstance(wq, QTensor)
    assert wq.scale.sharding.spec == wq.q.sharding.spec[-1:]
    assert drive(srv) == base


@pytest.mark.parametrize("draft_kind", ["self", "random"])
def test_speculative_serving_token_exact(rng, draft_kind):
    """Speculative continuous batching is token-exact vs the plain greedy
    server for ANY draft (greedy acceptance commits exactly the target's
    greedy tokens): a perfect self-draft accepts everything, a random-init
    draft accepts ~nothing — outputs must be identical either way,
    staggered admission and slot reuse included."""
    model = tiny()
    params = model.init_params(0)
    if draft_kind == "self":
        draft, dparams = model, params
    else:
        draft = tiny(n_layers=1)
        dparams = draft.init_params(7)
    pa = list(rng.integers(0, 96, 6))
    pb = list(rng.integers(0, 96, 11))
    pc = list(rng.integers(0, 96, 4))

    def drive(srv):
        ra = srv.submit(pa, max_new_tokens=7)
        srv.step()
        rb = srv.submit(pb, max_new_tokens=5)
        out = dict(srv.run_to_completion())
        rc = srv.submit(pc, max_new_tokens=6)     # slot reuse
        out.update(srv.run_to_completion())
        return out[ra], out[rb], out[rc]

    base = drive(DecodeServer(model, params, slots=2, max_len=64))
    spec = drive(DecodeServer(model, params, slots=2, max_len=64,
                              draft=draft, draft_params=dparams,
                              draft_len=3))
    assert spec == base


def test_speculative_serving_sampling_preserves_distribution():
    """T>0 speculative serving applies the rejection rule: empirical
    first-token frequencies over many seeded servers match the target's
    own softmax (tiny vocab, 4-sigma) — the serving analogue of the
    one-shot decoder's distribution test."""
    import jax
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    vocab = 8
    target = Transformer(TransformerConfig(
        vocab=vocab, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq=64, dtype=jnp.float32))
    draft = Transformer(TransformerConfig(
        vocab=vocab, d_model=8, n_heads=1, n_layers=1, d_ff=16,
        max_seq=64, dtype=jnp.float32))
    tparams, dparams = target.init_params(0), draft.init_params(3)
    prompt = [2, 2, 2, 2]
    counts0 = np.zeros(vocab)
    counts1 = np.zeros(vocab)
    reps, slots = 48, 8
    for seed in range(reps):
        srv = DecodeServer(target, tparams, slots=slots, max_len=32,
                           temperature=1.0, seed=seed,
                           draft=draft, draft_params=dparams, draft_len=2)
        rids = [srv.submit(prompt, max_new_tokens=2)
                for _ in range(slots)]
        out = srv.run_to_completion()
        for rid in rids:
            counts0[out[rid][0]] += 1
            counts1[out[rid][1]] += 1
    n = reps * slots
    from parameter_server_distributed_tpu.models.generation import prefill
    logits, _ = prefill(target, tparams,
                        jnp.asarray([prompt], jnp.int32), 8)
    p0 = np.asarray(jax.nn.softmax(logits[0]))
    # position 0 is submit()'s direct target sample; position 1 is the
    # ROUND's accept/resample product — its ground truth marginalizes
    # over the first token: p1[j] = sum_i p0[i] * P(j | prompt+[i])
    p1 = np.zeros(vocab)
    for i in range(vocab):
        li, _ = prefill(target, tparams,
                        jnp.asarray([prompt + [i]], jnp.int32), 8)
        p1 += p0[i] * np.asarray(jax.nn.softmax(li[0]))
    for freq, p in ((counts0 / n, p0), (counts1 / n, p1)):
        sigma = np.sqrt(p * (1 - p) / n)
        np.testing.assert_array_less(np.abs(freq - p), 4 * sigma + 0.01)


def test_serving_stats(rng):
    """Observability counters: request/step/token accounting on the plain
    server; a perfect self-draft reports acceptance 1.0 and k+1
    tokens/round while requests are saturating the slots."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 5))
    srv = DecodeServer(model, params, slots=2, max_len=64)
    rid = srv.submit(prompt, max_new_tokens=4)
    srv.run_to_completion()
    s = srv.stats
    assert s["requests_admitted"] == s["requests_completed"] == 1
    assert s["steps"] == 3          # first token came from prefill
    assert s["tokens_emitted"] == 3
    assert "draft_accept_rate" not in s

    spec = DecodeServer(model, params, slots=1, max_len=64,
                        draft=model, draft_params=params, draft_len=3,
                        adaptive_draft=False)  # pin k: exact round counts
    spec.submit(prompt, max_new_tokens=8)
    spec.run_to_completion()
    s = spec.stats
    assert s["draft_accept_rate"] == 1.0
    assert s["requests_completed"] == 1
    # 7 round-produced tokens (first came from prefill) over 2 rounds:
    # full k+1=4 then truncated at max_new
    assert s["tokens_per_round"] == 3.5


def test_speculative_serving_validation(rng):
    model = tiny()
    params = model.init_params(0)
    with pytest.raises(ValueError, match="top_k/top_p"):
        DecodeServer(model, params, slots=2, max_len=64, top_k=5,
                     draft=model, draft_params=params)
    with pytest.raises(ValueError, match="draft_params"):
        DecodeServer(model, params, slots=2, max_len=64, draft=model)
    other = tiny(vocab=64)
    with pytest.raises(ValueError, match="vocab"):
        DecodeServer(model, params, slots=2, max_len=64, draft=other,
                     draft_params=other.init_params(0))


def test_prompt_validation(rng):
    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=1, max_len=32)
    with pytest.raises(ValueError):
        srv.submit([])
    with pytest.raises(ValueError):
        srv.submit(list(rng.integers(0, 96, 30)), max_new_tokens=10)


def test_speculative_serving_adaptive_depth(rng):
    """adaptive_draft: the server's depth controller follows acceptance —
    a perfect self-draft deepens to the cap, a random draft drops to 1 —
    while outputs stay token-exact vs the plain greedy server."""
    model = tiny()
    params = model.init_params(0)
    prompts = [list(rng.integers(0, model.config.vocab, 5))
               for _ in range(6)]

    def run(**kwargs):
        srv = DecodeServer(model, params, slots=2, max_len=64, **kwargs)
        pending = list(prompts)
        while pending or not srv.idle:
            while pending and srv.has_free_slot:
                srv.submit(pending.pop(0), max_new_tokens=24)
            srv.step()
        return srv

    plain = run()
    perfect = run(draft=model, draft_params=params, draft_len=4,
                  adaptive_draft=True, draft_cost_ratio=0.3)
    assert perfect.stats["draft_depth"] == 4
    junk = tiny(n_layers=1)
    junky = run(draft=junk, draft_params=junk.init_params(99),
                draft_len=4, adaptive_draft=True, draft_cost_ratio=0.3)
    # accept ~0: the controller disables speculation (k=0) and the
    # server switches to plain greedy rounds mid-flight
    assert junky.stats["draft_depth"] == 0
    for rid in range(6):
        want = plain.result(rid)      # result() pops — read once
        assert perfect.result(rid) == want
        assert junky.result(rid) == want
    # pinned mode keeps the configured depth
    pinned = run(draft=junk, draft_params=junk.init_params(99),
                 draft_len=3, adaptive_draft=False)
    assert pinned.stats["draft_depth"] == 3


def test_step_many_token_exact_vs_step_loop(rng):
    """Fused multi-round serving == the step() loop token for token:
    greedy and per-request-temperature sampling (identical rng split
    sequence), a stop token retiring a request MID-fused-block, and a
    mixed-length batch (the round count clamps to the minimum remaining
    budget)."""
    model = tiny()
    params = model.init_params(0)
    prompts = [list(rng.integers(0, 96, 5)) for _ in range(3)]

    def run(fused, stops=(), temps=()):
        srv = DecodeServer(model, params, slots=2, max_len=96, seed=3)
        results = {}
        pending = list(enumerate(prompts))
        while pending or not srv.idle:
            while pending and srv.has_free_slot:
                i, p = pending.pop(0)
                rid = srv.submit(
                    p, max_new_tokens=10 + 3 * i,       # mixed budgets
                    stop=list(stops),
                    temperature=(temps[i % len(temps)] if temps
                                 else None))
            (srv.step_many(4) if fused else srv.step())
        for rid in srv.finished():
            results[rid] = srv.result(rid)
        return srv, results

    base_srv, base = run(fused=False)
    fused_srv, got = run(fused=True)
    assert got == base
    assert fused_srv.stats["steps"] == base_srv.stats["steps"]

    # sampling path: same rng stream through the fused scan
    _, base_s = run(fused=False, temps=(0.8, 0.0))
    _, got_s = run(fused=True, temps=(0.8, 0.0))
    assert got_s == base_s

    # a stop token that fires mid-block: truncation must match exactly
    stop_tok = base[0][1]
    _, base_stop = run(fused=False, stops=(stop_tok,))
    _, got_stop = run(fused=True, stops=(stop_tok,))
    assert got_stop == base_stop


def test_step_many_speculative_falls_back(rng):
    """With an active draft the fused path defers to the adaptive spec
    round (host decisions between rounds); output stays exact."""
    model = tiny()
    params = model.init_params(0)
    prompt = list(rng.integers(0, 96, 5))

    def run(fused):
        srv = DecodeServer(model, params, slots=1, max_len=96,
                           draft=model, draft_params=params, draft_len=3,
                           adaptive_draft=False)
        rid = srv.submit(prompt, max_new_tokens=8)
        while not srv.idle:
            (srv.step_many(4) if fused else srv.step())
        return srv.result(rid)

    assert run(True) == run(False)


# --------------------------------------- an admission by its legs (ISSUE 38)
ADMIT_LEGS = ("lookup", "forward", "tree", "first_token", "splice")
# the stepped clock of the legs' arithmetic: a read is this much later than
# the one before it (a leg of a thousand reads would be a slow leg), and an
# admission's block may hold this many reads that no leg of it counts
TICK = 1e-4
BETWEEN_LEGS = 16


def admit_histograms() -> dict:
    """(count, sum) of ``serve.admit_s`` and of each leg's histogram."""
    snap = obs_stats.REGISTRY.snapshot()["histograms"]
    names = ["serve.admit_s", "serve.admit_device_s"] + [
        f"serve.admit_{leg}_s" for leg in ADMIT_LEGS]
    return {name: (snap[name]["count"], snap[name]["sum"])
            for name in names}


@pytest.mark.parametrize("path,legs_run", [
    ("extension", ADMIT_LEGS),
    ("prefill", ADMIT_LEGS),
    # a whole-prompt hit replays the row: no forward, nothing to insert
    ("hit", ("lookup", "first_token", "splice")),
    # three chunks through _chunk_runner are ONE forward leg
    ("chunked", ADMIT_LEGS),
])
def test_admission_legs_once_each_and_add_up_to_the_block(
        rng, monkeypatch, path, legs_run):
    if path == "chunked":
        monkeypatch.setattr(serving, "_PREFILL_WHOLE", 0)
        monkeypatch.setattr(serving, "_PREFILL_CHUNK", 8)
    # wide enough that an admission's forward takes milliseconds on a
    # CPU, as it does on the chip: the legs' own clocks then weigh little
    model = tiny(d_model=512, n_heads=8, n_layers=6, d_ff=2048)
    srv = DecodeServer(model, model.init_params(0), slots=4, max_len=96,
                       prompt_cache=8)
    shared = list(rng.integers(0, 96, 20))
    firsts = iter(range(96))     # no two suffixes share their first token

    def prompt():
        if path == "extension":
            return shared + [next(firsts)] + list(rng.integers(0, 96, 4))
        if path == "hit":
            return shared
        return [next(firsts)] + list(rng.integers(0, 96, 19))   # unshared

    # every program the path needs, and the shared prefix in the tree
    for tokens in (shared, prompt(), prompt()):
        srv.submit(tokens, max_new_tokens=2)
        srv.run_to_completion()
    # the legs' clock from here on is one the test steps itself: every
    # read of obs/trace.py's ``perf_counter`` is one TICK later than the
    # read before it, so a leg's time is the clock reads inside it and the
    # arithmetic below holds whatever the host is doing (the wall clock's
    # own held it within 2 ms an admission until six workers of the suite
    # took the thread's core BETWEEN two legs for longer than that)
    reads = itertools.count()
    monkeypatch.setattr(obs_trace, "time", types.SimpleNamespace(
        time=time.time, perf_counter=lambda: next(reads) * TICK))
    before = admit_histograms()
    stats = dict(srv.stats)
    srv.slow_legs.clear()       # (the warm-up's: every compile is one)
    admissions = 3
    for _ in range(admissions):
        srv.submit(prompt(), max_new_tokens=2)
        srv.run_to_completion()
    after = admit_histograms()
    moved = {name: after[name][0] - before[name][0] for name in after}
    assert moved == {
        "serve.admit_s": admissions, "serve.admit_device_s": admissions,
        **{f"serve.admit_{leg}_s": admissions * (leg in legs_run)
           for leg in ADMIT_LEGS}}
    took = {"extension": "prefix_hits", "hit": "prompt_cache_hits"}.get(path)
    if took:
        assert srv.stats[took] - stats[took] == admissions
    else:
        assert srv.stats["prefill_tokens"] - stats["prefill_tokens"] == \
            admissions * 20
    # disjoint, and with the slot bookkeeping they cover the block; the
    # three under serve/admit/device cover that one.  In ticks: a leg that
    # ran inside another, or twice, would make the legs MORE than their
    # block; what no leg counts is the reads between two legs, the next
    # one's opening each time (a few an admission: each leg reads the clock
    # twice, and a round landed inside the block reads it for its own legs)
    spent = {name: round((after[name][1] - before[name][1]) / TICK)
             for name in after}
    legs = sum(spent[f"serve.admit_{leg}_s"] for leg in ADMIT_LEGS)
    block = spent["serve.admit_s"]
    assert len(legs_run) * admissions <= legs < block
    assert block - legs <= BETWEEN_LEGS * admissions
    under_device = sum(spent[f"serve.admit_{leg}_s"]
                       for leg in ("forward", "tree", "first_token"))
    block = spent["serve.admit_device_s"]
    assert under_device < block <= under_device + BETWEEN_LEGS * admissions
    # no leg is slow by this test's own making: none built a program (the
    # warm-up above has every one) and none spent the limit on the thread's
    # own CPU.  A leg that only WAITED for a core on a loaded host is the
    # host's, and is what the recorder is for (its cpu_s says so)
    own = [leg for leg in srv.slow_legs
           if leg["programs_built"] or leg["cpu_s"] > obs_legs.SLOW_LEG_S]
    assert not own, own


def test_a_refused_forward_leaves_no_span_open(rng, monkeypatch):
    """``serve/admit/device`` is a ``with`` block: a runner that raises
    leaves the thread's span stack as it found it, and the histograms with
    their observation."""
    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=2, max_len=64,
                       prompt_cache=4)

    def refusing(*args, **kwargs):
        def run(*a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return run

    monkeypatch.setattr(serving, "_prefill_runner", refusing)
    before = admit_histograms()
    obs_trace.clear()
    obs_trace.enable(True)
    try:
        with obs_trace.span("test/outer"):
            found = obs_trace.current()
            with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
                srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=2)
            assert obs_trace.current() == found
        assert obs_trace.current() is None
        spans = [s["name"] for s in obs_trace.spans()]
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
    assert spans == ["serve/admit/lookup", "serve/admit/forward",
                     "serve/admit/device", "serve/admit", "test/outer"]
    after = admit_histograms()
    assert {name: after[name][0] - before[name][0] for name in after} == {
        "serve.admit_s": 1, "serve.admit_device_s": 1,
        "serve.admit_lookup_s": 1, "serve.admit_forward_s": 1,
        "serve.admit_tree_s": 0, "serve.admit_first_token_s": 0,
        "serve.admit_splice_s": 0}
    # nothing was admitted, and the next request is
    assert srv.idle and srv._admission is None
    monkeypatch.undo()
    rid = srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=2)
    assert len(srv.run_to_completion()[rid]) == 2


def test_a_dropped_server_is_freed_without_the_collector(rng):
    """The slow-leg watch asks the server what it held; it must not keep
    it (and the slot cache, the prefix rows, the weights) alive: the
    benchmark drops the server to make room on the device and does not
    wait for a collection."""
    import gc
    import weakref

    model = tiny()
    srv = DecodeServer(model, model.init_params(0), slots=2, max_len=64,
                       prompt_cache=4)
    srv.submit(list(rng.integers(0, 96, 9)), max_new_tokens=3)
    srv.run_to_completion()
    cache = weakref.ref(srv._cache.k[0])
    gone = weakref.ref(srv)
    gc.disable()
    try:
        del srv
        assert gone() is None and cache() is None
    finally:
        gc.enable()
