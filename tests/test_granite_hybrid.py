"""Granite 4.0-H's layers through the model, the cache and the server,
against the plain reference (``perfbench/reference/granite_hybrid.py``), at a
small size in float32 on the CPU, LOGITS and not tokens: a state-space
layer in its dual form (``ops/ssd.py``: one decay a head and position, keys
and queries a group's heads share, a state [H, P, N] with H != P != N)
behind one convolution with a bias, the norm after the gate; grouped-query
attention without rotary whose scores are multiplied by ``attn_scale``;
the three muP scalars and a tied head.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from parameter_server_distributed_tpu.models import (  # noqa: E402
    generation, serving, transformer)
from parameter_server_distributed_tpu.models.transformer import (  # noqa: E402
    LayerSpec, Transformer, TransformerConfig, transformer_rule)
from parameter_server_distributed_tpu.ops.pallas import (  # noqa: E402
    full_decode)
from parameter_server_distributed_tpu.ops.ssd import ssd  # noqa: E402
from perfbench import correct  # noqa: E402
from perfbench.families import granite_hybrid  # noqa: E402
from perfbench.reference import granite_hybrid as reference  # noqa: E402

SEQ = 72
# float32 logits of the program against the reference's, over the standard
# deviation of the reference's logits (a tied head over a small embedding:
# the logits themselves are a few thousandths)
CLOSE = 1e-3


def _apart(got, want) -> float:
    """The largest difference over the deviation of ``want``."""
    want = np.asarray(want, np.float32)
    return _diff(got, want) / float(np.std(want))


def _diff(got, want) -> float:
    """The largest difference."""
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))
FILE = os.path.join(ROOT, "perfbench", "configs", "granite-4.0-h-micro.json")


def _configuration(**changes) -> dict:
    with open(FILE) as handle:
        config = granite_hybrid.tiny(json.load(handle))
    config.update(changes)
    return config


def _small(**changes):
    """(configuration, model, weights, the reference's weights)."""
    config = _configuration(**changes)
    model = granite_hybrid.model(config)
    params = granite_hybrid.make_weights(model, 3)
    return config, model, params, granite_hybrid.reference_weights(config,
                                                                   params)


@pytest.fixture(scope="module")
def small():
    return _small()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (2, SEQ)).astype(
        np.int32)


def _expected(small, tokens):
    config, _, _, weights = small
    return np.asarray(jax.jit(lambda w, t: granite_hybrid.reference_forward(
        config, w, t))(weights, tokens))


@pytest.fixture(scope="module")
def expected(small, tokens):
    return _expected(small, tokens)


def _reference_logits(small, sequence):
    return _expected(small, np.asarray(sequence, np.int32)[None])[0]


# --------------------------------------------------------------- the model
def test_the_model_is_ssm_around_attention_without_rotary(small):
    config, model, params, _ = small
    c = model.config
    assert [c.layer_spec(i).mixer for i in range(c.n_layers)] == [
        "ssm", "ssm", "softmax", "ssm"]
    assert c.prologue == () and c.norm_placement == "pre"
    assert (c.layer_spec(2).rope, c.layer_spec(2).ffn) == (False, "mlp")
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups) == (
        12, 8, 16, 1)
    assert c.ssm_dims == (96, 96 + 2 * 16)
    assert (c.attn_scale, c.embed_scale, c.residual_scale, c.logit_scale) \
        == (0.015625, 12.0, 0.22, 0.125)
    assert c.query_gain == pytest.approx(0.015625 * 12 ** 0.5)
    assert params["layer0/ssm/in_proj"].shape == (48, 96 + 128 + 12)
    assert params["layer0/ssm/conv/kernel"].shape == (4, 128)
    assert params["layer0/ssm/conv/bias"].shape == (128,)
    assert params["layer0/ssm/skip"].shape == (12,)
    assert params["layer0/ssm/norm/scale"].shape == (96,)
    assert params["layer2/attn/wk"].shape == (48, 24)
    assert "layer2/ssm/in_proj" not in params
    # the head is tied: a second matrix that holds the transpose
    assert np.array_equal(params["lm_head/w"], params["embed/tok"].T)
    assert generation.state_shape(model)[0] == (
        ((3, 128), jnp.float32), ((12, 8, 16), jnp.float32))
    assert model.num_params() == granite_hybrid.stored_params(config)
    assert model.num_params() == sum(x.size for x in params.values())
    # two products with a [8, 16] state a head, an ssm layer
    flops = model.flops_per_sample()
    no_state = 6.0 * model.num_params() * c.max_seq \
        + 12.0 * c.d_model * c.max_seq * c.max_seq
    assert flops == pytest.approx(no_state + 3 * 12.0 * 96 * 16 * c.max_seq)


def test_the_published_model_counts_its_parameters_and_its_cache():
    with open(FILE) as handle:
        config = json.load(handle)
    assert config["reduced"] == [] and config["omitted"] == []
    assert granite_hybrid.layer_params(config, 0) == 76_182_976
    assert granite_hybrid.layer_params(config, 5) == 60_821_504
    assert granite_hybrid.param_count(config) == 3_191_396_096 \
        == config["published"]["parameters"]
    model = granite_hybrid.model(config)
    assert model.num_params() == granite_hybrid.stored_params(config) \
        == config["parameters"] == 3_396_916_992
    c = model.config
    assert c.layers_of("softmax") == (5, 15, 25, 35)
    assert len(c.layers_of("ssm")) == 36 and c.query_gain == 0.125
    cache = jax.eval_shape(lambda: generation.init_cache(model, 64, 2048))

    def nbytes(parts):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(parts))

    lane = granite_hybrid.slot_bytes(config, 2048)
    assert nbytes(cache.state) == 64 * lane["state"] == 64 * 36 * 2_123_264
    assert nbytes((cache.k, cache.v)) == 64 * lane["full"] \
        == 64 * 4 * 2048 * 2048
    # the two byte functions the roofline metrics name
    assert granite_hybrid.ssd_state_bytes(config, 10) == 10 * 2 * 2_097_152
    assert granite_hybrid.linear_attn_bytes(config, 10) == 10 * 2 * 2_123_264
    assert serving._builds_few(model) and serving._suffix_floor(model) == 256
    assert serving._prefills_whole(model, 1024)


def test_forward_against_the_reference(small, tokens, expected):
    _, model, params, _ = small
    got = np.asarray(jax.jit(model.apply)(params, tokens))
    assert _apart(got, expected) < CLOSE


@pytest.mark.parametrize("prompt", [1, 3, 17, 40])
def test_prefill_then_decode_through_the_cache(small, tokens, expected,
                                               prompt):
    """Every position's logits: the prompt whole (one chunk), then a token
    a round against both states (the one-position recurrence) and the
    attention layer's K/V."""
    _, model, params, _ = small
    logits, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, SEQ))(params, tokens[:, :prompt])
    assert _apart(logits, expected[:, prompt - 1]) < CLOSE
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for i in range(prompt, SEQ):
        logits, cache = step(params, tokens[:, i], cache)
        assert _apart(logits, expected[:, i]) < CLOSE, i
    kinds = cache.nbytes_by_kind()
    assert kinds["full"] == 2 * 2 * SEQ * 24 * 4
    assert kinds["state"] == 2 * 3 * (3 * 128 + 12 * 8 * 16) * 4
    assert kinds["latent"] == kinds["window"] == 0


# ------------------------------------------------------------------ the op
def _recurrence(x, dt, a, b, c, state):
    """ops/ssd.py's module docstring, a position at a time in numpy float64:
    x [T, H, P], dt [T, H], a [H], b, c [T, G, N], state [H, P, N]."""
    per_group = x.shape[1] // b.shape[1]
    out = []
    for t in range(x.shape[0]):
        b_t, c_t = (np.repeat(part[t], per_group, axis=0) for part in (b, c))
        state = (np.exp(dt[t] * a)[:, None, None] * state
                 + (dt[t][:, None] * x[t])[:, :, None] * b_t[:, None, :])
        out.append(np.einsum("hpn,hn->hp", state, c_t))
    return np.stack(out) if out else np.zeros((0,) + x.shape[1:]), state


def _ssd_inputs(t, low, high, groups=1, seed=0):
    """heads 6 of 5 over a state of 7: H != P != N; a head keeps between
    ``low`` and ``high`` of its state a position."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(2, t, 6, 5))
    b, c = rng.normal(size=(2, 2, t, groups, 7))
    a = -rng.uniform(0.5, 1.0, 6)
    kept = rng.uniform(low, high, (2, t, 6))
    dt = np.log(kept) / a
    state = rng.normal(size=(2, 6, 5, 7))
    return x, dt, a, b, c, state


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("chunk", [1, 4, 16, 64])
@pytest.mark.parametrize("decays", ["near_0.9", "near_0.9999", "near_zero"])
def test_the_chunked_dual_form_is_the_recurrence(chunk, decays, groups):
    """Whole sequences and a block against a cached state, a head keeping
    nine tenths, nearly all or nearly nothing (1e-4: the cumulative decay
    of a chunk underflows, which a quotient of cumulative decays would not
    survive) of its state a position."""
    low, high = {"near_0.9": (0.85, 0.95), "near_0.9999": (0.9995, 0.99999),
                 "near_zero": (1e-4, 1e-3)}[decays]
    x, dt, a, b, c, state = _ssd_inputs(37, low, high, groups)
    for start in (None, state):
        got, after = ssd(*map(jnp.asarray, (x, dt, a, b, c)),
                         None if start is None else jnp.asarray(
                             start, jnp.float32), chunk=chunk)
        for row in range(2):
            want, last = _recurrence(
                x[row], dt[row], a, b[row], c[row],
                np.zeros_like(state[row]) if start is None else start[row])
            assert _diff(got[row], want) < 2e-4 * max(
                1.0, np.max(np.abs(want)))
            assert _diff(after[row], last) < 2e-4 * max(
                1.0, np.max(np.abs(last)))


def test_a_rounds_single_token_is_the_recurrence():
    """T == 1 takes ``_one_position`` (no cumulative sum, no [C, C] term):
    a token a call from a state equals the chunked form over all of them,
    and a pad's call leaves the state bit for bit."""
    x, dt, a, b, c, state = _ssd_inputs(9, 0.85, 0.95, groups=2)
    args = tuple(map(jnp.asarray, (x, dt, a, b, c)))
    whole, last = ssd(*args, jnp.asarray(state, jnp.float32), chunk=4)
    held = jnp.asarray(state, jnp.float32)
    for t in range(9):
        got, held = ssd(args[0][:, t:t + 1], args[1][:, t:t + 1], args[2],
                        args[3][:, t:t + 1], args[4][:, t:t + 1], held)
        assert _diff(got[:, 0], whole[:, t]) < 1e-4
    assert _diff(held, last) < 1e-4
    jaxpr = str(jax.make_jaxpr(lambda *v: ssd(*v))(
        args[0][:, :1], args[1][:, :1], args[2], args[3][:, :1],
        args[4][:, :1], held))
    assert "cumsum" not in jaxpr and "dot_general" not in jaxpr
    _, same = ssd(args[0][:, :1], args[1][:, :1], args[2], args[3][:, :1],
                  args[4][:, :1], held, counts=jnp.asarray([0, 1]))
    assert np.array_equal(same[0], held[0])
    assert not np.array_equal(same[1], held[1])


@pytest.mark.parametrize("counts", [(20, 37), (13, 10), (3, 1), (0, 8)])
def test_pads_stay_out_of_the_state(counts):
    """A pad neither decays the state nor writes to it, and a chunk of pads
    alone (chunks of 8: the first row's from its third chunk on) is not
    worked through: the state after is the one after the last real
    position, the real positions' outputs are the recurrence's."""
    x, dt, a, b, c, state = _ssd_inputs(37, 0.85, 0.95, groups=2, seed=1)
    got, after = ssd(*map(jnp.asarray, (x, dt, a, b, c)),
                     jnp.asarray(state, jnp.float32),
                     jnp.asarray(counts, jnp.int32), chunk=8)
    for row, n in enumerate(counts):
        want, last = _recurrence(x[row, :n], dt[row, :n], a, b[row, :n],
                                 c[row, :n], state[row])
        if n:
            assert _diff(got[row, :n], want) < 2e-4
        assert _diff(after[row], last) < 2e-4
    jaxpr = str(jax.make_jaxpr(functools.partial(ssd, chunk=8))(
        *map(jnp.asarray, (x, dt, a, b, c))))
    assert "cond" in jaxpr


def test_two_groups_through_the_model_against_the_reference(tokens):
    """``mamba_n_groups`` 2: a key and a query a group of six heads, the
    gated norm over a group's 48 channels at a time."""
    small = _small(mamba_n_groups=2)
    assert small[1].config.ssm_dims == (96, 96 + 4 * 16)
    got = np.asarray(jax.jit(small[1].apply)(small[2], tokens))
    assert _apart(got, _expected(small, tokens)) < CLOSE
    one = _expected(_small(), tokens)
    assert _apart(got, one) > 100 * CLOSE


# --------------------------------------------------------------- the server
def _served(small, prompts, new=12, max_len=128, slots=4, budget=1 << 24):
    _, model, params, _ = small
    srv = serving.DecodeServer(model, params, slots=slots, max_len=max_len,
                               prompt_cache=8, prefix_cache_bytes=budget)
    out = []
    for prompt in prompts:
        rid = srv.submit(prompt, max_new_tokens=new)
        out.append(srv.run_to_completion()[rid])
    return srv, out


def _counters(*names):
    from parameter_server_distributed_tpu.obs import stats

    return [stats.counter(name).value for name in names]


def test_an_extension_against_a_restored_row_and_snapshot(small):
    """A resident system prompt, then the prompt + a turn: the admission
    restores the attention layer's K/V by position and three layers' two
    states at the node's end and forwards only the turn; the node's logits
    are the reference's over the uncut sequence and every served token its
    argmax.  The ssm layers count into the shared state counter: the lanes
    a round decoded for (one here: each request is served alone, eleven
    rounds after its first token) and not the idle ones beside them, which
    ``serve.linear.state_places`` holds too."""
    rng = np.random.default_rng(5)
    system = rng.integers(0, 512, 50)
    turn = np.concatenate([system, rng.integers(0, 512, 21)])
    before = _counters("serve.linear.state_updates",
                       "serve.linear.state_places")
    srv, (_, served) = _served(small, [system, turn])
    stats = srv.stats
    assert stats["prefix_hits"] == 1 and stats["prefill_tokens"] == 50 + 21
    node, matched, _ = srv._prefix_tree.lookup(tuple(turn.tolist()))
    assert matched == 71 and node.handle.state_at == 71
    row = node.handle.row
    # (two K/V heads of 12 share a row of 128 lanes; a lane of 128 does not
    # hold the suffix floor of 256 beside the prefix's 64)
    assert row[0].shape == row[1].shape == (1, 64 + 32, 1, 24)
    assert [x.shape for x in row[2:]] == [(3, 128), (12, 8, 16)] * 3
    logits = _reference_logits(small, np.concatenate([turn, served]))
    assert served == np.argmax(logits[70:82], -1).tolist()
    assert _apart(node.last, logits[70]) < CLOSE
    assert stats["cache_full_bytes"] == 4 * 2 * 128 * 24 * 4
    assert stats["cache_state_bytes"] == 4 * 3 * (3 * 128 + 12 * 8 * 16) * 4
    after = _counters("serve.linear.state_updates",
                      "serve.linear.state_places")
    assert srv.stats["steps"] == 2 * 11
    assert after[0] - before[0] == 2 * 11 * 1 * 3
    assert after[1] - before[1] == 2 * 11 * 4 * 3


def test_a_snapshot_stored_evicted_and_restored_under_another_slot(small):
    """A store that holds one row at a time: the system prompt's row (K/V
    and three layers' snapshots) is evicted by another prompt's, comes back
    with its next request, and is restored into whichever slot is free:
    behind two live requests the same turn reads the same logits."""
    rng = np.random.default_rng(7)
    system, other = rng.integers(0, 512, (2, 50))
    turn = np.concatenate([system, rng.integers(0, 512, 21)])
    _, model, params, _ = small
    srv = serving.DecodeServer(model, params, slots=4, max_len=128,
                               prompt_cache=8, prefix_cache_bytes=1 << 24)
    want = _reference_logits(small, turn)[70]

    def first_logits(prompt):
        rid = srv.submit(prompt, max_new_tokens=2)
        srv.run_to_completion()
        node, matched, _ = srv._prefix_tree.lookup(tuple(prompt.tolist()))
        assert matched == len(prompt)
        return rid, np.asarray(node.last)

    first_logits(system)
    _, got = first_logits(turn)
    assert _apart(got, want) < CLOSE
    # evict everything, as a budget that binds would
    tree = srv._prefix_tree
    budget, tree.budget_bytes = tree.budget_bytes, 0
    assert tree.evict_over_budget() >= 2 and tree.bytes == 0
    tree.budget_bytes = budget
    assert tree.lookup(tuple(system.tolist()))[1] == 0
    first_logits(other)
    hits = srv.stats["prefix_hits"]
    first_logits(system)                      # stored again
    # two requests hold slots 0 and 1 while the turn is admitted
    for _ in range(2):
        srv.submit(rng.integers(0, 512, 9), max_new_tokens=40)
    srv.step()
    again = np.concatenate([turn, [3]])
    rid = srv.submit(again, max_new_tokens=6)
    assert srv.stats["prefix_hits"] == hits + 1
    served = srv.run_to_completion()[rid]
    logits = _reference_logits(small, np.concatenate([again, served]))
    assert served == np.argmax(logits[71:77], -1).tolist()


# ------------------------------------------------------- the round's mask
@pytest.fixture(scope="module")
def registers():
    """The tiny copy at a state of 128: a head's matrix [8, 128] is whole
    registers, the shapes ops/pallas/ssd_decode.py takes."""
    return _small(mamba_d_state=128)


ARMS = ["plain", "kernel"]


def _take_round_arm(monkeypatch, arm):
    """What ``transformer.round_arm`` answers for an ssm layer's token on
    this CPU: ``kernel`` makes the backend a TPU's (the kernel runs
    interpreted); a shared model's traced runners are dropped."""
    monkeypatch.setattr(generation, "_RUNNERS", type(generation._RUNNERS)())
    monkeypatch.setattr(transformer, "_kernel_backend",
                        lambda: arm == "kernel")
    assert transformer.round_arm("ssm", (3, 1, 12, 8), (3, 12, 8, 128)) == arm


def _states_of(srv, slot):
    """Every ssm layer's (register, matrix) of a slot, on the host."""
    return [np.asarray(part[slot]) for layer in srv._cache.state
            for part in layer]


@pytest.mark.parametrize("arm", ARMS)
def test_a_retired_lanes_states_stay_until_its_next_request(
        registers, monkeypatch, arm):
    """Two requests decode, the short one ends: over the later rounds its
    lane's registers and matrices are bit for bit what they were when it
    ended (the other lane's move on), and the request admitted into that
    lane afterwards decodes the reference's tokens, as the long one does."""
    _take_round_arm(monkeypatch, arm)
    _, model, params, _ = registers
    rng = np.random.default_rng(11)
    short, long, later = (rng.integers(0, 512, n) for n in (9, 13, 17))
    srv = serving.DecodeServer(model, params, slots=3, max_len=128)
    first = srv.submit(short, max_new_tokens=4)
    second = srv.submit(long, max_new_tokens=24)
    while first not in srv.finished():
        srv.step()
    assert srv._slot[0] is None and srv._slot[1] is not None
    srv.step()          # the round in flight when it ended decoded for it
    ended = _states_of(srv, 0)
    other = _states_of(srv, 1)
    for _ in range(6):
        srv.step()
    assert all(np.array_equal(a, b)
               for a, b in zip(_states_of(srv, 0), ended))
    assert not any(np.array_equal(a, b)
                   for a, b in zip(_states_of(srv, 1), other))
    third = srv.submit(later, max_new_tokens=8)
    assert srv._slot[0] is not None
    served = srv.run_to_completion()
    for rid, prompt in ((second, long), (third, later)):
        logits = _reference_logits(registers, np.concatenate(
            [prompt, served[rid]]))
        at = len(prompt) - 1
        assert served[rid] == np.argmax(
            logits[at:at + len(served[rid])], -1).tolist()


@pytest.mark.parametrize("arm", ARMS)
def test_step_many_agrees_with_step_and_leaves_an_idle_lane(
        registers, monkeypatch, arm):
    """Fused rounds take the same mask, constant over their rounds: the
    tokens are a step() loop's, and the lane of a request that ended before
    them keeps its states through the fused block."""
    _take_round_arm(monkeypatch, arm)
    _, model, params, _ = registers
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 512, n) for n in (7, 11, 15)]
    budgets = (2, 18, 18)

    def server():
        srv = serving.DecodeServer(model, params, slots=4, max_len=128)
        rids = [srv.submit(prompt, max_new_tokens=budget)
                for prompt, budget in zip(prompts, budgets)]
        while rids[0] not in srv.finished():
            srv.step()
        srv.land()
        return srv, rids

    stepped, rids = server()
    want = stepped.run_to_completion()
    fused, rids = server()
    ended = _states_of(fused, 0)
    emitted = fused.step_many(8)
    assert len(emitted) == 2 * 8
    assert all(np.array_equal(a, b)
               for a, b in zip(_states_of(fused, 0), ended))
    while not fused.idle:
        fused.step_many(8)
    got = {rid: fused.result(rid) for rid in rids}
    assert got == {rid: want[rid] for rid in rids}


@pytest.mark.parametrize("arm", ARMS)
def test_the_state_counters_follow_the_lanes_that_decode(
        registers, monkeypatch, arm):
    """A landed round adds its live lanes x the three ssm layers to
    ``serve.linear.state_updates`` and slots x layers to
    ``serve.linear.state_places``, under either arm (the plain pass leaves
    an idle lane's states as they are too)."""
    _take_round_arm(monkeypatch, arm)
    _, model, params, _ = registers
    rng = np.random.default_rng(13)
    srv = serving.DecodeServer(model, params, slots=4, max_len=128)
    names = ("serve.linear.state_updates", "serve.linear.state_places")
    before = _counters(*names)
    for n, budget in ((6, 6), (8, 3)):
        srv.submit(rng.integers(0, 512, n), max_new_tokens=budget)
    srv.run_to_completion()
    moved = [b - a for a, b in zip(before, _counters(*names))]
    # five rounds for the first request, the first two of them for both
    assert srv.stats["steps"] == 5
    assert moved == [(5 + 2) * 3, 5 * 4 * 3]


def _round_as_it_was(model, top_k=0, top_p=0.0):
    """The step program of PR 57's ``serving._step_runner``, written out:
    no mask anywhere."""
    @functools.partial(jax.jit, donate_argnums=(3,))
    def run(params, prev, fresh, cache, lengths, temps, rng):
        routed: list = []
        selected: list = []
        tokens = jnp.where(fresh < 0, prev, fresh)
        logits, cache = generation.decode_block(
            model, params, tokens[:, None], cache, lengths=lengths,
            route_stats=routed, sparse_stats=selected)
        with jax.named_scope("sample"):
            rng, sub = jax.random.split(rng)
            nxt = generation.sample_token_rowwise(logits[:, 0], sub, temps,
                                                  top_k, top_p)
        counted = (jnp.concatenate(routed) if routed else None,
                   sum(selected) if selected else None)
        return nxt, cache, rng, counted

    return run


# the models whose rounds are told which lanes hold a request: an ssm
# layer's kernel moves those lanes' states alone (PR 58), an experts layer
# routes those lanes' tokens alone (PR 60)
_TOLD = ("granite-4.0-h-micro", "smallthinker-21b-a3b-8l")


@pytest.mark.parametrize("name", ["gpt2-medium", "olmo-hybrid-7b-16l",
                                  *_TOLD])
def test_only_a_model_with_a_layer_that_reads_the_lanes_traces_the_mask(
        name):
    """GPT-2's step and a state model's (gdn layers beside softmax ones)
    are the programs they were, equation for equation: the mask enters a
    round only where a layer reads it (``LayerSpec.reads_live_lanes``).
    Granite's (ssm layers) and SmallThinker's (experts layers) are not."""
    from perfbench import families

    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(family.tiny(config))
    slots = 3
    shapes = jax.eval_shape(lambda: (
        family.make_weights(model, 1), jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.int32),
        generation.init_cache(model, slots, 64),
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.float32),
        jax.random.key(0)))
    now = str(jax.make_jaxpr(serving._step_runner(
        model, slots, 0, 0.0, "native"))(*shapes))
    was = str(jax.make_jaxpr(_round_as_it_was(model))(*shapes))
    assert bool(serving._mask_layers(model)) == (name in _TOLD)
    assert (now == was) == (name not in _TOLD)


# ------------------------------------------------------- the scores' scale
def _wide(**changes):
    """The tiny copy at heads of 64, two K/V heads a row of 128 lanes: the
    widths the kernels take."""
    return _small(hidden_size=128, num_attention_heads=2,
                  num_key_value_heads=2, mamba_n_heads=16, mamba_d_head=16,
                  max_position_embeddings=256, **changes)


@pytest.fixture(scope="module")
def wide():
    return _wide()


@pytest.mark.parametrize("arm", ["einsum", "blockwise", "fused_kernel"])
def test_attn_scale_reaches_every_arm_of_a_whole_sequence(wide, monkeypatch,
                                                          arm):
    """``attn_scale`` 1/64 at heads of 64 (1/8 is the usual): the einsum,
    blockwise in plain XLA and the fused kernel (interpreted) each read the
    reference's logits, which multiplies its scores by 1/64 itself."""
    config, model, params, weights = wide
    assert model.config.query_gain == 0.125
    sequence = np.random.default_rng(2).integers(0, 512, (2, 128)).astype(
        np.int32)
    if arm == "blockwise":
        monkeypatch.setattr(Transformer, "BLOCKWISE_FROM", 64)
    if arm == "fused_kernel":
        monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    q_shape, kv_shape = (2, 128, 2, 64), (2, 128, 2, 64)
    assert transformer.device_arm(q_shape, kv_shape) == {
        "einsum": "dense", "blockwise": "blockwise",
        "fused_kernel": "kernel"}[arm]
    got = np.asarray(jax.jit(model.apply)(params, sequence))
    want = _expected(wide, sequence)
    assert _apart(got, want) < 2 * CLOSE


@pytest.mark.parametrize("arm", ["dense", "blockwise", "full_decode"])
def test_attn_scale_reaches_every_arm_against_a_cache(wide, monkeypatch,
                                                      arm):
    """An extension of 128 tokens against a cached prefix (the dense cache
    einsums; blockwise from 128 queries against a long lane) and then
    rounds of one token (the einsums; ``full_decode`` interpreted at a
    block of 128): every position's logits are the reference's."""
    _, model, params, _ = wide
    sequence = np.random.default_rng(4).integers(0, 512, (2, 180)).astype(
        np.int32)
    want = _expected(wide, sequence)
    if arm == "blockwise":
        monkeypatch.setattr(Transformer, "BLOCKWISE_FROM", 256)
    if arm == "full_decode":
        monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
        monkeypatch.setattr(full_decode, "LARGEST_BLOCK", 128)
    monkeypatch.setattr(generation, "_RUNNERS", type(generation._RUNNERS)())
    logits, cache = jax.jit(lambda p, t: generation.prefill(
        model, p, t, 256))(params, sequence[:, :40])
    assert _apart(logits, want[:, 39]) < 2 * CLOSE
    block, cache = jax.jit(lambda p, t, c: generation.decode_block(
        model, p, t, c))(params, sequence[:, 40:168], cache)
    assert _apart(block, want[:, 40:168]) < 2 * CLOSE
    part = cache.k[0]
    assert transformer.round_arm("softmax", (2, 1, 2, 64), part.shape,
                                 part.dtype) == (
        "kernel" if arm == "full_decode" else "dense")
    step = jax.jit(lambda p, t, c: generation.decode_step(model, p, t, c))
    for i in range(168, 180):
        logits, cache = step(params, sequence[:, i], cache)
        assert _apart(logits, want[:, i]) < 2 * CLOSE, i


def test_attn_scale_zero_is_the_usual_scale():
    """0 leaves every program as it was: no multiplication is traced."""
    usual = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                              d_ff=64, max_seq=32, dtype=jnp.float32)
    assert usual.attn_scale == 0 and usual.query_gain == 1.0
    scaled = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                               d_ff=64, max_seq=32, dtype=jnp.float32,
                               attn_scale=0.25)
    assert scaled.query_gain == 1.0    # 0.25 IS 16 ** -0.5
    sample = jnp.zeros((1, 8), jnp.int32)
    texts = []
    for config in (usual, scaled):
        model = Transformer(config)
        texts.append(str(jax.make_jaxpr(model.apply)(
            model.init_params(0), sample)))
    assert texts[0] == texts[1]


# ---------------------------------------------------- the check's controls
CONTROLS = {
    "sound": None,
    "scale_an_eighth_for_a_64th": {"attention": {"scale": 12 ** -0.5}},
    "skip_left_out": {"ssm": {"skip": False}},
    "norm_before_the_gate": {"ssm": {"gate_first": False}},
    "conv_bias_left_out": {"ssm": {"conv_bias": False}},
    "state_in_bfloat16": {"ssm": {"state_bits": 7}}}


def test_the_check_reads_the_first_state(small, tokens, expected,
                                         monkeypatch):
    """What ``reference_forward`` judges by, at the tiny size in float32:
    every ssm layer's matrix state after the last token is the reference's
    scan's, and a matrix state kept at bfloat16's mantissa (in the
    reference: the comparison cannot tell whose fault it is) is NOT a
    number, by the state's limit alone; no host callback keeps the
    reference's program out of the compile cache."""
    config, _, _, weights = small
    read = jax.jit(lambda w, t: granite_hybrid.reference_readings(
        config, w, t))
    logits, apart = read(weights, tokens)
    assert _apart(logits, expected) < CLOSE
    assert apart.shape == () and float(apart) < 1e-5
    assert "callback" not in read.lower(weights, tokens).as_text()
    faults = CONTROLS["state_in_bfloat16"]
    _, apart = jax.jit(lambda w, t: granite_hybrid.reference_readings(
        config, w, t, faults))(weights, tokens)
    assert float(apart) > 1e-3
    monkeypatch.setattr(granite_hybrid, "STATE_TOLERANCE", 1e-3)
    refused = jax.jit(lambda w, t: granite_hybrid.reference_forward(
        config, w, t, faults))(weights, tokens)
    assert bool(jnp.all(jnp.isnan(refused)))


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_fails_the_harness_own_comparison(small, monkeypatch,
                                                       control):
    """``correct.compare_forward``, the comparison that decides ``correct``
    in the cell, with the fault handed to the family's ``reference_forward``
    (``scripts/granite_controls.py`` does the same on the chip): the sound
    run reads ``ok`` true and each of ISSUE 57's five controls ``ok`` false
    by one of the chip's own limits; but for the bfloat16 state, which the
    state's limit alone catches here (not a number): 2,048 positions on the
    chip read 0.109 against 0.0148, 64 positions of this float32 program
    read 2e-7 sound and 0.0076 with the fault, so its limit here is 1e-3."""
    config, model, _, _ = small
    faults = CONTROLS[control]
    if control == "state_in_bfloat16":
        monkeypatch.setattr(granite_hybrid, "STATE_TOLERANCE", 1e-3)
    monkeypatch.setattr(granite_hybrid, "reference_forward",
                        functools.partial(granite_hybrid.reference_forward,
                                          faults=faults))
    verdict = correct.compare_forward(config, model, 3000000061,
                                      {"sequences": 1, "tokens": 64})
    assert verdict["ok"] is (faults is None)
    assert (verdict["logits_rms_error_std"] < 1e-5) is (faults is None)


@pytest.mark.parametrize("control", sorted(set(CONTROLS) - {"sound"}))
def test_the_controls_are_far_from_the_reference(small, tokens, expected,
                                                 control):
    """Each of the faults the chip's controls use moves the float32
    reference's logits by far more than CLOSE: the comparison sees them."""
    config, _, _, weights = small
    arguments = granite_hybrid._reference_arguments(config)
    got = np.asarray(jax.jit(lambda w, t: reference.forward(
        w, t, faults=CONTROLS[control], **arguments))(weights, tokens))
    error, worst = correct.logits_errors(got, expected)
    narrow = control == "state_in_bfloat16"
    assert error > (3e-4 if narrow else 0.01)
    assert worst > (10 if narrow else 100) * CLOSE


def test_the_loss_and_its_gradient_against_the_reference(small, tokens):
    config, model, params, weights = small
    tokens = tokens[:, :64]
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, tokens)
    (ref_loss, _), ref_grads = jax.jit(jax.value_and_grad(
        lambda w, t: granite_hybrid.reference_loss(config, w, t),
        has_aux=True))(weights, tokens)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) \
        < granite_hybrid.LOSS_TOLERANCE
    error, cosine = correct.gradient_errors(
        granite_hybrid.reference_weights(config, grads), ref_grads)
    assert error < granite_hybrid.GRADIENT_TOLERANCE and cosine > 0.999


# ------------------------------------------------------------ what is refused
def test_the_new_leaves_have_a_sharding_rule():
    from parameter_server_distributed_tpu.parallel.mesh import (
        MeshConfig, build_mesh)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    rule = transformer_rule(mesh)
    for name, shape in (("layer0/ssm/conv/kernel", (4, 128)),
                        ("layer0/ssm/conv/bias", (128,)),
                        ("layer0/ssm/skip", (12,)),
                        ("layer0/ssm/decay/a_log", (12,)),
                        ("layer0/ssm/decay/dt_bias", (12,)),
                        ("layer0/ssm/norm/scale", (96,))):
        assert tuple(rule(name, shape)) == (), name
    # three parts side by side: never cut over ``tensor``
    assert "tensor" not in tuple(rule("layer0/ssm/in_proj", (48, 236)))
    assert "tensor" not in tuple(rule("layer0/ssm/out_proj", (96, 48)))


BASE = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32,
            dtype=jnp.float32)
SSM = dict(ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
           conv_kernel=4, pattern=(LayerSpec(mixer="ssm"), LayerSpec()))


@pytest.mark.parametrize("fields,message", [
    (dict(ssm_heads=4), "an ssm layer's"),
    (dict(SSM, ssm_state=0), "an ssm layer's"),
    (dict(SSM, ssm_groups=3), "an ssm layer's"),
    (dict(SSM, pos_emb="learned"), "an ssm layer's"),
    (dict(SSM, bias=True), "no bias on a projection"),
    (dict(SSM, scan_layers=True), "run unrolled"),
    (dict(attn_scale=-1.0), "a softmax layer's"),
    (dict(SSM, pattern=(LayerSpec(mixer="ssm"),), attn_scale=0.5),
     "a softmax layer's")])
def test_configurations_the_program_refuses(fields, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**{**BASE, **fields})
    with pytest.raises(ValueError, match="norms and gates its output "
                                         "always"):
        LayerSpec(mixer="ssm", gate=True)


def test_a_draft_and_an_int8_cache_are_refused_by_name(small):
    _, model, params, _ = small
    with pytest.raises(ValueError, match="cannot be rolled"):
        serving.DecodeServer(model, params, slots=2, max_len=64,
                             draft=model, draft_params=params)
    with pytest.raises(ValueError, match="native cache"):
        generation.init_cache(model, 2, 64, "int8")


def test_the_model_is_in_the_registry_under_its_program_name():
    """``pst-serve`` resolves a name of ``models/registry.REGISTRY``."""
    from parameter_server_distributed_tpu.models.registry import REGISTRY
    from perfbench import program

    config = _configuration()
    name = program.register_model(config, lambda batch, seed: iter(()))
    assert name == "granite-4.0-h-micro-transformer-lm" and name in REGISTRY
    model = REGISTRY[name][0](dtype=jnp.float32)
    assert model.config.layers_of("ssm") == (0, 1, 3)
    REGISTRY.pop(name)
