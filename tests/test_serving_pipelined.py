"""The plain decode round runs one round ahead of the host
(``DecodeServer.step``): a round is dispatched on its predecessor's tokens
where they lie on the device, before the host has fetched them.  Held here,
in float32 on the CPU at a small size: a greedy request gets EXACTLY the
tokens of a standalone ``generate`` (the serial order's answer) whatever
was in flight around its admission and its end; a sampled one repeats for
one seed and one sequence of calls; ``step()`` keeps its contract; the
counters ``serve.rounds`` / ``serve.rounds_chained`` count what the
schedule implies.  Three layer patterns go through the one round: GPT-2's
(by-position cache), window/full + dropless experts (rings that wrap) and
sparse + linear (compressed keys, states, a snapshot restored at
admission), the latter two from the fixtures of ``test_layer_pattern.py``
and ``test_minicpm_sala.py``.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from test_layer_pattern import CONFIG as WINDOW_EXPERTS  # noqa: E402
from test_minicpm_sala import SPARSE  # noqa: E402
from test_serving import tiny  # noqa: E402

from parameter_server_distributed_tpu.models import transformer as tr  # noqa: E402
from parameter_server_distributed_tpu.models.generation import (  # noqa: E402
    _decode_step_runner, generate, prefill)
from parameter_server_distributed_tpu.models.serving import DecodeServer  # noqa: E402
from parameter_server_distributed_tpu.obs import stats as obs_stats  # noqa: E402
from perfbench.families import gpt2, minicpm_sala, smallthinker  # noqa: E402

SLOTS = 3
NEW = 12    # every request's budget, unless a case says otherwise


def _gpt2():
    # learned positions, LayerNorm, biases.  The head untied and the
    # embedding fifty times the matrices' width, as the other two families
    # draw theirs: a tied, narrow one decodes greedily into a fixed point
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "gpt2-medium.json")) as handle:
        model = gpt2.model(gpt2.tiny(json.load(handle)))
    params = gpt2.make_weights(model, 5, tie_head=False)
    params["embed/tok"] = 50 * params["embed/tok"]
    return model, params, 512, 64, 12, 1


def _window_experts():
    # window 8: a 20-token document and every prompt after it pass the ring
    model = smallthinker.model(WINDOW_EXPERTS)
    return model, smallthinker.make_weights(model, 11), 512, 96, 20, 1


def _sparse_linear():
    # dense_len 64, blocks of 8: a 90-token document selects, and its row
    # in the prefix tree carries the linear layers' snapshot
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm-sala-12l.json")) as handle:
        config = minicpm_sala.tiny(json.load(handle))
    config["sparse_config"] = dict(SPARSE)
    config["max_position_embeddings"] = 256
    model = minicpm_sala.model(config)
    return model, minicpm_sala.make_weights(model, 3), 512, 208, 90, 8


@pytest.fixture(scope="module", params=[_gpt2, _window_experts,
                                        _sparse_linear],
                ids=["gpt2", "window_experts", "sparse_linear"])
def family(request):
    """(model, weights, max_len, prompts, each prompt's greedy tokens, the
    resident document): seven prompts, six of them the document and a
    turn, the last one unshared."""
    model, params, vocab, max_len, document_len, multiple = request.param()
    rng = np.random.default_rng(17)
    document = rng.integers(0, vocab, document_len).astype(np.int32)
    prompts = [np.concatenate([document,
                               rng.integers(0, vocab, n).astype(np.int32)])
               for n in (3, 9, 5, 7, 4, 6)]
    prompts.append(rng.integers(0, vocab, 11).astype(np.int32))

    def greedy(prompt):
        # (a cache with sparse layers is whole blocks: decode a few more
        # and keep the first NEW; greedy tokens do not depend on the rest)
        n = NEW + (-(len(prompt) + NEW)) % multiple
        return [int(t) for t in np.asarray(generate(
            model, params, jnp.asarray(prompt[None]), n))[0, :NEW]]

    return (model, params, max_len, prompts, [greedy(p) for p in prompts],
            document)


def _counts():
    return {name: obs_stats.counter(name).value
            for name in ("serve.rounds", "serve.rounds_chained")}


def _moved(before):
    after = _counts()
    return (int(after["serve.rounds"] - before["serve.rounds"]),
            int(after["serve.rounds_chained"]
                - before["serve.rounds_chained"]))


def _ends(tokens, eos, stop):
    """What the server serves of a greedy continuation: up to and with the
    first finishing token."""
    for n, token in enumerate(tokens):
        if token == eos or token in stop:
            return tokens[:n + 1]
    return tokens


def _drive(family, temperatures=None, seed=0):
    """One schedule through a fresh server; returns (results by prompt
    index, the cancelled request's tokens at its cancellation, the
    server).

    Requests 0..2 arrive one a call, so 1 and 2 are admitted with a round
    in flight; 1 ends on a ``stop`` token and 2 on the server's ``eos_id``,
    each with its next token already decoded; whenever a request ends the
    next waiting one is admitted in the same turn of the loop, into the
    slot just freed (3, 5, 4, 6); 5 is cancelled two calls later;
    ``swap_params`` (the same weights) comes with rounds in flight.  Every
    call's returned requests are held to step()'s contract."""
    model, params, max_len, prompts, greedy, document = family
    temps = temperatures or [None] * len(prompts)
    eos = greedy[2][5]
    stops = {1: {greedy[1][3]}}
    server = DecodeServer(model, params, slots=SLOTS, max_len=max_len,
                          eos_id=eos, prompt_cache=8,
                          prefix_cache_bytes=1 << 24, seed=seed)
    server.result(server.submit(document, max_new_tokens=1))
    assert server.idle                      # resident; nothing in flight
    ids: dict[int, int] = {}                # prompt index -> request id
    admitted_at: dict[int, int] = {}        # prompt index -> calls before
    seen: dict[int, list[int]] = {}         # request id -> tokens streamed
    results: dict[int, list[int]] = {}

    def admit(index):
        rid = server.submit(prompts[index], max_new_tokens=NEW,
                            temperature=temps[index],
                            stop=stops.get(index, ()))
        ids[index] = rid
        admitted_at[index] = call
        if rid in server.finished():
            results[index] = server.result(rid)
        else:
            seen[rid] = list(server.peek(rid))

    waiting = [3, 5, 4, 6]
    cancelled = None
    was_active: set[int] = set()
    call = 0
    admit(0)
    while not server.idle:
        active = set(seen)
        # one token for every request that was active when the call before
        # returned; if none of those is left (idle since, or cancelled),
        # for every active one: the call then dispatches two rounds
        expected = (active & was_active) or active
        emitted = server.step()
        call += 1
        assert sorted(rid for rid, _ in emitted) == sorted(expected), call
        for rid, token in emitted:
            seen[rid].append(token)
        for rid in server.finished():
            index = next(i for i, r in ids.items() if r == rid)
            results[index] = server.result(rid)
            assert results[index] == seen.pop(rid)
        for rid, tokens in seen.items():
            assert server.peek(rid) == tokens
        was_active = set(seen)
        # the caller's turn, with a round in flight
        if call in (1, 2):
            admit(call)
        if call == 4:
            # rounds are in flight; prefix rows go, the stream stays
            server.swap_params(dict(params))
            assert server.stats["weight_swaps"] == 1
        if ids.get(5) in seen and call == admitted_at[5] + 2:
            cancelled = seen.pop(ids[5])
            assert server.cancel(ids[5])
        while waiting and server.has_free_slot and call > 2:
            admit(waiting.pop(0))
    assert not waiting and not seen
    return results, cancelled, server


def test_greedy_requests_get_generates_tokens_whatever_is_in_flight(family):
    """(a) and (b): every request of the schedule, token-exact against a
    standalone ``generate``; the token a request decoded past its end never
    reaches ``result()`` or ``peek()``."""
    _, _, _, prompts, greedy, _ = family
    before = _counts()
    results, cancelled, server = _drive(family)
    eos = greedy[2][5]
    for index in (0, 1, 2, 3, 4, 6):
        stop = {greedy[1][3]} if index == 1 else ()
        assert results[index] == _ends(greedy[index], eos, stop), index
    assert len(results[1]) <= 4 and len(results[2]) <= 6
    # the cancelled request streamed a prefix of its answer and left no
    # result
    assert 5 not in results and cancelled == greedy[5][:2]
    assert server.stats["requests_completed"] == 7   # the document's too
    rounds, chained = _moved(before)
    # only the first round after idle was dispatched on the host's tokens
    assert rounds - chained == 1 and chained >= 15


def test_an_idle_lanes_length_stays_and_a_live_ones_leads_by_a_round(family):
    model, params, max_len, prompts, greedy, _ = family
    server = DecodeServer(model, params, slots=SLOTS, max_len=max_len)
    first = server.submit(prompts[0], max_new_tokens=3)
    server.run_to_completion()
    held = server._lengths.copy()            # lane 0 retired at its end
    assert held[0] == len(prompts[0]) + 2 and not held[1:].any()
    rid = server.submit(prompts[6], max_new_tokens=6)
    assert rid != first and server._slot[1] is None
    for call in range(1, 4):
        server.step()
        # lane 0 has a request again, one round dispatched ahead of the
        # tokens fetched; the idle lanes stay where they were
        assert server._lengths[0] == len(prompts[6]) + call + 1
        assert (server._lengths[1:] == held[1:]).all()
    assert server.run_to_completion()[rid] == greedy[6][:6]


def test_sampled_requests_repeat_for_one_seed_and_one_sequence_of_calls(
        family):
    """Per-request temperatures through the same schedule, twice with one
    seed: the same tokens; with another seed, others."""
    temperatures = [0.7, 1.3, 0.0, 0.9, 1.1, 0.8, 0.0]
    runs = [_drive(family, temperatures, seed)[0] for seed in (5, 5, 6)]
    assert runs[0] == runs[1]
    assert runs[0] != runs[2]
    # (greedy requests ride the same rounds and still get generate's
    # tokens; request 2 ends on the eos token)
    _, _, _, _, greedy, _ = family
    assert runs[0][6] == _ends(greedy[6], greedy[2][5], ())
    assert runs[0][2] == _ends(greedy[2], greedy[2][5], ())


def _small():
    model = tiny()
    return model, model.init_params(0)


def test_the_counters_count_rounds_dispatched_and_rounds_chained():
    """(c) by hand.  A (4 tokens) alone, B (3) admitted after the first
    call: R1 {A} from the host, R2 {A} chained; call 2 dispatches R3 {A
    chained, B from the host} and lands R2; call 3 dispatches R4 {B} (A's
    budget ends with R3) and lands R3; call 4 dispatches nothing (no budget
    left) and lands R4."""
    model, params = _small()
    server = DecodeServer(model, params, slots=4, max_len=64)
    before = _counts()
    a = server.submit([5, 6, 7], max_new_tokens=4)
    assert [rid for rid, _ in server.step()] == [a]
    assert _moved(before) == (2, 1)
    b = server.submit([9, 8, 7, 6], max_new_tokens=3)
    assert [rid for rid, _ in server.step()] == [a]
    assert _moved(before) == (3, 2)
    assert [rid for rid, _ in server.step()] == [a, b]
    assert _moved(before) == (4, 3) and server.finished() == [a]
    assert [rid for rid, _ in server.step()] == [b]
    assert _moved(before) == (4, 3) and server.idle
    assert server.step() == []
    assert server.stats["steps"] == 4


def test_a_round_decoded_past_a_stop_token_is_dispatched_and_dropped():
    model, params = _small()
    want = [int(t) for t in np.asarray(generate(
        model, params, jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32), 8))[0]]
    server = DecodeServer(model, params, slots=2, max_len=64)
    before = _counts()
    rid = server.submit([3, 1, 4, 1, 5], max_new_tokens=8,
                        stop=[want[2]])
    ends = want.index(want[2]) + 1       # tokens served, the stop included
    assert ends == 3
    while not server.idle:
        server.step()
    assert server.result(rid) == want[:ends]
    # the rounds that decoded its tokens after the first, and one more
    assert _moved(before) == (ends, ends - 1)
    assert server._flight is None


def test_step_many_lands_the_round_in_flight_first():
    model, params = _small()
    want = [int(t) for t in np.asarray(generate(
        model, params, jnp.asarray([[2, 7, 1, 8]], jnp.int32), 12))[0]]
    server = DecodeServer(model, params, slots=2, max_len=64)
    rid = server.submit([2, 7, 1, 8], max_new_tokens=12)
    streamed = [want[0]] + [t for _, t in server.step()]
    assert server._flight is not None
    fused = server.step_many(4)
    assert len(fused) == 1 + 4 and server._flight is None
    streamed += [t for _, t in fused]
    while not server.idle:
        streamed += [t for _, t in server.step()]
    assert streamed == want == server.result(rid)


def decoded_by(model, stores, prompt, versions, max_len=64):
    """Greedy tokens against ONE cache where token i is decoded under
    ``stores[versions[i]]`` (the first by the prefill): what a stream
    whose weights were swapped on the way is, if its stamps are true."""
    logits, cache = jax.jit(lambda params, tokens: prefill(
        model, params, tokens, max_len))(
            stores[versions[0]], jnp.asarray([prompt], jnp.int32))
    step = _decode_step_runner(model)
    tokens = [int(jnp.argmax(logits[0]))]
    for version in versions[1:]:
        logits, cache = step(stores[version],
                             jnp.asarray(tokens[-1:], jnp.int32), cache)
        tokens.append(int(jnp.argmax(logits[0])))
    return tokens


@pytest.mark.parametrize("versioned", [True, False],
                         ids=["landed_first", "left_in_flight"])
def test_a_swap_to_other_weights_with_a_round_in_flight(versioned):
    """Every token is the one the weights it is attributed to decode.  The
    round in flight at the swap ran under the weights that leave: a caller
    that stamps tokens lands it first and hands its token out under the old
    version (a versioned swap refuses otherwise, and changes nothing); an
    unversioned swap leaves it, and the next step() returns that token."""
    model, params = _small()
    stores = {0: params, 7: model.init_params(1)}
    prompt, new, swap_after = [3, 1, 4, 1, 5], 10, 3
    server = DecodeServer(model, params, slots=2, max_len=64)
    rid = server.submit(prompt, max_new_tokens=new)
    stamps = [server.params_version]         # of the prefill's token
    calls = 0
    while not server.idle:
        emitted = server.step()
        calls += 1
        stamps += [server.params_version] * len(emitted)
        if calls != swap_after:
            continue
        assert server._flight is not None
        if versioned:
            with pytest.raises(RuntimeError, match="in flight"):
                server.swap_params(stores[7], version=7)
            assert server.params_version == 0 and server.params is params
            landed = server.land()
            assert [r for r, _ in landed] == [rid]
            assert server._flight is None
            stamps.append(server.params_version)
            server.swap_params(stores[7], version=7)
            assert server.params_version == 7
        else:
            server.swap_params(stores[7])
            assert server._flight is not None
    served = server.result(rid)
    # the prefill's token, one a call up to the swap, the round in flight
    by = [0] * (1 + swap_after + 1) + [7] * (new - swap_after - 2)
    assert served == decoded_by(model, stores, prompt, by)
    if versioned:
        assert stamps == by
    # (the comparison can tell: a token attributed to the other version
    # gives another stream)
    early = by[:swap_after + 1] + [7] * (new - swap_after - 1)
    assert decoded_by(model, stores, prompt, early) != served
    assert served != [int(t) for t in np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32), new))[0]]


def test_a_speculative_server_chains_no_round():
    model, params = _small()
    draft = tr.Transformer(tr.TransformerConfig(
        vocab=96, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_seq=128,
        dtype=jnp.float32))
    server = DecodeServer(model, params, slots=2, max_len=64, draft=draft,
                          draft_params=draft.init_params(1), draft_len=2,
                          adaptive_draft=False)
    before = _counts()
    rid = server.submit([1, 2, 3, 4, 5], max_new_tokens=9)
    served = server.run_to_completion()[rid]
    assert served == [int(t) for t in np.asarray(generate(
        model, params, jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32), 9))[0]]
    assert _moved(before) == (0, 0)
