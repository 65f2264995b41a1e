"""A decode round's idle lanes leave the experts' groups: the round of a
model with an ``experts`` layer is told which lanes hold a request
(``serving._mask_layers``, ``_IDLE`` in ``fresh``), ``decode_block`` forms
``moe.dropless_experts``'s ``live`` from its ``counts``, and every other
consumer of ``counts`` in those models (the rings, a conv layer's register,
a kda layer's states) sees 0 for an idle lane and holds it still.  Held
here in float32 on the CPU at the families' tiny sizes: served tokens are
``generate``'s whatever stands idle beside them, through ``step()`` and
``step_many()``; the counters count the live lanes' assignments; a lane
that stood idle while its neighbour's ring wrapped serves its next request
exactly.  An idle lane's contents are nobody's: an admission's splice
(``serving._splice_runner``) writes EVERY part of the lane it is given (K/V
by position, rings, compressed keys, states, latent rows), so nothing of
what stood there is read again.
"""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from parameter_server_distributed_tpu.models import serving  # noqa: E402
from parameter_server_distributed_tpu.models.generation import (  # noqa: E402
    generate)
from parameter_server_distributed_tpu.obs import stats as obs_stats  # noqa: E402
from perfbench import families  # noqa: E402

# plain experts behind rings; a held share behind rings; conv registers; kda
# states and a held share
CONFIGS = {
    "smallthinker": "smallthinker-21b-a3b-8l",
    "k_exaone": "k-exaone-236b-a23b-8l-ep8",
    "lfm2": "lfm2-24b-a2b-10l",
    "kimi_linear": "kimi-linear-48b-a3b-12l-ep8",
}
_BUILT: dict = {}


def _built(name):
    """(model, weights, experts layers) of a family's tiny configuration."""
    if name not in _BUILT:
        with open(os.path.join(ROOT, "perfbench", "configs",
                               CONFIGS[name] + ".json")) as handle:
            config = json.load(handle)
        family = families.of(config)
        model = family.model(family.tiny(config))
        c = model.config
        layers = sum(c.layer_spec(i).ffn == "experts"
                     for i in range(c.n_layers))
        _BUILT[name] = model, family.make_weights(model, 7), layers
    return _BUILT[name]


def _want(model, params, prompt, new):
    return [int(t) for t in np.asarray(generate(
        model, params, jnp.asarray(prompt, jnp.int32)[None], new))[0]]


def _moved(before):
    return {name: obs_stats.counter(f"serve.moe.{name}").value - was
            for name, was in before.items()}


def _counters(*names):
    return {name: obs_stats.counter(f"serve.moe.{name}").value
            for name in names}


def _lane_parts(srv, lane):
    """What a decode round holds still for a lane that is nobody's: its
    rings and its states (registers and matrices)."""
    cache = srv._cache
    parts = [np.asarray(part[lane]) for name in ("wk", "wv")
             for part in getattr(cache, name)]
    return parts + [np.asarray(state[lane]) for layer in cache.state
                    for state in layer]


@pytest.mark.parametrize("how", ["step", "step_many"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_served_tokens_are_generates_with_lanes_idle(name, how):
    """Four lanes, three requests of budgets 3, 9 and 14: one lane never
    holds a request, one stands idle from the third round on, one from the
    ninth.  Every request's tokens are a standalone ``generate``'s."""
    model, params, _ = _built(name)
    assert serving._mask_layers(model)
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 512, n) for n in (9, 5, 13)]
    budgets = (3, 9, 14)
    srv = serving.DecodeServer(model, params, slots=4, max_len=128)
    assert srv._masked
    rids = [srv.submit(prompt, max_new_tokens=budget)
            for prompt, budget in zip(prompts, budgets)]
    while not srv.idle:
        srv.step() if how == "step" else srv.step_many(4)
    for rid, prompt, budget in zip(rids, prompts, budgets):
        assert srv.result(rid) == _want(model, params, prompt, budget)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_rounds_assignments_are_its_live_lanes(name):
    """A landed round adds its live lanes x ``moe_top_k`` x experts layers
    to ``serve.moe.round_assignments`` and slots x ``moe_top_k`` x layers to
    ``serve.moe.round_assignment_places``; an admission adds its prompt's
    tokens, not its bucket's, to ``serve.moe.assignments_routed``; and no
    experts layer of a round is touched by more than its live lanes'
    choices."""
    model, params, layers = _built(name)
    top_k = model.config.moe_top_k
    rng = np.random.default_rng(22)
    srv = serving.DecodeServer(model, params, slots=4, max_len=128)
    before = _counters("round_assignments", "round_assignment_places",
                       "assignments_routed", "experts_touched",
                       "layer_rounds")
    for n, budget in ((6, 6), (8, 3)):
        srv.submit(rng.integers(1, 512, n), max_new_tokens=budget)
    srv.run_to_completion()
    moved = _moved(before)
    # five rounds for the first request, the first two of them for both
    assert srv.stats["steps"] == 5
    assert moved["layer_rounds"] == 5 * layers
    assert moved["round_assignments"] == (5 + 2) * top_k * layers
    assert moved["round_assignment_places"] == 5 * 4 * top_k * layers
    assert moved["assignments_routed"] == (5 + 2 + 6 + 8) * top_k * layers
    assert moved["experts_touched"] <= (5 + 2) * top_k * layers


@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_lane_readmitted_after_standing_idle(name):
    """Lane 0's request ends after two tokens; lane 1's decodes 40 more
    (five times K-EXAONE's tiny ring, more than twice SmallThinker's),
    through rounds in which lane 0 is nobody's: its rings, registers and
    states stand as its request left them (before this mask a stale token
    went through them every round).  The request admitted to lane 0
    afterwards is spliced over every part and served ``generate``'s
    tokens, as is the neighbour."""
    model, params, _ = _built(name)
    rng = np.random.default_rng(23)
    short, long, later = (rng.integers(1, 512, n) for n in (11, 7, 17))
    srv = serving.DecodeServer(model, params, slots=2, max_len=128)
    first = srv.submit(short, max_new_tokens=2)
    second = srv.submit(long, max_new_tokens=44)
    while first not in srv.finished():
        srv.step()
    srv.land()
    assert srv._slot[0] is None and srv._slot[1] is not None
    ended, other = _lane_parts(srv, 0), _lane_parts(srv, 1)
    assert ended
    for _ in range(30):
        srv.step()
    assert all(np.array_equal(a, b)
               for a, b in zip(_lane_parts(srv, 0), ended))
    assert not any(np.array_equal(a, b)
                   for a, b in zip(_lane_parts(srv, 1), other))
    third = srv.submit(later, max_new_tokens=10)
    assert srv._slot[0] is not None
    served = srv.run_to_completion()
    assert served[second] == _want(model, params, long, 44)
    assert served[third] == _want(model, params, later, 10)
    assert served[first] == _want(model, params, short, 2)
