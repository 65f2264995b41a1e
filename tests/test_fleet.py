"""Decode fleet control plane (fleet/, ISSUE 14).

The invariants everything hangs on:

- a stream admitted through the router produces EXACTLY the tokens of a
  standalone DecodeServer on the same prompt (the fleet is transparent);
- a stream is pinned to its server for its lifetime: a mid-fleet
  rolling weight update swaps versions UNDER the stream (no drop, no
  re-route), and a rollback to a pinned version never serves a
  newer-version continuation (every chunk's weight_version stamp is the
  evidence);
- scale-in is drain-before-stop: the victim finishes its in-flight
  streams, leaves the table, and only then is stopped — the acceptance
  test rolls weights across a 4-server fleet under sustained open-loop
  load with zero dropped streams.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.config import CoordinatorConfig
from parameter_server_distributed_tpu.core.coordinator_core import (
    CoordinatorCore)
from parameter_server_distributed_tpu.fleet import messages as fmsg
from parameter_server_distributed_tpu.fleet.controller import (
    FleetController, ScalePolicy, occupancy, scale_decision)
from parameter_server_distributed_tpu.fleet.decode import FleetDecodeServer
from parameter_server_distributed_tpu.fleet.router import (FleetRouter,
                                                           score_backends)
from parameter_server_distributed_tpu.models.generation import generate
from parameter_server_distributed_tpu.models.serving import DecodeServer
from parameter_server_distributed_tpu.models.transformer import (
    Transformer, TransformerConfig)
from parameter_server_distributed_tpu.rpc.service import RpcClient
from parameter_server_distributed_tpu.server.coordinator_service import (
    Coordinator)

VOCAB = 64


def tiny(**kw):
    cfg = dict(vocab=VOCAB, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_seq=160, dtype=jnp.float32)
    cfg.update(kw)
    return Transformer(TransformerConfig(**cfg))


_MODEL = tiny()
_PARAMS = _MODEL.init_params(0)


def reference(prompt, n):
    out = generate(_MODEL, _PARAMS, jnp.asarray([prompt], jnp.int32), n)
    return list(np.asarray(out)[0])


def entry(sid, state=fmsg.MEMBER_ACTIVE, free=4, queue=0, slots=4,
          version=0, fp=b""):
    return fmsg.FleetEntry(server_id=sid, address=f"h:{5000 + sid}",
                           slots=slots, free_slots=free,
                           queue_depth=queue, weight_version=version,
                           state=state, prefix_fp=fp)


# --------------------------------------------------------------- registry
def test_fleet_registry_lifecycle_and_epochs():
    core = CoordinatorCore("127.0.0.1", 1234)
    e0 = core.fleet_register(7, "h:1", 4)
    epoch, table, target = core.fleet_table()
    assert epoch == e0 and target == 0
    assert [(m.server_id, m.state, m.slots) for m in table] == \
        [(7, fmsg.MEMBER_ACTIVE, 4)]
    # heartbeat refreshes load without bumping the epoch
    state = core.fleet_heartbeat(7, free_slots=1, queue_depth=3,
                                 weight_version=5, active_streams=3)
    assert state == fmsg.MEMBER_ACTIVE
    epoch2, table, _ = core.fleet_table()
    assert epoch2 == epoch
    assert (table[0].free_slots, table[0].queue_depth,
            table[0].weight_version) == (1, 3, 5)
    # drain -> leave: two transitions, two epoch bumps
    assert core.fleet_drain(7)
    assert core.fleet_state(7) == fmsg.MEMBER_DRAINING
    assert core.fleet_leave(7)
    epoch3, table, _ = core.fleet_table()
    assert table[0].state == fmsg.MEMBER_GONE and epoch3 == epoch2 + 2
    # heartbeat from a GONE server asks it to re-register
    assert core.fleet_heartbeat(7, 4, 0, 0, 0) is None
    assert core.fleet_drain(7) is False
    # re-register resurrects the row
    core.fleet_register(7, "h:2", 8)
    assert core.fleet_state(7) == fmsg.MEMBER_ACTIVE
    assert core.fleet_table()[1][0].slots == 8


def test_fleet_reap_marks_gone():
    now = [0.0]
    core = CoordinatorCore("127.0.0.1", 1234, time_fn=lambda: now[0])
    core.fleet_register(1, "h:1", 4)
    core.fleet_register(2, "h:2", 4)
    now[0] = 10.0
    core.fleet_heartbeat(2, 4, 0, 0, 0)
    assert core.remove_stale_fleet(5.0) == [1]
    assert core.fleet_state(1) == fmsg.MEMBER_GONE
    assert core.fleet_state(2) == fmsg.MEMBER_ACTIVE


def test_fleet_manual_scale_target():
    core = CoordinatorCore("127.0.0.1", 1234)
    core.set_fleet_target(3)
    assert core.fleet_table()[2] == 3
    core.set_fleet_target(0)
    assert core.fleet_table()[2] == 0


# ---------------------------------------------------------------- scoring
def test_router_scoring_prefers_free_slots_then_queue():
    entries = [entry(0, free=1), entry(1, free=3),
               entry(2, free=3, queue=2),
               entry(3, state=fmsg.MEMBER_DRAINING, free=4),
               entry(4, state=fmsg.MEMBER_GONE, free=4)]
    ranked = score_backends(entries)
    assert [e.server_id for e in ranked] == [1, 2, 0]
    # claims debit capacity the table has not yet heartbeaten
    ranked = score_backends(entries, claims={1: 3})
    assert [e.server_id for e in ranked] == [2, 0, 1]


def test_router_scoring_prefix_overlap_affinity():
    """ISSUE 20: cached-prefix overlap counts as weight free slots —
    a backend already holding the prompt's leading blocks outranks an
    equally-free one; with no fingerprints, no prompt hashes, or weight
    0 the order is EXACTLY the PR 14 free-slot score (the downgrade)."""
    from parameter_server_distributed_tpu.models.prefix_tree import (
        pack_fp)
    hashes = [111, 222]
    entries = [entry(0, free=2), entry(1, free=2),
               entry(2, free=2, fp=pack_fp([111, 222, 333]))]
    # overlap 2 on server 2 beats the sid tie-break
    ranked = score_backends(entries, prompt_hashes=hashes, weight=1.0)
    assert [e.server_id for e in ranked] == [2, 0, 1]
    # one-block overlap loses to one extra free slot at weight 1.0 ...
    entries = [entry(0, free=3), entry(1, free=2, fp=pack_fp([111]))]
    ranked = score_backends(entries, prompt_hashes=hashes, weight=1.0)
    assert [e.server_id for e in ranked] == [0, 1]
    # ... and wins at weight 2.0
    ranked = score_backends(entries, prompt_hashes=hashes, weight=2.0)
    assert [e.server_id for e in ranked] == [1, 0]
    # downgrades: weight 0 / no hashes / fingerprint-free entries all
    # reproduce the PR 14 ordering
    entries = [entry(0, free=1), entry(1, free=3),
               entry(2, free=3, queue=2, fp=pack_fp([111, 222]))]
    assert [e.server_id for e in
            score_backends(entries, prompt_hashes=hashes,
                           weight=0.0)] == [1, 2, 0]
    assert [e.server_id for e in
            score_backends(entries, prompt_hashes=None)] == [1, 2, 0]
    # a diverging prompt (no leading-block match) scores zero overlap
    assert [e.server_id for e in
            score_backends(entries, prompt_hashes=[999],
                           weight=5.0)] == [1, 2, 0]


def test_heartbeat_carries_prefix_fingerprint():
    """The fingerprint rides the heartbeat into the fleet table and
    back out of UpdateFleet QUERY — pre-radix heartbeats (no field)
    leave it empty rather than erroring."""
    core = CoordinatorCore("127.0.0.1", 1234)
    core.fleet_register(7, "h:1", 4)
    core.fleet_heartbeat(7, 4, 0, 0, 0, prefix_fp=b"\x01\x02\x03\x04")
    _epoch, table, _t = core.fleet_table()
    assert table[0].prefix_fp == b"\x01\x02\x03\x04"
    core.fleet_heartbeat(7, 4, 0, 0, 0)  # positional legacy caller
    assert core.fleet_table()[1][0].prefix_fp == b""


def test_heartbeat_fingerprint_rpc_roundtrip():
    coordinator = Coordinator(CoordinatorConfig(bind_address="127.0.0.1",
                                                port=0))
    cport = coordinator.start()
    coordinator.core.fleet_register(0, "h:1", 4)
    client = RpcClient(f"127.0.0.1:{cport}", "coordinator.Coordinator",
                       fmsg.FLEET_COORD_METHODS)
    try:
        client.call("UpdateFleet", fmsg.FleetRequest(
            server_id=0, action=fmsg.FLEET_HEARTBEAT, free_slots=4,
            prefix_fp=b"\xaa\xbb\xcc\xdd"), timeout=5.0)
        resp = client.call("UpdateFleet", fmsg.FleetRequest(
            server_id=-1, action=fmsg.FLEET_QUERY), timeout=5.0)
    finally:
        client.close()
        coordinator.stop()
    by_sid = {int(e.server_id): bytes(e.prefix_fp)
              for e in resp.entries}
    assert by_sid[0] == b"\xaa\xbb\xcc\xdd"


def test_scale_decision_watermarks_and_manual():
    policy = ScalePolicy(low=0.3, high=0.8, min_servers=1, max_servers=4)
    idle = [entry(0, free=4), entry(1, free=4)]
    busy = [entry(0, free=0, queue=2), entry(1, free=1)]
    assert occupancy(idle) == 0.0
    assert occupancy(busy) == pytest.approx((4 + 3 + 2) / 8)
    assert scale_decision(idle, policy) == 1          # below low: -1
    assert scale_decision(busy, policy) == 3          # above high: +1
    assert scale_decision(busy, policy, manual_target=2) == 2
    assert scale_decision(idle, policy, manual_target=9) == 4  # clamp
    one = [entry(0, free=4)]
    assert scale_decision(one, policy) == 1           # min floor


# --------------------------------------------------------- gRPC plumbing
class _Fleet:
    """One coordinator + N FleetDecodeServers + router, torn down in
    reverse order.  Servers share one process (the decode dispatch lock
    serializes their jax) — the production shape is one per process,
    but loopback pinning/drain/version semantics are identical."""

    def __init__(self, n, slots=4, prompt_cache=0, heartbeat_s=0.1,
                 round_delay_s=0.0):
        self.coordinator = Coordinator(CoordinatorConfig(
            bind_address="127.0.0.1", port=0))
        cport = self.coordinator.start()
        self.caddr = f"127.0.0.1:{cport}"
        self.servers = []
        for sid in range(n):
            server = FleetDecodeServer(
                DecodeServer(_MODEL, _PARAMS, slots=slots, max_len=160,
                             prompt_cache=prompt_cache),
                server_id=sid, coordinator=self.caddr,
                heartbeat_s=heartbeat_s)
            # synthetic service time (the seam fleet/decode.py leaves
            # for tests): keeps streams IN FLIGHT long enough for a rollout
            # or drain to land mid-stream on this fast tiny model
            server._round_delay_s = round_delay_s
            server.start()
            self.servers.append(server)
        deadline = time.time() + 10.0
        while time.time() < deadline:
            _e, table, _t = self.coordinator.core.fleet_table()
            if sum(1 for f in table
                   if f.state == fmsg.MEMBER_ACTIVE) == n:
                break
            time.sleep(0.02)
        self.router = FleetRouter(self.caddr, poll_s=0.05)
        rport = self.router.start()
        self.client = RpcClient(f"127.0.0.1:{rport}",
                                fmsg.DECODE_SERVICE, fmsg.DECODE_METHODS)
        self.controller = FleetController(self.coordinator.core)

    def stream(self, prompt, max_new=6):
        """Submit through the router; returns (tokens, versions, error)."""
        chunks = list(self.client.call(
            "SubmitStream",
            fmsg.DecodeRequest(tokens=[int(t) for t in prompt],
                               max_new=max_new, temperature=-1.0),
            timeout=None))
        assert chunks and chunks[-1].done
        tokens = [int(c.token) for c in chunks if not c.done]
        versions = {int(c.weight_version) for c in chunks}
        return tokens, versions, chunks[-1].error

    def close(self):
        self.controller.close()
        self.client.close()
        self.router.stop()
        for server in self.servers:
            server.stop()
        self.coordinator.stop()


@pytest.fixture
def fleet2():
    fleet = _Fleet(2)
    yield fleet
    fleet.close()


def test_routed_stream_matches_standalone_generate(fleet2, rng):
    prompt = [int(t) for t in rng.integers(1, VOCAB, 7)]
    tokens, versions, error = fleet2.stream(prompt, max_new=6)
    assert not error
    assert tokens == reference(prompt, 6)
    assert versions == {0}  # boot weights


def test_router_spreads_streams_and_pins(fleet2, rng):
    """Concurrent streams land on BOTH servers (free-slot score +
    claims), and each stream's chunks all come from one server."""
    results = []
    lock = threading.Lock()

    def drive():
        prompt = [int(t) for t in rng.integers(1, VOCAB, 5)]
        out = fleet2.stream(prompt, max_new=8)
        with lock:
            results.append(out)

    threads = [threading.Thread(target=drive, daemon=True,
                                name=f"fleet-test-{i}") for i in range(6)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=60.0)
    assert len(results) == 6
    assert all(not err for _t, _v, err in results)
    served = [s.streams_served for s in fleet2.servers]
    assert sum(served) == 6
    assert all(n > 0 for n in served), f"one server idle: {served}"


def test_router_prefers_backend_with_cached_prefix(rng):
    """End-to-end prefix-aware placement (ISSUE 20): after one stream
    warms a backend's radix cache with a long shared prefix, a second
    stream sharing that prefix routes to the SAME backend (overlap
    outbids the sid tie-break) and rides its suffix-only path — and
    stays token-exact through the router."""
    fleet = _Fleet(2, prompt_cache=8)
    try:
        shared = [int(t) for t in rng.integers(1, VOCAB, 18)]
        first = shared + [int(t) for t in rng.integers(1, VOCAB, 3)]
        tokens, _v, error = fleet.stream(first, max_new=4)
        assert not error and tokens == reference(first, 4)
        warm = next(s for s in fleet.servers if s.streams_served == 1)
        # wait for the fingerprint heartbeat to land in the fleet table
        deadline = time.time() + 10.0
        while time.time() < deadline:
            _e, table, _t = fleet.coordinator.core.fleet_table()
            if any(m.server_id == warm.server_id and m.prefix_fp
                   for m in table):
                break
            time.sleep(0.02)
        else:
            pytest.fail("prefix fingerprint never heartbeaten")
        second = shared + [int(t) for t in rng.integers(1, VOCAB, 4)]
        tokens, _v, error = fleet.stream(second, max_new=4)
        assert not error and tokens == reference(second, 4)
        assert warm.streams_served == 2, "router ignored the warm cache"
        assert warm.server.stats["prefix_hits"] == 1  # suffix-only path
    finally:
        fleet.close()


@pytest.mark.lockcheck
def test_lockcheck_concurrent_prefix_admit_extend_evict_swap(rng):
    """Radix cache under the real thread mix, PSDT_LOCK_CHECK=1: gRPC
    streams sharing prefixes (admit / extend / byte-bound evict on the
    decode loop), mid-hammer weight swaps (tree clear), and the
    heartbeat thread reading the fingerprint snapshot — every stream
    token-exact, no lock-order assertion."""
    server = FleetDecodeServer(
        DecodeServer(_MODEL, _PARAMS, slots=4, max_len=160,
                     prompt_cache=8, prefix_cache_bytes=1 << 16),
        server_id=0, heartbeat_s=0.02)
    server.start()
    shared = [int(t) for t in rng.integers(1, VOCAB, 14)]
    prompts = [shared[:6 + 4 * (i % 3)]
               + [int(t) for t in rng.integers(1, VOCAB, 3)]
               for i in range(12)]
    results = []
    lock = threading.Lock()

    def drive(worker):
        client = RpcClient(server.address, fmsg.DECODE_SERVICE,
                           fmsg.DECODE_METHODS)
        try:
            for prompt in prompts[worker::4]:
                chunks = list(client.call(
                    "SubmitStream",
                    fmsg.DecodeRequest(tokens=prompt, max_new=4,
                                       temperature=-1.0), timeout=None))
                with lock:
                    results.append(
                        (prompt,
                         [int(c.token) for c in chunks if not c.done],
                         chunks[-1].error))
        finally:
            client.close()

    threads = [threading.Thread(target=drive, args=(i,), daemon=True,
                                name=f"prefix-hammer-{i}")
               for i in range(4)]
    for thread in threads:
        thread.start()
    store = {name: np.array(arr) for name, arr in _PARAMS.items()}
    for version in (1, 2, 3):  # same values: swaps stay token-exact
        server.publish_version(store, version)
        resp = server.Control(fmsg.DecodeControlRequest(
            action=fmsg.CTRL_SWAP, version=version), None)
        assert resp.success, resp.message
        time.sleep(0.05)
    for thread in threads:
        thread.join(timeout=120.0)
    try:
        assert len(results) == 12
        assert all(not err for _p, _t, err in results)
        for prompt, tokens, _err in results:
            assert tokens == reference(prompt, 4)
    finally:
        server.stop()


def test_empty_fleet_rejects_instead_of_hanging():
    coordinator = Coordinator(CoordinatorConfig(bind_address="127.0.0.1",
                                                port=0))
    cport = coordinator.start()
    router = FleetRouter(f"127.0.0.1:{cport}", poll_s=0.05)
    rport = router.start()
    client = RpcClient(f"127.0.0.1:{rport}", fmsg.DECODE_SERVICE,
                       fmsg.DECODE_METHODS)
    try:
        chunks = list(client.call(
            "SubmitStream", fmsg.DecodeRequest(tokens=[1, 2],
                                               max_new=4), timeout=10.0))
        assert chunks[-1].error and chunks[-1].done
    finally:
        client.close()
        router.stop()
        coordinator.stop()


def test_bad_request_is_a_stream_error_not_a_crash(fleet2):
    _tokens, _versions, error = fleet2.stream([], max_new=4)
    assert "empty prompt" in error
    # the fleet still serves
    tokens, _versions, error = fleet2.stream([1, 2, 3], max_new=4)
    assert not error and len(tokens) == 4


# ------------------------------------------------------------ version skew
def test_rolling_update_and_rollback_version_rows(rng):
    """The ISSUE's version-skew rows: (1) a stream pinned to a v_k
    server survives a mid-fleet rollout to v_{k+1} — its early chunks
    decoded under v_k, its late chunks under v_{k+1}, nothing dropped;
    (2) after rollback to a pinned version, NO chunk anywhere carries a
    newer version until unpin."""
    fleet = _Fleet(2, round_delay_s=0.01)
    try:
        store = {name: np.array(arr) for name, arr in _PARAMS.items()}
        for server in fleet.servers:
            server.publish_version(store, 1)
        # a long stream rides through the rollout
        result = {}

        def long_stream():
            prompt = [int(t) for t in rng.integers(1, VOCAB, 5)]
            result["out"] = fleet.stream(prompt, max_new=40)

        thread = threading.Thread(target=long_stream, daemon=True,
                                  name="fleet-test-long")
        thread.start()
        time.sleep(0.15)  # stream under way on its pinned server
        swapped = fleet.controller.rolling_update(1)
        assert all(swapped.values()), swapped
        thread.join(timeout=60.0)
        tokens, versions, error = result["out"]
        assert not error and len(tokens) == 40
        assert versions <= {0, 1} and 1 in versions, versions
        # row 2: publish v2 everywhere, roll back to pinned v1
        for server in fleet.servers:
            server.publish_version(store, 2)
        rolled = fleet.controller.rollback(1)
        assert all(rolled.values()), rolled
        for _ in range(4):
            _tokens, versions, error = fleet.stream(
                [int(t) for t in rng.integers(1, VOCAB, 4)], max_new=6)
            assert not error
            assert versions == {1}, \
                f"newer-version continuation: {versions}"
        # pinned servers refuse the newer version outright
        refused = fleet.controller.rolling_update(2)
        assert not any(refused.values()), refused
        fleet.controller.unpin()
        assert all(fleet.controller.rolling_update(2).values())
        _tokens, versions, _error = fleet.stream([1, 2, 3], max_new=4)
        assert versions == {2}
    finally:
        fleet.close()


def test_swap_of_unheld_version_refused(fleet2):
    res = fleet2.controller.rolling_update(99)
    assert not any(res.values())


# ------------------------------------------------------------- autoscaler
class _FakeSpawner:
    def __init__(self):
        self.spawned = 0
        self.stopped = []

    def spawn(self):
        self.spawned += 1

    def stop(self, server_id):
        self.stopped.append(server_id)


def test_autoscaler_scale_out_on_high_occupancy():
    core = CoordinatorCore("127.0.0.1", 1234)
    core.fleet_register(0, "h:1", 4)
    core.fleet_heartbeat(0, free_slots=0, queue_depth=4,
                         weight_version=0, active_streams=4)
    spawner = _FakeSpawner()
    controller = FleetController(core, spawner=spawner,
                                 policy=ScalePolicy(max_servers=4))
    assert controller.scale_step() == 2
    assert spawner.spawned == 1
    # the new server has not registered yet: a second step re-asks for 2
    # but must not spawn a third while one drain/spawn is outstanding...
    core.fleet_register(1, "h:2", 4)  # ...it arrives
    core.fleet_heartbeat(1, 3, 0, 0, 1)
    core.fleet_heartbeat(0, 1, 0, 0, 3)
    assert controller.scale_step() == 2  # 0.5 occupancy: steady state
    assert spawner.spawned == 1


def test_autoscaler_scale_in_drains_before_stop():
    """The drain-before-stop contract: the victim is DRAINED first,
    spawner.stop only fires after the server reached GONE."""
    core = CoordinatorCore("127.0.0.1", 1234)
    for sid in range(2):
        core.fleet_register(sid, f"h:{sid}", 4)
        core.fleet_heartbeat(sid, 4, 0, 0, 0)
    spawner = _FakeSpawner()
    controller = FleetController(core, spawner=spawner,
                                 policy=ScalePolicy(low=0.3, high=0.8,
                                                    min_servers=1))
    assert controller.scale_step() == 1      # idle fleet: scale in
    assert core.fleet_state(1) == fmsg.MEMBER_DRAINING  # youngest first
    assert spawner.stopped == []             # NOT stopped yet
    assert controller.scale_step() == 1      # still draining: no action
    assert spawner.stopped == []
    core.fleet_leave(1)                      # drain completes
    controller.scale_step()
    assert spawner.stopped == [1]            # only now reaped
    controller.close()


def test_manual_scale_target_via_rpc(fleet2):
    resp = RpcClient(fleet2.caddr, "coordinator.Coordinator",
                     fmsg.FLEET_COORD_METHODS)
    try:
        out = resp.call("UpdateFleet", fmsg.FleetRequest(
            server_id=-1, action=fmsg.FLEET_SCALE, scale_target=3),
            timeout=5.0)
        assert out.scale_target == 3
    finally:
        resp.close()
    assert fleet2.coordinator.core.fleet_table()[2] == 3


# ------------------------------------------------------------ drain paths
def test_coordinator_drain_finishes_streams_then_leaves(fleet2, rng):
    """pst-ctl fleet-drain semantics over the heartbeat: the drained
    server's in-flight stream completes, the server goes GONE, new
    streams route to the survivor."""
    target = fleet2.servers[1]
    result = {}

    def long_stream():
        prompt = [int(t) for t in rng.integers(1, VOCAB, 5)]
        chunks = list(RpcClient(target.address, fmsg.DECODE_SERVICE,
                                fmsg.DECODE_METHODS).call(
            "SubmitStream",
            fmsg.DecodeRequest(tokens=prompt, max_new=30,
                               temperature=-1.0), timeout=None))
        result["tokens"] = [c.token for c in chunks if not c.done]
        result["error"] = chunks[-1].error

    thread = threading.Thread(target=long_stream, daemon=True,
                              name="fleet-test-drain")
    thread.start()
    time.sleep(0.1)
    fleet2.coordinator.core.fleet_drain(1)
    assert target.wait_drained(30.0), "drain never completed"
    thread.join(timeout=30.0)
    assert not result["error"] and len(result["tokens"]) == 30
    assert fleet2.coordinator.core.fleet_state(1) == fmsg.MEMBER_GONE
    # draining server rejects direct new submissions
    direct = RpcClient(target.address, fmsg.DECODE_SERVICE,
                       fmsg.DECODE_METHODS)
    try:
        chunks = list(direct.call("SubmitStream", fmsg.DecodeRequest(
            tokens=[1, 2], max_new=2), timeout=10.0))
        assert chunks[-1].error
    finally:
        direct.close()
    # the router still serves through the survivor
    tokens, _versions, error = fleet2.stream([3, 4, 5], max_new=4)
    assert not error and len(tokens) == 4


# -------------------------------------------------------------- ctl / CLI
def test_ctl_fleet_and_scale_cli(fleet2, capsys):
    from parameter_server_distributed_tpu.cli.ctl_main import main
    assert main(["fleet", fleet2.caddr]) == 0
    out = capsys.readouterr().out
    assert "2 servers" in out and "server 0" in out and "active" in out
    assert main(["scale", "3", fleet2.caddr]) == 0
    assert "scale target 3" in capsys.readouterr().out
    assert fleet2.coordinator.core.fleet_table()[2] == 3
    assert main(["fleet-drain", "1", fleet2.caddr]) == 0
    assert fleet2.coordinator.core.fleet_state(1) == fmsg.MEMBER_DRAINING
    assert main(["fleet-drain", "42", fleet2.caddr]) == 1


def test_fleet_rollup_rendered(fleet2):
    """The coordinator's GetClusterMetrics carries a fleet dict and
    pst-status renders it as one line."""
    import json

    from parameter_server_distributed_tpu.obs.export import render_fleet
    from parameter_server_distributed_tpu.rpc import messages as m
    client = RpcClient(fleet2.caddr, m.COORDINATOR_SERVICE,
                       m.COORDINATOR_EXT_METHODS)
    try:
        rollup = json.loads(client.call(
            "GetClusterMetrics", m.ClusterMetricsRequest(),
            timeout=5.0).rollup_json)
    finally:
        client.close()
    fleet = rollup["fleet"]
    assert fleet["states"]["active"] == 2
    assert fleet["slots"] == 8
    line = render_fleet(fleet)
    assert "2 active" in line and "slots free" in line


# -------------------------------------------------------------- acceptance
def test_rolling_update_4_server_fleet_zero_dropped_streams(rng):
    """THE acceptance row: a rolling weight update across a 4-server
    fleet under sustained open-loop load over loopback gRPC completes
    with zero dropped streams — every submitted stream runs to its done
    chunk with no error, while every server confirms its swap."""
    fleet = _Fleet(4, slots=2)
    try:
        store = {name: np.array(arr) for name, arr in _PARAMS.items()}
        for server in fleet.servers:
            server.publish_version(store, 1)
        results = []
        lock = threading.Lock()
        stop = threading.Event()

        def load_generator(i):
            while not stop.is_set():
                prompt = [int(t) for t in rng.integers(1, VOCAB, 4)]
                out = fleet.stream(prompt, max_new=10)
                with lock:
                    results.append(out)

        threads = [threading.Thread(target=load_generator, args=(i,),
                                    daemon=True, name=f"fleet-load-{i}")
                   for i in range(6)]
        for thread in threads:
            thread.start()
        time.sleep(0.3)  # load established
        swapped = fleet.controller.rolling_update(1)
        assert all(swapped.values()), swapped
        assert len(swapped) == 4
        time.sleep(0.3)  # load continues over the rolled fleet
        stop.set()
        for thread in threads:
            thread.join(timeout=60.0)
        assert len(results) >= 6
        dropped = [err for _t, _v, err in results if err]
        assert dropped == [], f"dropped streams: {dropped}"
        assert all(len(tokens) == 10 for tokens, _v, _e in results)
        # post-rollout streams decode under the new version
        _tokens, versions, error = fleet.stream([1, 2, 3], max_new=4)
        assert not error and versions == {1}
        assert sum(s.streams_served for s in fleet.servers) >= len(results)
    finally:
        fleet.close()


def test_abandoned_stream_frees_its_slot(rng):
    """A client that disconnects mid-stream must not burn its slot for
    the rest of max_new: the handler marks the stream cancelled and the
    decode loop reaps it (review finding — the capacity-collapse
    feedback loop under overload)."""
    server = FleetDecodeServer(
        DecodeServer(_MODEL, _PARAMS, slots=1, max_len=160),
        server_id=0, heartbeat_s=0.05)
    server._round_delay_s = 0.02
    server.start()
    client = RpcClient(server.address, fmsg.DECODE_SERVICE,
                       fmsg.DECODE_METHODS)
    try:
        prompt = [int(t) for t in rng.integers(1, VOCAB, 4)]
        chunks = client.call("SubmitStream", fmsg.DecodeRequest(
            tokens=prompt, max_new=200, temperature=-1.0), timeout=None)
        next(chunks)  # stream established and decoding
        chunks.cancel()  # client walks away mid-stream
        deadline = time.time() + 10.0
        while time.time() < deadline and server.server.active:
            time.sleep(0.02)
        # 200 rounds at 20ms would be 4s; the reap frees it in a round
        assert server.server.active == 0, "abandoned slot never freed"
        # and the freed slot serves the next client
        out = list(client.call("SubmitStream", fmsg.DecodeRequest(
            tokens=prompt, max_new=3, temperature=-1.0), timeout=30.0))
        assert out[-1].done and not out[-1].error
    finally:
        client.close()
        server.stop()


def test_pinned_version_survives_continued_publication():
    """The rollback pin exempts its version from LRU eviction: the
    training side keeps publishing past the bounded store, and the
    pinned version must stay swappable (review finding — a version-
    split fleet could otherwise never be re-pinned)."""
    server = FleetDecodeServer(
        DecodeServer(_MODEL, _PARAMS, slots=1, max_len=160),
        server_id=0, versions_kept=2, heartbeat_s=0.05)
    server.start()
    try:
        store = {name: np.array(arr) for name, arr in _PARAMS.items()}
        server.publish_version(store, 1)
        resp = server.Control(fmsg.DecodeControlRequest(
            action=fmsg.CTRL_ROLLBACK, version=1), None)
        assert resp.success and resp.pinned_version == 1
        for version in (2, 3, 4, 5):
            server.publish_version(store, version)
        with server._lock:
            held = list(server._versions)
        assert 1 in held, f"pinned version evicted: {held}"
        assert len(held) == 2  # the cap still holds for the rest
        # a rollback retry (new server joining the pinned fleet, a
        # failed swap) still finds the pinned version
        resp = server.Control(fmsg.DecodeControlRequest(
            action=fmsg.CTRL_ROLLBACK, version=1), None)
        assert resp.success, resp.message
        assert server.weight_version() == 1
    finally:
        server.stop()


@pytest.mark.parametrize("action", [fmsg.CTRL_SWAP, fmsg.CTRL_ROLLBACK],
                         ids=["swap", "rollback"])
def test_every_chunk_carries_the_version_that_decoded_it(rng, action):
    """A swap to OTHER weights mid-stream, the DecodeServer a round ahead
    of its step(): the round in flight ran under the weights that leave,
    so its token is delivered before the swap, under the old stamp.  Each
    token is the one the version on its chunk decodes (against the one
    cache, as the stream's is), and no old chunk follows a new one."""
    from test_serving_pipelined import decoded_by

    stores = {0: _PARAMS, 7: _MODEL.init_params(3)}
    server = FleetDecodeServer(
        DecodeServer(_MODEL, _PARAMS, slots=2, max_len=160),
        server_id=0, heartbeat_s=0.05)
    server.auto_advance = False
    server._round_delay_s = 0.02
    server.start()
    client = RpcClient(server.address, fmsg.DECODE_SERVICE,
                       fmsg.DECODE_METHODS)
    try:
        prompt = [int(t) for t in rng.integers(1, VOCAB, 6)]
        stream = client.call("SubmitStream", fmsg.DecodeRequest(
            tokens=prompt, max_new=40, temperature=-1.0), timeout=None)
        chunks = [next(stream) for _ in range(4)]   # decoding, a round ahead
        server.publish_version(
            {name: np.array(arr) for name, arr in stores[7].items()}, 7)
        resp = server.Control(fmsg.DecodeControlRequest(
            action=action, version=7), None)
        assert resp.success and resp.weight_version == 7, resp.message
        chunks += list(stream)
    finally:
        client.close()
        server.stop()
    assert chunks[-1].done and not chunks[-1].error
    tokens = [int(c.token) for c in chunks if not c.done]
    stamps = [int(c.weight_version) for c in chunks if not c.done]
    assert len(tokens) == 40 and set(stamps) == {0, 7}
    assert stamps == sorted(stamps) and chunks[-1].weight_version == 7
    assert tokens == decoded_by(_MODEL, stores, prompt, stamps, max_len=160)
    # (the comparison can tell: the first new token attributed to the old
    # weights, or the last old one to the new, is another stream)
    at = stamps.index(7)
    for wrong in (stamps[:at] + [0] + stamps[at + 1:],
                  stamps[:at - 1] + [7] + stamps[at:]):
        assert tokens != decoded_by(_MODEL, stores, prompt, wrong,
                                    max_len=160)


def test_control_swap_reports_real_outcome():
    """Control(SWAP) success means the swap APPLIED — a version evicted
    or a store the DecodeServer rejects must come back success=False
    (review finding — 'processed' is not 'succeeded')."""
    server = FleetDecodeServer(
        DecodeServer(_MODEL, _PARAMS, slots=1, max_len=160),
        server_id=0, heartbeat_s=0.05)
    server.start()
    try:
        # a shape-drifted publication: held, but swap_params raises
        bad = {name: np.zeros((3, 3), np.float32) for name in _PARAMS}
        server.publish_version(bad, 7)
        resp = server.Control(fmsg.DecodeControlRequest(
            action=fmsg.CTRL_SWAP, version=7), None)
        assert not resp.success and "failed" in resp.message
        assert server.weight_version() == 0  # last-good kept
    finally:
        server.stop()


def test_fleet_messages_wire_roundtrip():
    req = fmsg.FleetRequest(server_id=3, action=fmsg.FLEET_HEARTBEAT,
                            address="h:1", slots=8, free_slots=2,
                            queue_depth=5, weight_version=7,
                            active_streams=6,
                            prefix_fp=b"\x01\x00\x00\x00\x02\x00\x00\x00")
    assert fmsg.FleetRequest.decode(req.encode()) == req
    ent = fmsg.FleetEntry(server_id=1, address="h:2", slots=4,
                          prefix_fp=b"\xff\xee\xdd\xcc")
    assert fmsg.FleetEntry.decode(ent.encode()) == ent
    resp = fmsg.FleetResponse(epoch=4, success=True, message="ok",
                              self_state=1, scale_target=2,
                              entries=[fmsg.FleetEntry(server_id=1,
                                                       address="h:2",
                                                       slots=4)])
    assert fmsg.FleetResponse.decode(resp.encode()) == resp
    chunk = fmsg.DecodeChunk(request_id=9, token=42, done=False,
                             weight_version=3)
    assert fmsg.DecodeChunk.decode(chunk.encode()) == chunk
    req2 = fmsg.DecodeRequest(tokens=[1, 2, 3], max_new=16,
                              temperature=-1.0, stop=[7])
    back = fmsg.DecodeRequest.decode(req2.encode())
    assert [int(t) for t in back.tokens] == [1, 2, 3]
    assert back.temperature == pytest.approx(-1.0)
