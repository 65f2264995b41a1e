"""Versioned delta serving + live weight publication (delta/, ISSUE 10).

The contract everything hangs on: chain-applied deltas are BIT-IDENTICAL
to a full pull at the same wire dtype, across dtypes, chunk budgets, and
stripe counts (the byte-identity oracle).  Around it: the depth-budget
and restore/reset fallback rows, serve_version monotonicity across
restore (a reused version id would silently serve a wrong delta base),
the client downgrade matrix (UNIMPLEMENTED / checksum mismatch =>
permanent per-connection full serve, zero failed steps), the
SubscribeWeights follower + DecodeServer hot swap acceptance, the
lockcheck-marked concurrent subscribe/apply/close hammer, and the obs
surfaces (rollup line, pst-trace events).
"""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.checkpoint.manager import (
    CheckpointManager)
from parameter_server_distributed_tpu.config import ParameterServerConfig
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.delta import messages as dmsg
from parameter_server_distributed_tpu.delta.chain import (DeltaChain,
                                                          store_crc)
from parameter_server_distributed_tpu.delta.client import (DeltaBaseMismatch,
                                                           DeltaPullState,
                                                           apply_frames)
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.server.ps_service import (
    ParameterServer, ParameterServerService)


def make_core(total_workers=1, lr=0.001, **kw):
    from parameter_server_distributed_tpu.core.optimizer import SGD

    return ParameterServerCore(total_workers=total_workers,
                               optimizer=SGD(lr), **kw)


def make_service(core, tmp=None):
    return ParameterServerService(core, CheckpointManager(
        core, directory=tmp or tempfile.mkdtemp(prefix="psdt-deltatest-"),
        checkpoint_interval=10**9, check_period_s=3600.0))


def rand_store(rng, shapes):
    return {name: rng.standard_normal(shape).astype(np.float32)
            for name, shape in shapes.items()}


def decode_full_pull(service, wire_dtype, iteration=0):
    """The full-serve oracle: what a plain pull at ``wire_dtype`` decodes
    to, through the ordinary encode-once path."""
    out = {}
    for chunk in service._parameter_chunks(iteration, wire_dtype):
        decoded = m.ParameterUpdate.decode(chunk.encode())
        assert decoded.ready
        out.update({t.name: t.to_array() for t in decoded.parameters})
    return out


def delta_round(service, state, wire_dtype, iteration=0, wire_bytes=None):
    """One client-side PullParametersDelta round against the in-process
    service (frames re-decoded from their wire bytes, like gRPC would;
    a ``wire_bytes`` list is given the round's encoded size)."""
    req = dmsg.DeltaPullRequest(worker_id=0, iteration=iteration,
                                wire_dtype=wire_dtype,
                                held_version=max(state.version, 0))
    encoded = [f.encode() for f in service.PullParametersDelta(req, None)]
    if wire_bytes is not None:
        wire_bytes.append(sum(len(e) for e in encoded))
    return apply_frames(iter(dmsg.DeltaFrame.decode(e) for e in encoded),
                        state)


def delta_counters():
    snap = obs_stats.REGISTRY.snapshot()["counters"]
    return (snap.get("ps.serve.delta_hit", 0),
            snap.get("ps.serve.delta_miss", 0),
            snap.get("ps.serve.delta_bytes", 0))


# --------------------------------------------------------- byte identity


@pytest.mark.parametrize("dtype_name,wire_dtype", [
    ("bf16", m.WIRE_BF16),
    ("f32", m.WIRE_F32),
    ("raw", m.WIRE_RAW_F32),
])
@pytest.mark.parametrize("chunk_bytes", [1 << 20, 96])
@pytest.mark.parametrize("stripes", [1, 3])
def test_chain_applied_deltas_bit_identical_to_full_pull(
        monkeypatch, dtype_name, wire_dtype, chunk_bytes, stripes):
    """THE oracle: across wire dtypes x chunk budgets x stripe counts,
    a receiver advancing version by version through delta chains holds
    exactly the bytes a fresh full pull at the same dtype would."""
    monkeypatch.setenv("PSDT_DELTA_DTYPE", dtype_name)
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", str(chunk_bytes))
    monkeypatch.setenv("PSDT_STRIPES", str(stripes))
    rng = np.random.default_rng(7)
    core = make_core()
    service = make_service(core)
    core.initialize_parameters(rand_store(
        rng, {"w": (512,), "b": (33,), "deep/k": (4, 64)}))
    state = DeltaPullState()
    first = delta_round(service, state, wire_dtype)
    assert not first.served_delta and first.store is not None
    served_any_delta = False
    for it in range(1, 5):
        grads = {k: rng.standard_normal(v.shape).astype(np.float32) * 1e-3
                 for k, v in core.get_parameters().items()}
        core.receive_gradients(0, it, grads)
        result = delta_round(service, state, wire_dtype, iteration=it)
        served_any_delta = served_any_delta or result.served_delta
        oracle = decode_full_pull(service, wire_dtype, iteration=it)
        assert set(result.store) == set(oracle)
        for name in oracle:
            np.testing.assert_array_equal(
                result.store[name].reshape(-1),
                oracle[name].reshape(-1),
                err_msg=f"{name} diverged from the full pull "
                        f"({dtype_name}, chunk={chunk_bytes}, "
                        f"stripes={stripes})")
    assert served_any_delta, "no round was ever delta-served"


def test_delta_bitwise_semantics_negzero_and_nan(monkeypatch):
    """The diff is BITWISE in wire space: 0.0 -> -0.0 ships (a float
    compare would miss it) and NaNs patch deterministically."""
    monkeypatch.setenv("PSDT_DELTA_DTYPE", "f32")
    core = make_core()
    service = make_service(core)
    core.initialize_parameters({"w": np.zeros(8, np.float32)})
    state = DeltaPullState()
    delta_round(service, state, m.WIRE_F32)  # arms the lazy chain
    # warm-up: the first post-arm version seeds the retained image (no
    # pair yet), and the round re-bases the receiver onto it
    core.initialize_parameters({"w": np.zeros(8, np.float32)})
    delta_round(service, state, m.WIRE_F32)
    tricky = np.zeros(8, np.float32)
    tricky[1] = np.float32(-0.0)
    tricky[2] = np.nan
    # the next initialize bumps the version by exactly one, so the sink
    # builds a (v, v+1) pair over the controlled value change
    core.initialize_parameters({"w": tricky})
    result = delta_round(service, state, m.WIRE_F32, iteration=1)
    assert result.served_delta
    oracle = decode_full_pull(service, m.WIRE_F32, iteration=1)
    got, want = result.store["w"], oracle["w"]
    assert got.tobytes() == want.tobytes()  # -0.0 and NaN, bit for bit
    assert np.signbit(got[1])  # the 0.0 -> -0.0 flip actually shipped
    assert np.isnan(got[2])


@pytest.mark.parametrize("optimizer", ["sgd", "momentum"])
def test_delta_serve_ships_a_fraction_of_the_full_serve(monkeypatch,
                                                        optimizer):
    """ISSUE 10's bound, in bytes: with fine-tuning-sized steps (most
    elements move less than a bf16 ulp) a receiver that pulls every
    version gets at most 30% of the full serve's bytes, one delta pull a
    step and never a full fallback; one that pulls every second version
    crosses a two-pair chain and still gets less than the whole model."""
    from parameter_server_distributed_tpu.core.optimizer import make_optimizer

    monkeypatch.setenv("PSDT_DELTA_DEPTH", "2")
    pulls, shape = 4, (8192,)
    rng = np.random.default_rng(0)
    store = rand_store(rng, {f"w{i}": shape for i in range(4)})
    ratio = {}
    for locality in (1, 2):
        core = ParameterServerCore(total_workers=1,
                                   optimizer=make_optimizer(optimizer, 1e-3))
        service = make_service(core)
        core.initialize_parameters(store)
        state, sizes = DeltaPullState(), []
        first = delta_round(service, state, m.WIRE_BF16, wire_bytes=sizes)
        assert not first.served_delta
        full_bytes = sizes.pop()
        grads = np.random.default_rng(1)
        iteration = 0

        def pull_after(applies, wire_bytes=None):
            nonlocal iteration
            for _ in range(applies):
                iteration += 1
                core.receive_gradients(0, iteration, {
                    name: (grads.standard_normal(shape) * 0.1)
                    .astype(np.float32) for name in store})
            return delta_round(service, state, m.WIRE_BF16,
                               iteration=iteration, wire_bytes=wire_bytes)

        # the first pull armed the lazy chain and the first apply after it
        # only seeds the retained image: one unmeasured apply + pull
        pull_after(1)
        served = [pull_after(locality, sizes).served_delta
                  for _ in range(pulls)]
        assert served == [True] * pulls, (locality, served)
        ratio[locality] = sum(sizes) / pulls / full_bytes
    assert 0 < ratio[1] <= 0.30, ratio
    assert ratio[1] < ratio[2] < 1.0, ratio


@pytest.mark.parametrize("indices,values", [
    # non-ascending indices whose max is out of range (idx[-1] in range)
    (np.array([12, 3], "<u4").tobytes(), np.zeros(2, "<f4").tobytes()),
    # truncated values buffer: not a multiple of the wire itemsize
    (np.array([1], "<u4").tobytes(), b"\x00\x01\x02"),
    # truncated index buffer: not a multiple of 4
    (b"\x00\x01\x02", np.zeros(1, "<f4").tobytes()),
])
def test_malformed_delta_entries_raise_base_mismatch(indices, values):
    """Wire-facing hardening: a buggy/version-skewed server's malformed
    entry must ride the SAME downgrade path as a drifted base (the
    never-failed-step / never-crashed-follower contract) — never a raw
    numpy IndexError/ValueError escaping into the caller's step."""
    state = DeltaPullState()
    state.note_full({"w": np.zeros(8, np.float32)}, 1)
    frame = dmsg.DeltaFrame(
        from_version=1, to_version=2, delta=True, last=True,
        wire_dtype=m.WIRE_F32, crc=0,
        entries=[dmsg.DeltaEntry(name="w", indices=indices,
                                 values=values, dense=False)])
    with pytest.raises(DeltaBaseMismatch):
        apply_frames(iter([frame]), state)


# ------------------------------------------------------- fallback matrix


def test_depth_budget_fallback_and_within_depth_hit(monkeypatch):
    monkeypatch.setenv("PSDT_DELTA_DEPTH", "2")
    rng = np.random.default_rng(3)
    core = make_core()
    service = make_service(core)
    core.initialize_parameters({"w": rng.standard_normal(256)
                                .astype(np.float32)})
    state = DeltaPullState()
    delta_round(service, state, m.WIRE_BF16)
    held_at_base = state.version
    for it in range(1, 4):  # 3 applies > depth 2
        core.receive_gradients(
            0, it, {"w": rng.standard_normal(256).astype(np.float32)})
    # 3 versions behind with depth 2: full serve
    h0, m0, _ = delta_counters()
    behind = DeltaPullState()
    behind.base = {k: v.copy() for k, v in state.base.items()}
    behind.version = held_at_base
    result = delta_round(service, behind, m.WIRE_BF16, iteration=3)
    h1, m1, _ = delta_counters()
    assert not result.served_delta and m1 - m0 == 1 and h1 - h0 == 0
    # the full serve re-based it; one more apply => within depth => delta
    core.receive_gradients(
        0, 4, {"w": rng.standard_normal(256).astype(np.float32) * 1e-3})
    result = delta_round(service, behind, m.WIRE_BF16, iteration=4)
    h2, m2, _ = delta_counters()
    assert result.served_delta and h2 - h1 == 1 and m2 - m1 == 0
    np.testing.assert_array_equal(
        result.store["w"], decode_full_pull(service, m.WIRE_BF16)["w"])


def test_restore_resets_chain_and_falls_back_full(tmp_path):
    """A checkpoint restore is a new world: the chain resets, the next
    serve is full (never a stale pair patching toward the old store),
    and the receiver re-bases correctly."""
    rng = np.random.default_rng(5)
    core = make_core()
    manager = CheckpointManager(core, directory=str(tmp_path),
                                checkpoint_interval=10**9,
                                check_period_s=3600.0)
    service = ParameterServerService(core, manager)
    core.initialize_parameters({"w": rng.standard_normal(128)
                                .astype(np.float32)})
    manager.save(epoch=1)
    state = DeltaPullState()
    delta_round(service, state, m.WIRE_BF16)  # arms the lazy chain
    # warm-up apply seeds the retained image; the round re-bases
    core.receive_gradients(0, 1, {"w": rng.standard_normal(128)
                                  .astype(np.float32) * 1e-3})
    delta_round(service, state, m.WIRE_BF16, iteration=1)
    core.receive_gradients(0, 2, {"w": rng.standard_normal(128)
                                  .astype(np.float32) * 1e-3})
    result = delta_round(service, state, m.WIRE_BF16, iteration=2)
    assert result.served_delta
    # restore the older checkpoint: chain must reset
    manager.load(manager.latest())
    assert service.delta_chain.pairs_between(state.version,
                                             core.serve_version()) is None
    result = delta_round(service, state, m.WIRE_BF16, iteration=3)
    assert not result.served_delta  # full re-base, not a stale delta
    np.testing.assert_array_equal(
        result.store["w"], decode_full_pull(service, m.WIRE_BF16)["w"])


def test_dtype_mismatch_serves_full(monkeypatch):
    """A chain built for bf16 must not patch an f32 receiver: the wire
    bytes differ even for identical values."""
    monkeypatch.setenv("PSDT_DELTA_DTYPE", "bf16")
    rng = np.random.default_rng(11)
    core = make_core()
    service = make_service(core)
    core.initialize_parameters({"w": rng.standard_normal(64)
                                .astype(np.float32)})
    state = DeltaPullState()
    delta_round(service, state, m.WIRE_F32)
    core.receive_gradients(0, 1, {"w": rng.standard_normal(64)
                                  .astype(np.float32) * 1e-3})
    result = delta_round(service, state, m.WIRE_F32, iteration=1)
    assert not result.served_delta
    np.testing.assert_array_equal(
        result.store["w"], decode_full_pull(service, m.WIRE_F32)["w"])


def test_depth_zero_disables_subsystem(monkeypatch):
    monkeypatch.setenv("PSDT_DELTA_DEPTH", "0")
    core = make_core()
    service = make_service(core)
    assert service.delta_chain is None
    core.initialize_parameters({"w": np.ones(8, np.float32)})
    state = DeltaPullState()
    result = delta_round(service, state, m.WIRE_BF16)
    assert not result.served_delta and result.store is not None
    # and the client side refuses to even try
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient
    client = PSClient.__new__(PSClient)
    client.chunk_bytes = 1 << 20
    client._delta_ok = None
    assert not client._delta()


# -------------------------------------------- serve_version monotonicity


def test_restore_never_reuses_a_served_version(tmp_path):
    """Satellite regression: restoring an OLDER checkpoint must not
    rewind the version counter — a delta receiver holding version v
    would silently patch a wrong base if v were re-served with
    different values."""
    rng = np.random.default_rng(1)
    core = make_core()
    manager = CheckpointManager(core, directory=str(tmp_path),
                                checkpoint_interval=10**9,
                                check_period_s=3600.0)
    core.initialize_parameters({"w": rng.standard_normal(32)
                                .astype(np.float32)})
    manager.save(epoch=1)
    for it in range(1, 6):
        core.receive_gradients(0, it, {"w": rng.standard_normal(32)
                                       .astype(np.float32)})
    served_max = core.serve_version()
    manager.load(manager.latest())  # back to the epoch-1 params
    assert core.serve_version() > served_max


def test_version_monotonic_across_processes_via_meta_sidecar(tmp_path):
    """The checkpoint meta sidecar carries the save-time counter, so a
    FRESH process restoring the file resumes numbering past everything
    the saving process served; a reference checkpoint (no sidecar)
    still restores."""
    rng = np.random.default_rng(2)
    core = make_core()
    manager = CheckpointManager(core, directory=str(tmp_path),
                                checkpoint_interval=10**9,
                                check_period_s=3600.0)
    core.initialize_parameters({"w": rng.standard_normal(16)
                                .astype(np.float32)})
    for it in range(1, 4):
        core.receive_gradients(0, it, {"w": rng.standard_normal(16)
                                       .astype(np.float32)})
    saved_at = core.serve_version()
    manager.save(epoch=1)
    # "new process": a fresh core restoring the same directory
    core2 = make_core()
    manager2 = CheckpointManager(core2, directory=str(tmp_path),
                                 checkpoint_interval=10**9,
                                 check_period_s=3600.0)
    manager2.load(manager2.latest())
    assert core2.serve_version() > saved_at
    # corrupt OPTIONAL sidecar (wrong-typed value): best-effort by
    # contract — the valid .ckpt must still restore
    for path in os.listdir(tmp_path):
        if path.endswith(".meta.json"):
            with open(os.path.join(tmp_path, path), "w",
                      encoding="utf-8") as f:
                f.write('{"params_version": null}')
    core25 = make_core()
    manager25 = CheckpointManager(core25, directory=str(tmp_path),
                                  checkpoint_interval=10**9,
                                  check_period_s=3600.0)
    manager25.load(manager25.latest())
    assert core25.get_parameters()
    # reference-written checkpoint: sidecar absent => still restores
    for path in os.listdir(tmp_path):
        if path.endswith(".meta.json"):
            os.remove(os.path.join(tmp_path, path))
    core3 = make_core()
    manager3 = CheckpointManager(core3, directory=str(tmp_path),
                                 checkpoint_interval=10**9,
                                 check_period_s=3600.0)
    manager3.load(manager3.latest())
    assert core3.get_parameters()


# ------------------------------------------------------ client downgrade


def test_client_downgrades_against_unary_only_server(tmp_path):
    """A reference PS (no delta methods bound) answers UNIMPLEMENTED:
    delta_pull returns None ONCE, latches, and the plain path serves —
    zero failed steps."""
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient
    from parameter_server_distributed_tpu.rpc.service import (bind_service,
                                                              make_server)

    core = make_core()
    core.initialize_parameters({"w": np.array([1.0, 2.0], np.float32)})
    service = make_service(core, tmp=str(tmp_path))
    server = make_server()
    bind_service(server, m.PARAMETER_SERVER_SERVICE,
                 m.PARAMETER_SERVER_METHODS, service)  # unary only
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        with PSClient(f"127.0.0.1:{port}") as client:
            assert client.delta_pull(m.PullRequest(
                worker_id=0, iteration=0,
                wire_dtype=m.WIRE_BF16), timeout=10) is None
            assert client._delta_ok is False
            assert client.delta_push_pull(0, 1, list, timeout=10) is None
            pulled = client.pull_parameters(
                m.PullRequest(worker_id=0, iteration=0))
            np.testing.assert_allclose(pulled.parameters[0].to_array(),
                                       [1.0, 2.0])
    finally:
        server.stop(0)


def test_checksum_mismatch_downgrades_and_recovers(tmp_path):
    """A poisoned base (receiver-side drift) fails the post-apply
    checksum: the connection downgrades PERMANENTLY and the next pull
    serves full — the training step never fails."""
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient

    rng = np.random.default_rng(9)
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.001, autosave_period_s=600.0))
    port = server.start()
    server.core.initialize_parameters(
        {"w": rng.standard_normal(512).astype(np.float32)})
    try:
        with PSClient(f"127.0.0.1:{port}") as client:
            r = client.delta_pull(m.PullRequest(
                worker_id=0, iteration=0, wire_dtype=m.WIRE_BF16),
                timeout=10)
            assert r is not None and r.store is not None
            # warm-up: the first post-arm apply seeds the retained image
            # and this pull re-bases, so the NEXT pull is delta-served
            server.core.receive_gradients(
                0, 1, {"w": rng.standard_normal(512)
                       .astype(np.float32) * 1e-3})
            r = client.delta_pull(m.PullRequest(
                worker_id=0, iteration=1, wire_dtype=m.WIRE_BF16),
                timeout=10)
            assert r is not None
            # poison the cached base behind the client's back
            client._delta_state.base["w"][0] += 1.0
            server.core.receive_gradients(
                0, 2, {"w": rng.standard_normal(512)
                       .astype(np.float32) * 1e-3})
            assert client.delta_pull(m.PullRequest(
                worker_id=0, iteration=2, wire_dtype=m.WIRE_BF16),
                timeout=10) is None
            assert client._delta_ok is False
            # the plain protocol still serves, bit-correct
            pulled = client.pull_parameters(m.PullRequest(
                worker_id=0, iteration=2, wire_dtype=m.WIRE_BF16))
            assert pulled.parameters
    finally:
        server.stop()


def test_fused_delta_round_e2e_and_cache_one_repack(tmp_path, monkeypatch):
    """Loopback fused rounds: PushPullDeltaStream folds + barriers like
    PushPullStream, serves O(changed bytes), and the encoded delta-frame
    cache repacks each version pair ONCE for the whole fan-out."""
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient

    monkeypatch.setenv("PSDT_SHM", "0")  # shm would bypass the delta RPC
    rng = np.random.default_rng(17)
    n = 3
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=n,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.001, autosave_period_s=600.0))
    port = server.start()
    w0 = rng.standard_normal(4096).astype(np.float32)
    server.core.initialize_parameters({"w": w0})
    clients = [PSClient(f"127.0.0.1:{port}") for _ in range(n)]
    try:
        def round_once(it):
            grads = rng.standard_normal(4096).astype(np.float32) * 1e-3
            results = [None] * n

            def run(wid):
                results[wid] = clients[wid].delta_push_pull(
                    wid, it, lambda: [m.Tensor.from_array("w", grads)],
                    pull_wire_dtype=m.WIRE_BF16, timeout=30)

            threads = [threading.Thread(target=run, args=(wid,))
                       for wid in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert all(not t.is_alive() for t in threads)
            return results

        round_once(1)  # arms the lazy chain, establishes every base
        round_once(2)  # first post-arm apply seeds the image; re-bases
        repacks_before = len(server.service._delta_cache._frames)
        results = round_once(3)
        for r in results:
            assert r is not None and r.push.success
            assert r.served_delta, "steady-state round was not delta-served"
        # the fan-out crossed ONE new version pair: one repack, n replays
        assert len(server.service._delta_cache._frames) \
            == repacks_before + 1
        # bit-identity against the live store's bf16 decode
        oracle = decode_full_pull(server.service, m.WIRE_BF16)
        for r in results:
            np.testing.assert_array_equal(r.store["w"], oracle["w"])
    finally:
        for c in clients:
            c.close()
        server.stop()


def test_delta_training_run_matches_full_serve_bit_for_bit(tmp_path,
                                                           monkeypatch):
    """Acceptance flavor: N iterations of fused training with delta
    serving land on EXACTLY the params of the same run with deltas
    disabled (both at bf16 pull) — the wire protocol is invisible to
    the training trajectory — and the delta run actually hit the chain."""
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient

    monkeypatch.setenv("PSDT_SHM", "0")

    def run(depth):
        monkeypatch.setenv("PSDT_DELTA_DEPTH", str(depth))
        rng = np.random.default_rng(23)
        server = ParameterServer(ParameterServerConfig(
            bind_address="127.0.0.1", port=0, total_workers=1,
            checkpoint_interval=100,
            checkpoint_dir=str(tmp_path / f"d{depth}"),
            learning_rate=0.05, autosave_period_s=600.0))
        port = server.start()
        server.core.initialize_parameters(
            {"w": np.linspace(-1, 1, 2048).astype(np.float32)})
        deltas = 0
        with PSClient(f"127.0.0.1:{port}") as client:
            for it in range(1, 7):
                grads = rng.standard_normal(2048).astype(np.float32)
                r = client.delta_push_pull(
                    0, it, lambda: [m.Tensor.from_array("w", grads)],
                    pull_wire_dtype=m.WIRE_BF16, timeout=30)
                if r is None:
                    push, store = client.push_pull(
                        0, it, [m.Tensor.from_array("w", grads)],
                        pull_wire_dtype=m.WIRE_BF16)
                    assert push.success
                else:
                    assert r.push.success
                    deltas += int(r.served_delta)
        final = np.asarray(server.core.get_parameters()["w"])
        server.stop()
        return final, deltas

    with_delta, hits = run(depth=4)
    without, zero_hits = run(depth=0)
    assert hits >= 4 and zero_hits == 0
    np.testing.assert_array_equal(with_delta, without)


# --------------------------------------------------------- subscription


class _StubContext:
    def __init__(self):
        self._active = True

    def is_active(self):
        return self._active

    def cancel(self):
        self._active = False


def test_subscribe_weights_streams_full_then_deltas(monkeypatch):
    monkeypatch.setenv("PSDT_SUBSCRIBE_POLL_S", "0.05")
    rng = np.random.default_rng(13)
    core = make_core()
    service = make_service(core)
    core.initialize_parameters({"w": rng.standard_normal(256)
                                .astype(np.float32)})
    ctx = _StubContext()
    stream = service.SubscribeWeights(
        dmsg.SubscribeRequest(subscriber_id=1, held_version=0,
                              wire_dtype=m.WIRE_BF16), ctx)
    state = DeltaPullState()
    versions = []

    def consume_one_version():
        batch = []
        for frame in stream:
            batch.append(dmsg.DeltaFrame.decode(frame.encode()))
            if batch[-1].last:
                break
        apply_frames(iter(batch), state)
        versions.append(state.version)

    consume_one_version()  # the establishing full serve
    assert versions[-1] == core.serve_version()
    for it in range(1, 4):
        core.receive_gradients(0, it, {"w": rng.standard_normal(256)
                                       .astype(np.float32) * 1e-3})
        consume_one_version()
        assert versions[-1] == core.serve_version()
        oracle = decode_full_pull(service, m.WIRE_BF16)
        np.testing.assert_array_equal(state.base["w"], oracle["w"])
    ctx.cancel()
    assert len(versions) == 4


def test_follower_backoff_decorrelated_jitter_bounds():
    """ISSUE 14 satellite: the reconnect backoff is decorrelated jitter
    with PINNED bounds — every sleep in [base, cap=8*base] and never
    above 3x the previous sleep — so a fleet of followers losing one
    restarted PS re-spreads instead of thundering-herding it."""
    from parameter_server_distributed_tpu.delta.subscriber import (
        WeightFollower)
    base = 0.5
    follower = WeightFollower("127.0.0.1:1", subscriber_id=3,
                              reconnect_backoff_s=base)  # never started
    cap = base * 8.0
    prev = base
    sleeps = [follower._next_backoff() for _ in range(64)]
    for sleep in sleeps:
        assert base <= sleep <= cap + 1e-9
        assert sleep <= max(base, prev * 3.0) + 1e-9
        prev = sleep
    # the walk actually moves (a constant schedule is the herd)
    assert len({round(s, 6) for s in sleeps}) > 10
    # different subscriber ids draw DIFFERENT schedules...
    other = WeightFollower("127.0.0.1:1", subscriber_id=4,
                           reconnect_backoff_s=base)
    assert [other._next_backoff() for _ in range(8)] != sleeps[:8]
    # ...while the same id reproduces (debuggability)
    replay = WeightFollower("127.0.0.1:1", subscriber_id=3,
                            reconnect_backoff_s=base)
    assert [replay._next_backoff() for _ in range(8)] == sleeps[:8]
    # a successful publish resets the walk to the base
    follower._prev_backoff = follower._backoff
    assert follower._next_backoff() <= 3.0 * base


def test_follower_wait_for_update_blocks_and_wakes():
    """wait_for_update parks on the mailbox CV (no busy-poll): a publish
    wakes the waiter with the pending version, and a degrade wakes it
    immediately with None instead of sleeping out the timeout."""
    from parameter_server_distributed_tpu.delta.subscriber import (
        WeightFollower)

    follower = WeightFollower("127.0.0.1:1")  # thread never started
    assert follower.wait_for_update(0.05) is None  # timeout path

    follower._state.base = {"w": np.arange(4, dtype=np.float32)}
    follower._state.version = 7
    t = threading.Timer(0.1, follower._publish)
    t.start()
    t0 = time.monotonic()
    got = follower.wait_for_update(10.0)
    assert got is not None
    store, version = got
    assert version == 7
    np.testing.assert_array_equal(store["w"], follower._state.base["w"])
    assert time.monotonic() - t0 < 5.0  # woke on publish, not timeout

    t = threading.Timer(0.1, follower._degrade, args=("test sever",))
    t.start()
    t0 = time.monotonic()
    assert follower.wait_for_update(10.0) is None
    assert time.monotonic() - t0 < 5.0  # degrade wakes the waiter
    assert follower.degraded


def test_weight_follower_tracks_live_run_and_severing_degrades(tmp_path):
    """Acceptance: a WeightFollower against a live PS receives >= 5
    versions; killing the PS mid-subscription degrades CLEANLY — the
    last-good weights stay available, no crash, bounded reconnects."""
    from parameter_server_distributed_tpu.delta.subscriber import (
        WeightFollower)

    rng = np.random.default_rng(29)
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.001, autosave_period_s=600.0))
    port = server.start()
    server.core.initialize_parameters(
        {"w": rng.standard_normal(1024).astype(np.float32)})
    follower = WeightFollower(f"127.0.0.1:{port}", subscriber_id=3,
                              reconnect_attempts=1,
                              reconnect_backoff_s=0.05).start()
    try:
        last = None
        deadline = time.monotonic() + 30
        versions_seen = 0
        it = 0
        while versions_seen < 6 and time.monotonic() < deadline:
            it += 1
            server.core.receive_gradients(
                0, it, {"w": rng.standard_normal(1024)
                        .astype(np.float32) * 1e-3})
            for _ in range(100):
                fresh = follower.poll()
                if fresh is not None:
                    last = fresh
                    versions_seen += 1
                    break
                time.sleep(0.01)
        assert versions_seen >= 6  # boot full + 5 live versions
        assert not follower.degraded
        store, version = last
        np.testing.assert_array_equal(
            store["w"],
            decode_full_pull(server.service, m.WIRE_BF16)["w"])
        # sever: the PS dies mid-subscription
        server.stop()
        deadline = time.monotonic() + 20
        while not follower.degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        assert follower.degraded
        # last-good weights still held by the consumer; poll never throws
        assert follower.poll() is None or True
        assert store["w"].size == 1024
    finally:
        follower.stop()


def test_follower_unimplemented_degrades_permanently(tmp_path):
    from parameter_server_distributed_tpu.delta.subscriber import (
        WeightFollower)
    from parameter_server_distributed_tpu.rpc.service import (bind_service,
                                                              make_server)

    core = make_core()
    core.initialize_parameters({"w": np.ones(8, np.float32)})
    service = make_service(core, tmp=str(tmp_path))
    server = make_server()
    bind_service(server, m.PARAMETER_SERVER_SERVICE,
                 m.PARAMETER_SERVER_METHODS, service)  # reference shape
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    follower = WeightFollower(f"127.0.0.1:{port}", subscriber_id=4).start()
    try:
        deadline = time.monotonic() + 15
        while not follower.degraded and time.monotonic() < deadline:
            time.sleep(0.05)
        assert follower.degraded
        assert "UNIMPLEMENTED" in follower.degrade_reason
    finally:
        follower.stop()
        server.stop(0)


# ------------------------------------------------- decode-server hot swap


def test_decode_server_hot_swaps_across_live_training(tmp_path):
    """THE publication acceptance: a DecodeServer following a live
    training PS hot-swaps params across >= 5 weight versions while
    token streams stay uninterrupted — tokens emitted before a swap
    stand, every request retires at full length, nothing crashes."""
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.delta.subscriber import (
        WeightFollower)
    from parameter_server_distributed_tpu.models.serving import DecodeServer
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    model = Transformer(TransformerConfig(
        vocab=96, d_model=48, n_heads=4, n_layers=2, d_ff=96,
        max_seq=128, dtype=jnp.float32))
    params = {k: np.asarray(v, np.float32)
              for k, v in model.init_params(0).items()}

    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=0.001, autosave_period_s=600.0))
    port = server.start()
    server.core.initialize_parameters(params)
    follower = WeightFollower(f"127.0.0.1:{port}", subscriber_id=9).start()
    srv = DecodeServer(model, model.init_params(0), slots=2, max_len=64)
    rng = np.random.default_rng(31)
    try:
        rid = srv.submit(list(rng.integers(0, 96, 5)), max_new_tokens=24)
        swaps, it = 0, 0
        emitted_before_swap: list[int] = []
        while srv.active and swaps < 5:
            it += 1
            server.core.receive_gradients(
                0, it, {k: rng.standard_normal(v.shape)
                        .astype(np.float32) * 1e-3
                        for k, v in params.items()})
            deadline = time.monotonic() + 10
            fresh = None
            while fresh is None and time.monotonic() < deadline:
                fresh = follower.poll()
                time.sleep(0.005)
            assert fresh is not None, "follower stalled"
            srv.step()  # a decode round between publications
            prefix = list(srv.peek(rid))
            srv.swap_params(fresh[0])  # between rounds: the swap point
            swaps += 1
            srv.step()
            after = list(srv.peek(rid))
            # tokens emitted before the swap are NEVER rewritten
            assert after[:len(prefix)] == prefix
            emitted_before_swap = after
        assert swaps >= 5
        while srv.active:
            srv.step()
        out = srv.result(rid)
        assert len(out) == 24  # retired at full length: stream unbroken
        assert out[:len(emitted_before_swap)] == emitted_before_swap
        assert srv.stats["weight_swaps"] >= 5
    finally:
        follower.stop()
        server.stop()


def test_swap_params_drops_prompt_cache_and_counts(rng=None):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models.serving import DecodeServer
    from parameter_server_distributed_tpu.models.transformer import (
        Transformer, TransformerConfig)

    model = Transformer(TransformerConfig(
        vocab=96, d_model=48, n_heads=4, n_layers=2, d_ff=96,
        max_seq=128, dtype=jnp.float32))
    params = model.init_params(0)
    srv = DecodeServer(model, params, slots=2, max_len=64, prompt_cache=2)
    rid = srv.submit([1, 2, 3, 4], max_new_tokens=4)
    srv.run_to_completion()
    assert srv._prefix_tree.nodes  # warmed
    srv.swap_params(model.init_params(1))
    assert not srv._prefix_tree.nodes  # stale prefill state dropped
    assert srv._prefix_tree.bytes == 0
    assert srv.prefix_fingerprint() == b""
    rid2 = srv.submit([1, 2, 3, 4], max_new_tokens=4)
    out = srv.run_to_completion()
    assert len(out[rid2]) == 4
    assert srv.stats["weight_swaps"] == 1
    # name/shape drift (upstream model change mid-publication) raises AT
    # THE SWAP POINT — where serve_main catches it and keeps last-good
    # weights — instead of crashing a later decode round
    good = srv.params
    with pytest.raises(ValueError):
        srv.swap_params({"nope": np.zeros(3, np.float32)})
    assert srv.params is good and srv.stats["weight_swaps"] == 1


# --------------------------------------------------- concurrency hammer


@pytest.mark.lockcheck
def test_lockcheck_concurrent_subscribe_apply_close_hammer(monkeypatch):
    """Applies (chain builds), delta pulls, subscribers opening/closing,
    and chain resets hammer the same service under PSDT_LOCK_CHECK=1:
    any rank inversion between DeltaChain._lock, the cache locks, and
    the core locks is a checked failure, and every served round must be
    bit-correct for SOME version (never a torn mix)."""
    monkeypatch.setenv("PSDT_SUBSCRIBE_POLL_S", "0.02")
    rng = np.random.default_rng(41)
    core = make_core(lr=0.01)
    service = make_service(core)
    core.initialize_parameters({"w": rng.standard_normal(256)
                                .astype(np.float32),
                                "b": rng.standard_normal(17)
                                .astype(np.float32)})
    stop = threading.Event()
    errors: list[BaseException] = []

    def applier():
        it = 0
        g = np.random.default_rng(1)
        while not stop.is_set():
            it += 1
            try:
                core.receive_gradients(
                    0, it, {"w": g.standard_normal(256)
                            .astype(np.float32) * 1e-2,
                            "b": g.standard_normal(17)
                            .astype(np.float32) * 1e-2})
            except BaseException as exc:  # noqa: BLE001 — hammer surface
                errors.append(exc)
                return

    def puller():
        state = DeltaPullState()
        while not stop.is_set():
            try:
                result = delta_round(service, state, m.WIRE_BF16)
                if result.store is not None:
                    crc = store_crc(result.store)
                    assert crc == store_crc(result.store)
            except DeltaBaseMismatch:
                state = DeltaPullState()  # re-base, like the client does
            except BaseException as exc:  # noqa: BLE001 — hammer surface
                errors.append(exc)
                return

    class _StopCtx:
        """Context that goes inactive when the hammer stops, so a parked
        SubscribeWeights generator unwinds instead of waiting forever."""

        def __init__(self):
            self._active = True

        def is_active(self):
            return self._active and not stop.is_set()

        def cancel(self):
            self._active = False

    def subscriber():
        while not stop.is_set():
            ctx = _StopCtx()
            state = DeltaPullState()
            stream = service.SubscribeWeights(
                dmsg.SubscribeRequest(subscriber_id=2, held_version=0,
                                      wire_dtype=m.WIRE_BF16), ctx)
            try:
                batch = []
                seen = 0
                for frame in stream:
                    decoded = dmsg.DeltaFrame.decode(frame.encode())
                    batch.append(decoded)
                    if decoded.last:
                        try:
                            apply_frames(iter(batch), state)
                        except DeltaBaseMismatch:
                            state = DeltaPullState()
                        batch = []
                        seen += 1
                        if seen >= 3:
                            break
            except BaseException as exc:  # noqa: BLE001 — hammer surface
                errors.append(exc)
                return
            finally:
                ctx.cancel()

    def resetter():
        while not stop.is_set():
            time.sleep(0.02)
            service.delta_chain.reset()

    threads = ([threading.Thread(target=applier, daemon=True,
                                 name="hammer-apply")]
               + [threading.Thread(target=puller, daemon=True,
                                   name=f"hammer-pull-{i}")
                  for i in range(2)]
               + [threading.Thread(target=subscriber, daemon=True,
                                   name="hammer-subscribe")]
               + [threading.Thread(target=resetter, daemon=True,
                                   name="hammer-reset")])
    for t in threads:
        t.start()
    time.sleep(2.0)
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), f"{t.name} wedged"
    assert not errors, errors


# --------------------------------------------------------------- obs


def test_delta_counters_surface_in_rollup():
    from parameter_server_distributed_tpu.obs.export import (render_rollup,
                                                             worker_rollup)

    obs_stats.counter("ps.serve.delta_hit").add(7)
    obs_stats.counter("ps.serve.delta_miss").add(2)
    obs_stats.counter("ps.serve.delta_bytes").add(12345)
    snap = obs_stats.REGISTRY.snapshot()
    rolled = worker_rollup(snap)
    assert rolled["ps"]["delta"]["hits"] >= 7
    text = render_rollup({"cluster": {}, "per_worker": {0: rolled}})
    assert "delta serve" in text


def test_delta_events_render_in_postmortem(tmp_path):
    from parameter_server_distributed_tpu.obs import flight, postmortem

    ring_dir = str(tmp_path / "flight")
    flight.enable(ring_dir, role="ps:delta", records=256)
    try:
        flight.record("serve.delta.build", a=4096, b=7)
        flight.record("serve.delta.hit", iteration=3, a=512, b=1)
        flight.record("serve.delta.miss", iteration=3, a=2, b=7,
                      note="depth/reset")
        flight.record("publish.subscribe", a=0, b=9)
        flight.record("publish.swap", a=7, b=1500)
        flight.record("publish.lag", a=3, b=9)
        flight.record("serve.delta.downgrade", note="checksum")
        flight.record("push.commit", iteration=3, worker=0, a=1, b=1)
        flight.record("barrier.publish", iteration=3, a=1, b=1)
    finally:
        flight.disable()
    rep = postmortem.report(ring_dir, iteration=3)
    tl = rep["timeline"]
    assert tl["delta_serve"]["hits"] == 1
    assert tl["delta_serve"]["misses"] == 1
    assert tl["delta_serve"]["delta_bytes"] == 512
    assert "depth/reset" in tl["delta_serve"]["miss_reasons"]
    pub = rep["narrative"]["publication"]
    assert pub["subscriptions"] == 1 and pub["swaps"] == 1
    assert pub["last_version"] == 7 and pub["max_lag"] == 3
    assert any(d["what"] == "serve.delta.downgrade"
               for d in rep["narrative"]["degrades"])
    text = postmortem.render_report(rep)
    assert "delta serve" in text and "weight publication" in text
