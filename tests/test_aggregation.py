"""Streaming incremental aggregation + encode-once broadcast serve.

Covers ISSUE 3: equivalence of the streaming fold-on-arrival data path
with the classic buffered mean (bit-for-bit in f32 on the numpy path),
the documented duplicate-push policies, the O(model)/1x-model close
properties, the apply-outside-the-lock aggregating phase, the
encoded-chunk broadcast cache (single-flight, invalidation on
apply/restore/initialize, mixed wire dtypes), and the barrier_width TTL
cache lock."""

import gc
import os
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.core.optimizer import SGD, Adam, Momentum
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.core.tensor import store_nbytes, to_wire
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import messages as m


def store(**kw):
    return {k: np.asarray(v, np.float32) for k, v in kw.items()}


@pytest.fixture
def numpy_only():
    """Pin the numpy aggregation path: the native kernels sum in a
    different association order, and the bit-for-bit equivalence contract
    is defined on the numpy semantics."""
    native.set_enabled(False)
    yield
    native.set_enabled(True)


def _random_grads(rng, shapes):
    return {name: rng.standard_normal(shape).astype(np.float32)
            for name, shape in shapes.items()}


# ------------------------------------------------------------- equivalence

@pytest.mark.parametrize("n_workers", [1, 2, 3, 5])
@pytest.mark.parametrize("make_opt", [lambda: SGD(1.0),
                                      lambda: Momentum(0.1, momentum=0.9),
                                      lambda: Adam(0.01)])
def test_streaming_matches_buffered_bit_for_bit(numpy_only, n_workers,
                                                make_opt):
    """The streaming accumulator must land EXACTLY the buffered
    contributor mean — same f32 sum order, same scale, same optimizer
    apply — across worker counts, optimizers, and several iterations."""
    rng = np.random.default_rng(42)
    shapes = {"w": (33, 7), "b": (11,), "scalar": ()}
    init = _random_grads(rng, shapes)
    cores = {mode: ParameterServerCore(total_workers=n_workers,
                                       optimizer=make_opt(),
                                       aggregation=mode)
             for mode in ("streaming", "buffered")}
    for core in cores.values():
        core.initialize_parameters(init)
    for it in range(1, 4):
        pushes = [_random_grads(rng, shapes) for _ in range(n_workers)]
        for mode, core in cores.items():
            for wid, grads in enumerate(pushes):
                r = core.receive_gradients(wid, it, grads)
            assert r.aggregation_complete
        a = cores["streaming"].get_parameters()
        b = cores["buffered"].get_parameters()
        for name in shapes:
            np.testing.assert_array_equal(a[name], b[name])


def test_streaming_matches_buffered_empty_store_bootstrap(numpy_only):
    """Bootstrap (first aggregated mean BECOMES the params) is identical
    in both modes."""
    for mode in ("streaming", "buffered"):
        ps = ParameterServerCore(total_workers=2, aggregation=mode)
        ps.receive_gradients(0, 0, store(w=[2.0, 4.0]))
        r = ps.receive_gradients(1, 0, store(w=[4.0, 8.0]))
        assert r.aggregation_complete
        np.testing.assert_array_equal(ps.get_parameters()["w"],
                                      np.asarray([3.0, 6.0], np.float32))


def test_streaming_matches_buffered_elastic_shrink(numpy_only):
    """A mid-iteration barrier shrink (worker evicted) releases a
    buffered iteration via the sync poll identically in both modes."""
    results = {}
    for mode in ("streaming", "buffered"):
        live = {"n": 3}
        ps = ParameterServerCore(total_workers=3, aggregation=mode,
                                 live_workers_fn=lambda: live["n"])
        ps.initialize_parameters(store(w=[0.0]))
        ps.receive_gradients(0, 1, store(w=[2.0]))
        ps.receive_gradients(1, 1, store(w=[4.0]))
        _, ready, _, _ = ps.check_sync_status(1)
        assert not ready
        live["n"] = 2  # worker 2 evicted
        _, ready, recv, total = ps.check_sync_status(1)
        assert ready and recv == 2 and total == 2
        results[mode] = ps.get_parameters()["w"]
    np.testing.assert_array_equal(results["streaming"], results["buffered"])
    np.testing.assert_allclose(results["streaming"], [-3.0])


def test_streaming_late_and_gcd_pushes_are_noops():
    ps = ParameterServerCore(total_workers=1, gc_iterations=4,
                             aggregation="streaming")
    ps.initialize_parameters(store(w=[0.0]))
    for it in range(10):
        ps.receive_gradients(0, it, store(w=[0.0]))
    before = ps.get_parameters()["w"].copy()
    late = ps.receive_gradients(1, 9, store(w=[500.0]))  # state still live
    assert late.success and late.aggregation_complete
    gcd = ps.receive_gradients(1, 1, store(w=[999.0]))   # state GC'd
    assert gcd.success and gcd.aggregation_complete
    np.testing.assert_array_equal(ps.get_parameters()["w"], before)
    _, ready, _, _ = ps.check_sync_status(1)
    assert ready


# -------------------------------------------------- chunked fold / dedup

def test_chunked_fold_equals_whole_push(numpy_only):
    """A push delivered as several chunks through begin_push lands exactly
    the state one whole-store receive_gradients lands."""
    whole = ParameterServerCore(total_workers=2, aggregation="streaming")
    chunked = ParameterServerCore(total_workers=2, aggregation="streaming")
    init = store(a=[1.0, 1.0], b=[2.0], c=[3.0])
    whole.initialize_parameters(init)
    chunked.initialize_parameters(init)
    g0 = store(a=[0.5, 0.5], b=[1.0], c=[2.0])
    g1 = store(a=[1.5, 1.5], b=[3.0], c=[4.0])

    whole.receive_gradients(0, 1, g0)
    r_whole = whole.receive_gradients(1, 1, g1)

    sink0 = chunked.begin_push(0, 1)
    sink0.fold({"a": g0["a"]})
    sink0.fold({"b": g0["b"], "c": g0["c"]})
    r0 = sink0.commit()
    assert r0.success and not r0.aggregation_complete
    sink1 = chunked.begin_push(1, 1)
    sink1.fold({"a": g1["a"], "b": g1["b"]})
    sink1.fold({"c": g1["c"]})
    r1 = sink1.commit()
    assert r1.aggregation_complete == r_whole.aggregation_complete is True
    for name in init:
        np.testing.assert_array_equal(whole.get_parameters()[name],
                                      chunked.get_parameters()[name])


def test_retry_replay_folds_each_tensor_once(numpy_only):
    """An RPC retry replays the SAME payload (worker/worker.py invariant);
    the per-(worker, tensor) dedup must fold each tensor exactly once, so
    a partially-landed push + full replay converges to one contribution."""
    ps = ParameterServerCore(total_workers=2, aggregation="streaming")
    ps.initialize_parameters(store(a=[0.0], b=[0.0]))
    # first attempt dies after chunk 1 (no commit)
    sink = ps.begin_push(0, 1)
    sink.fold({"a": np.asarray([2.0], np.float32)})
    # retry replays the full payload
    retry = ps.begin_push(0, 1)
    retry.fold({"a": np.asarray([2.0], np.float32)})
    retry.fold({"b": np.asarray([4.0], np.float32)})
    r = retry.commit()
    assert r.success and r.workers_received == 1
    ps.receive_gradients(1, 1, store(a=[4.0], b=[6.0]))
    p = ps.get_parameters()
    np.testing.assert_allclose(p["a"], [-3.0])  # mean(2,4), not mean(2,2,4)
    np.testing.assert_allclose(p["b"], [-5.0])


def test_streaming_duplicate_push_policy_and_message():
    ps = ParameterServerCore(total_workers=3, aggregation="streaming")
    ps.initialize_parameters(store(w=[0.0]))
    ps.receive_gradients(0, 1, store(w=[3.0]))
    dup = ps.receive_gradients(0, 1, store(w=[99.0]))
    assert dup.success and not dup.aggregation_complete
    assert dup.workers_received == 1
    assert "first-push-wins" in dup.message


# ------------------------------------------------- memory / close behavior

def test_streaming_peak_gradient_buffer_is_one_model():
    """N buffered pushes must cost ~1x model in streaming mode and N x
    model in buffered mode — the headline memory claim."""
    n = 6
    shapes = {"w": (256, 16), "b": (64,)}
    rng = np.random.default_rng(0)
    init = _random_grads(rng, shapes)
    model_bytes = store_nbytes(init)
    peaks = {}
    for mode in ("streaming", "buffered"):
        ps = ParameterServerCore(total_workers=n, aggregation=mode)
        ps.initialize_parameters(init)
        for wid in range(n):
            ps.receive_gradients(wid, 1, _random_grads(rng, shapes))
        assert ps.grad_buffer_bytes == 0  # released at close
        peaks[mode] = ps.peak_grad_buffer_bytes
    assert peaks["streaming"] == model_bytes
    assert peaks["buffered"] == n * model_bytes


class _SlowSGD(SGD):
    apply_delay_s = 0.25

    # the rule itself: what the serial apply and a range task both call
    def update_range(self, *args):
        time.sleep(self.apply_delay_s)
        return super().update_range(*args)


@pytest.mark.lockcheck
def test_streaming_apply_runs_outside_state_lock():
    """While iteration N's barrier apply is in flight (the "aggregating"
    phase), a push for iteration N+1 and a sync poll must NOT block
    behind it."""
    ps = ParameterServerCore(total_workers=2, optimizer=_SlowSGD(1.0),
                             aggregation="streaming")
    ps.initialize_parameters(store(w=[10.0]))
    ps.receive_gradients(0, 1, store(w=[1.0]))

    def close_barrier():
        ps.receive_gradients(1, 1, store(w=[1.0]))

    closer = threading.Thread(target=close_barrier)
    closer.start()
    time.sleep(0.05)  # let the closer enter the slow apply
    t0 = time.perf_counter()
    r = ps.receive_gradients(0, 2, store(w=[1.0]))
    push_latency = time.perf_counter() - t0
    _, ready, _, _ = ps.check_sync_status(1)
    poll_latency = time.perf_counter() - t0
    closer.join(timeout=5.0)
    assert not closer.is_alive()
    assert r.success and not r.aggregation_complete
    # both returned well inside the 0.25 s apply window
    assert push_latency < 0.15, f"push blocked {push_latency:.3f}s"
    assert poll_latency < 0.2, f"poll blocked {poll_latency:.3f}s"
    # iteration 1 only reads ready once its apply has landed
    _, ready1, _, _ = ps.check_sync_status(1)
    assert ready1
    np.testing.assert_allclose(ps.get_parameters()["w"], [9.0])


@pytest.mark.lockcheck
def test_push_during_aggregating_window_reports_incomplete():
    """A commit that lands while the barrier close is mid-apply must not
    claim completion: the params are not applied yet, and the worker must
    learn readiness from the poll/CV path when it is real."""
    ps = ParameterServerCore(total_workers=1, optimizer=_SlowSGD(1.0),
                             aggregation="streaming")
    ps.initialize_parameters(store(w=[5.0]))

    def close_barrier():
        ps.receive_gradients(0, 1, store(w=[1.0]))

    closer = threading.Thread(target=close_barrier)
    closer.start()
    time.sleep(0.05)
    late = ps.receive_gradients(1, 1, store(w=[100.0]))
    closer.join(timeout=5.0)
    assert late.success and not late.aggregation_complete
    assert "in progress" in late.message
    # the late worker's payload did not contaminate the closed mean
    _, ready, _, _ = ps.check_sync_status(1)
    assert ready
    np.testing.assert_allclose(ps.get_parameters()["w"], [4.0])


class _FlakySGD(SGD):
    """Raises on the first apply, works afterwards."""

    def __init__(self, lr):
        super().__init__(lr)
        self.failures_left = 1

    def update_range(self, *args):
        if self.failures_left:
            self.failures_left -= 1
            raise RuntimeError("injected apply failure")
        return super().update_range(*args)


@pytest.mark.lockcheck
@pytest.mark.parametrize("mode", ["streaming", "buffered"])
def test_failed_barrier_apply_is_retryable(numpy_only, mode):
    """An optimizer apply that raises at barrier close must not wedge the
    iteration: the aggregating flag comes back down, the gradients (or
    the restored accumulator) stay in place, and the next sync poll
    re-fires the close and lands the exact mean."""
    ps = ParameterServerCore(total_workers=2, optimizer=_FlakySGD(1.0),
                             aggregation=mode)
    ps.initialize_parameters(store(w=[10.0]))
    ps.receive_gradients(0, 1, store(w=[1.0]))
    with pytest.raises(RuntimeError, match="injected"):
        ps.receive_gradients(1, 1, store(w=[3.0]))
    # A straggler arriving between failure and retry: streaming SEALED
    # the contributor set at the close attempt (the restored accumulator
    # holds already-scaled means, so mixing in raw gradients would be
    # wrong); buffered keeps whole per-worker buffers, so including the
    # straggler in the retried mean is the original valid semantics.
    straggler = ps.receive_gradients(2, 1, store(w=[5.0]))
    assert straggler.success
    if mode == "streaming":
        # the straggler is deferred to the poll path, which re-fires
        assert not straggler.aggregation_complete
        _, ready, recv, _ = ps.check_sync_status(1)
        assert ready and recv == 2
        np.testing.assert_allclose(ps.get_parameters()["w"], [8.0])  # 10-mean(1,3)
    else:
        # the straggler's own push re-fires the close and joins the mean
        assert straggler.aggregation_complete
        _, ready, recv, _ = ps.check_sync_status(1)
        assert ready and recv == 3
        np.testing.assert_allclose(ps.get_parameters()["w"], [7.0])  # 10-mean(1,3,5)


def test_failed_fold_is_not_marked_folded():
    """A chunk whose accumulate raises (shape mismatch vs the running
    accumulator) must NOT be recorded as folded: the worker's retry with
    a good payload still contributes instead of being dedup-dropped."""
    ps = ParameterServerCore(total_workers=2, aggregation="streaming")
    ps.initialize_parameters(store(w=[0.0, 0.0]))
    ps.receive_gradients(0, 1, store(w=[2.0, 2.0]))
    with pytest.raises(ValueError):
        ps.receive_gradients(1, 1, store(w=[1.0, 1.0, 1.0]))  # bad shape
    r = ps.receive_gradients(1, 1, store(w=[4.0, 4.0]))
    assert r.aggregation_complete and r.workers_received == 2
    np.testing.assert_allclose(ps.get_parameters()["w"], [-3.0, -3.0])


@pytest.mark.lockcheck
def test_gc_never_evicts_mid_close_iteration():
    """GC pressure during the off-lock close window must not evict the
    closing iteration's state: a replayed (response-lost) push would
    recreate it and fire a SECOND aggregation for the same iteration."""
    ps = ParameterServerCore(total_workers=2, gc_iterations=1,
                             optimizer=_SlowSGD(1.0),
                             aggregation="streaming")
    ps.initialize_parameters(store(w=[10.0]))
    ps.receive_gradients(0, 1, store(w=[1.0]))
    closer = threading.Thread(
        target=lambda: ps.receive_gradients(1, 1, store(w=[1.0])))
    closer.start()
    time.sleep(0.05)  # closer is inside the slow apply
    for it in (2, 3, 4):  # GC pressure while iteration 1 is mid-close
        ps.receive_gradients(0, it, store(w=[1.0]))
    # replayed pushes for the closing iteration (lost responses)
    ps.receive_gradients(0, 1, store(w=[1.0]))
    ps.receive_gradients(1, 1, store(w=[1.0]))
    closer.join(timeout=5.0)
    assert not closer.is_alive()
    _, ready, _, _ = ps.check_sync_status(1)
    assert ready
    # exactly ONE apply of iteration 1's mean — 10 - mean(1,1), not 8.0
    np.testing.assert_allclose(ps.get_parameters()["w"], [9.0])


@pytest.mark.lockcheck
def test_restore_during_streaming_close_wins():
    """A checkpoint restore that lands while a barrier apply is in flight
    must end with EXACTLY the restored state: no stale mean applied on
    top, no resurrected watermark, and the next barrier works."""
    ps = ParameterServerCore(total_workers=1, optimizer=_SlowSGD(1.0),
                             aggregation="streaming")
    ps.initialize_parameters(store(w=[10.0]))

    def close_barrier():
        ps.receive_gradients(0, 1, store(w=[1.0]))

    closer = threading.Thread(target=close_barrier)
    closer.start()
    time.sleep(0.05)  # closer is inside the slow apply
    ps.restore(epoch=0, iteration=0, params=store(w=[42.0]))
    closer.join(timeout=5.0)
    assert not closer.is_alive()
    np.testing.assert_allclose(ps.get_parameters()["w"], [42.0])
    # the restored world starts fresh: a new iteration-1 barrier closes
    r = ps.receive_gradients(0, 1, store(w=[2.0]))
    assert r.aggregation_complete
    np.testing.assert_allclose(ps.get_parameters()["w"], [40.0])


# ------------------------------------------ the close's kept store (PR 39)
# The range-cut close writes each new store over the buffers of the store
# retired TWO closes ago, and only where no view of them is left.

KEPT = {"embed": (13, 7), "w1": (3, 5, 11), "w2": (3, 11, 5),
        "bias": (3,), "scale": ()}
_fresh_close_bytes = obs_stats.counter("ps.close.fresh_bytes")


def _kept_core(optimizer=None):
    core = ParameterServerCore(total_workers=1, stripes=3,
                               optimizer=optimizer or Adam(0.01),
                               aggregation="streaming")
    rng = np.random.default_rng(39)
    core.initialize_parameters(_random_grads(rng, KEPT))
    return core, rng


def _close(core, rng, iteration):
    """One barrier close; returns the bytes it had to allocate."""
    before = _fresh_close_bytes.value
    r = core.receive_gradients(0, iteration, _random_grads(rng, KEPT))
    assert r.aggregation_complete, r.message
    return _fresh_close_bytes.value - before


def _addresses(core):
    return {name: value.ctypes.data
            for name, value in core.get_parameters().items()}


def test_the_close_writes_over_the_store_of_two_versions_ago():
    """The first two closes have nothing of their own behind them and
    allocate a store each; from the third on every close lands in the
    buffers of the store two versions back, and allocates nothing."""
    core, rng = _kept_core()
    store_bytes = sum(4 * int(np.prod(shape)) for shape in KEPT.values())
    assert [_close(core, rng, it) for it in (1, 2)] == [store_bytes] * 2
    seen = [_addresses(core)]
    for it in range(3, 8):
        assert _close(core, rng, it) == 0
        seen.append(_addresses(core))
    assert seen[0] != seen[1]               # never over the served store
    assert seen[0] == seen[2] == seen[4] and seen[1] == seen[3] == seen[5]


def _hold_array(core):
    held = core.get_parameters()["w1"]
    return held, ["w1"], lambda: held.copy()


def _hold_slice(core):
    held = core.get_parameters()["w1"][1, 2:4]      # a view of a view
    return held, ["w1"], lambda: held.copy()


def _hold_serve_build(core):
    """A serve-cache body build in flight: the wire tensors hold the
    served arrays until the encode has read them."""
    from parameter_server_distributed_tpu.rpc.data_plane import (
        encode_parameter_records)

    _, served, _, _ = core.serve_view()
    held = to_wire(served, wire_dtype=m.WIRE_F32)
    del served
    return held, list(KEPT), lambda: bytes(encode_parameter_records(
        held, lambda size: memoryview(bytearray(size))))


@pytest.mark.parametrize("hold", [_hold_array, _hold_slice,
                                  _hold_serve_build],
                         ids=["array", "slice", "serve_build"])
def test_a_held_view_keeps_its_bytes_and_the_close_allocates_in_its_place(
        hold):
    """Whoever holds an array of a retired store (or a slice of one, or a
    body build still reading it) keeps that buffer: three further closes
    change no byte of it, the close that wanted it takes a new one in
    its place and counts it, and once the holder lets go nothing is
    allocated again."""
    core, rng = _kept_core()
    for it in (1, 2, 3):
        _close(core, rng, it)
    held, names, read = hold(core)
    snapshot = read()
    allocated = []
    for it in (4, 5, 6):
        allocated.append(_close(core, rng, it))
        got = read()
        if isinstance(snapshot, bytes):
            assert got == snapshot
        else:
            np.testing.assert_array_equal(got, snapshot)
    replaced = sum(4 * int(np.prod(KEPT[name])) for name in names)
    # close 4 writes over store 2's buffers; close 5 wants store 3's,
    # which are held; close 6 is back on store 4's
    assert allocated == [0, replaced, 0]
    del held, read
    assert [_close(core, rng, it) for it in (7, 8, 9)] == [0, 0, 0]


class _FlakyRangeSGD(SGD):
    """Raises inside ONE range task of the next close when armed."""

    armed = False

    def update_range(self, name, p, g, out, lo, hi):
        if self.armed and name == "w2":
            self.armed = False
            raise RuntimeError("injected range failure")
        return super().update_range(name, p, g, out, lo, hi)


@pytest.mark.lockcheck
def test_a_failed_range_task_leaves_the_barrier_retryable_and_the_store_whole(
        numpy_only):
    """One task of the range-cut close raises after its siblings wrote
    their ranges into the REUSED buffers: nothing served has changed, the
    version stands, the iteration is retryable, and the retry lands what
    a close that never failed lands, bit for bit."""
    flaky, calm = _FlakyRangeSGD(0.5), SGD(0.5)
    core, rng = _kept_core(flaky)
    twin, twin_rng = _kept_core(calm)
    for it in (1, 2, 3):
        _close(core, rng, it)
        _close(twin, twin_rng, it)
    served = core.get_parameters()
    snapshot = {name: value.copy() for name, value in served.items()}
    version = core.params_version
    grads = _random_grads(rng, KEPT)
    flaky.armed = True
    with pytest.raises(RuntimeError, match="injected range failure"):
        core.receive_gradients(0, 4, grads)
    assert core.params_version == version
    for name, value in core.get_parameters().items():
        assert value is served[name]
        np.testing.assert_array_equal(value, snapshot[name])
    _, ready, received, _ = core.check_sync_status(4)   # re-fires the close
    assert ready and received == 1
    twin.receive_gradients(0, 4, grads)
    for name, value in twin.get_parameters().items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)
    for name, value in served.items():                   # still untouched
        np.testing.assert_array_equal(value, snapshot[name])


class _SlowRangeSGD(SGD):
    def update_range(self, *args):
        time.sleep(0.05)
        return super().update_range(*args)


@pytest.mark.lockcheck
def test_restore_during_range_cut_close_wins():
    """A restore that lands while the range tasks run ends with exactly
    the restored store (the multi-tensor twin of the test above), and the
    closes after it neither write into the restored arrays nor into the
    buffers a reader of the dropped store still holds."""
    core, rng = _kept_core(_SlowRangeSGD(1.0))
    for it in (1, 2, 3):
        _close(core, rng, it)
    grads = _random_grads(rng, KEPT)
    closer = threading.Thread(
        target=lambda: core.receive_gradients(0, 4, grads))
    closer.start()
    time.sleep(0.02)  # the closer is inside its range tasks
    restored = _random_grads(rng, KEPT)
    original = {name: value.copy() for name, value in restored.items()}
    core.restore(epoch=0, iteration=0, params=restored)
    closer.join(timeout=5.0)
    assert not closer.is_alive()
    held = core.get_parameters()        # a reader of the restored store
    want = {name: value.copy() for name, value in original.items()}
    for name, value in original.items():
        np.testing.assert_array_equal(held[name], value)
    for it in (1, 2, 3):
        step = _random_grads(rng, KEPT)
        assert core.receive_gradients(0, it, step).aggregation_complete
        for name in want:
            want[name] = want[name] - step[name]
            np.testing.assert_array_equal(held[name], original[name])
            np.testing.assert_array_equal(restored[name], original[name])
    for name, value in want.items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)


# ------------------------------------- the fold's kept accumulator (PR 41)
# A streamed push is summed in the buffers of the accumulator closed last
# (core/fold_buffers.py), read where its frame lies, and only where no
# view of those buffers is left.

_fresh_fold_bytes = obs_stats.counter("ps.fold.fresh_bytes")
_decode_copied = obs_stats.counter("rpc.server.decode.copied_bytes")
KEPT_BYTES = sum(4 * int(np.prod(shape)) for shape in KEPT.values())


class _WatchedSGD(SGD):
    """Sees the sums as the close hands them to the rule: records where
    each lies and, when asked, keeps one (an optimizer that adopts a
    gradient, a hook that holds a mean)."""

    def __init__(self, lr=0.5):
        super().__init__(lr)
        self.addresses = []
        self.keep = None        # name -> what to keep of its sum
        self.kept = None

    def prepare(self, grads):
        self.addresses.append({name: g.ctypes.data
                               for name, g in grads.items()})
        if self.keep is not None:
            name, cut = self.keep
            self.kept, self.keep = cut(grads[name]), None
        super().prepare(grads)


def _fold_close(core, rng, iteration, workers=1):
    """One round of ``workers`` pushes; returns the accumulator bytes the
    core had to allocate for it."""
    before = _fresh_fold_bytes.value
    for wid in range(workers):
        r = core.receive_gradients(wid, iteration, _random_grads(rng, KEPT))
    assert r.aggregation_complete, r.message
    return _fresh_fold_bytes.value - before


@pytest.mark.parametrize("workers", [1, 3])
@pytest.mark.parametrize("stripes", [1, 3], ids=["serial", "striped"])
def test_the_accumulator_is_seeded_over_the_one_closed_last(workers, stripes):
    """The first round allocates an accumulator; from the second on every
    sum lies where the last round's lay and nothing is allocated,
    whoever contributes and however the fold is cut."""
    watched = _WatchedSGD()
    core = ParameterServerCore(total_workers=workers, stripes=3,
                               optimizer=watched, aggregation="streaming")
    rng = np.random.default_rng(41)
    core.initialize_parameters(_random_grads(rng, KEPT))
    core._stripes = stripes     # the FOLD's cut; the close stays by range
    assert _fold_close(core, rng, 1, workers) == KEPT_BYTES
    for it in range(2, 6):
        assert _fold_close(core, rng, it, workers) == 0
    assert all(seen == watched.addresses[0] for seen in watched.addresses)
    assert len(watched.addresses) == 5


@pytest.mark.parametrize("cut", [lambda g: g, lambda g: g[1, 2:4]],
                         ids=["array", "slice"])
def test_a_held_sum_keeps_its_bytes_and_the_fold_allocates_in_its_place(cut):
    """Whoever keeps a sum (or a slice of one) keeps its buffer: three
    further rounds change no byte of it, the seed that wanted the buffer
    takes a new one and counts exactly that tensor, and once the holder
    lets go nothing is allocated again."""
    watched = _WatchedSGD()
    core, rng = _kept_core(watched)
    for it in (1, 2):
        _fold_close(core, rng, it)
    watched.keep = ("w1", cut)
    _fold_close(core, rng, 3)
    snapshot = watched.kept.copy()
    allocated = []
    for it in (4, 5, 6):
        allocated.append(_fold_close(core, rng, it))
        np.testing.assert_array_equal(watched.kept, snapshot)
    assert allocated == [4 * int(np.prod(KEPT["w1"])), 0, 0]
    held_at = watched.addresses[2]["w1"]
    assert all(seen["w1"] != held_at for seen in watched.addresses[3:])
    watched.kept = None
    assert [_fold_close(core, rng, it) for it in (7, 8)] == [0, 0]


def test_a_second_iteration_folding_beside_an_open_one_allocates_and_says_so():
    """Two iterations' accumulators alive at once (a worker ahead of the
    barrier) cannot share buffers: the second allocates, counted; each
    goes back at its close and both rounds after find one."""
    core = ParameterServerCore(total_workers=2, stripes=3,
                               optimizer=SGD(0.5), aggregation="streaming")
    rng = np.random.default_rng(41)
    core.initialize_parameters(_random_grads(rng, KEPT))
    assert _fold_close(core, rng, 1, workers=2) == KEPT_BYTES
    before = _fresh_fold_bytes.value
    core.receive_gradients(0, 2, _random_grads(rng, KEPT))  # seeds 2
    assert _fresh_fold_bytes.value == before
    core.receive_gradients(0, 3, _random_grads(rng, KEPT))  # seeds 3
    assert _fresh_fold_bytes.value - before == KEPT_BYTES
    for it in (2, 3):
        assert core.receive_gradients(
            1, it, _random_grads(rng, KEPT)).aggregation_complete
    assert _fresh_fold_bytes.value - before == KEPT_BYTES
    assert _fold_close(core, rng, 4, workers=2) == 0


def test_takers_on_many_threads_never_share_a_buffer():
    """``FoldBuffers`` has no lock: a take is one dict pop and a give
    back one assignment, so folds of several iterations on several
    threads each get a buffer of their own.  More threads than cores, the
    interpreter switching as often as it can: every thread writes its
    mark over the sum it took and finds it whole after yielding."""
    import sys

    from parameter_server_distributed_tpu.core.fold_buffers import FoldBuffers

    kept = FoldBuffers()
    shape, clashes, done = (64, 33), [], []
    deadline = time.monotonic() + 1.5

    def fold(mark):
        laps = 0
        while time.monotonic() < deadline:
            acc = kept.take("w", shape)
            acc[...] = mark
            time.sleep(0)
            if not (acc == mark).all():
                clashes.append(mark)
            kept.give_back({"w": acc})
            del acc
            laps += 1
        done.append(laps)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=fold, args=(np.float32(i + 1),))
                   for i in range(4 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(done) == len(threads) and min(done) > 0
    assert not clashes


class _FlakyWatchedSGD(_WatchedSGD):
    armed = False

    def update_range(self, name, p, g, out, lo, hi):
        if self.armed and name == "w2":
            self.armed = False
            raise RuntimeError("injected range failure")
        return super().update_range(name, p, g, out, lo, hi)


@pytest.mark.lockcheck
def test_a_failed_apply_keeps_the_sums_in_their_buffers_until_the_retry(
        numpy_only):
    """The close puts the (scaled) sums back on a failed apply, buffers
    and all: a round that folds meanwhile may not seed over them, the
    retry closes on the very arrays, lands what an unfailed close lands,
    and only then do the buffers go back."""
    flaky = _FlakyWatchedSGD()
    core = ParameterServerCore(total_workers=2, stripes=3, optimizer=flaky,
                               aggregation="streaming")
    twin = ParameterServerCore(total_workers=2, stripes=3,
                               optimizer=SGD(0.5), aggregation="streaming")
    rng = np.random.default_rng(41)
    init = _random_grads(rng, KEPT)
    for c in (core, twin):
        c.initialize_parameters(init)
    for it in (1, 2):
        pushes = [_random_grads(rng, KEPT) for _ in range(2)]
        for c in (core, twin):
            for wid, grads in enumerate(pushes):
                c.receive_gradients(wid, it, grads)
    pushes = [_random_grads(rng, KEPT) for _ in range(2)]
    core.receive_gradients(0, 3, pushes[0])
    flaky.armed = True
    with pytest.raises(RuntimeError, match="injected range failure"):
        core.receive_gradients(1, 3, pushes[1])
    put_back = core._iteration_states[3].accum
    assert {n: a.ctypes.data for n, a in put_back.items()} \
        == flaky.addresses[-1]
    means = {n: a.copy() for n, a in put_back.items()}
    # iteration 4 folds while 3 waits for its retry: not over 3's sums
    before = _fresh_fold_bytes.value
    core.receive_gradients(0, 4, _random_grads(rng, KEPT))
    assert _fresh_fold_bytes.value - before == KEPT_BYTES
    for name, acc in put_back.items():
        np.testing.assert_array_equal(acc, means[name])
    _, ready, received, _ = core.check_sync_status(3)   # re-fires the close
    assert ready and received == 2
    assert flaky.addresses[-1] == flaky.addresses[-2]   # the very arrays
    for wid, grads in enumerate(pushes):
        twin.receive_gradients(wid, 3, grads)
    for name, value in twin.get_parameters().items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)
    # the buffers are back: the next round seeds over them (once the
    # failed attempt's traceback, a cycle that holds the sums, is gone)
    del put_back, acc
    gc.collect()
    before = _fresh_fold_bytes.value
    core.receive_gradients(0, 5, _random_grads(rng, KEPT))
    assert _fresh_fold_bytes.value == before


@pytest.mark.lockcheck
def test_restore_during_a_close_on_kept_sums_wins():
    """A restore that lands while the close sweeps kept sums ends with
    exactly the restored store, and the rounds after it fold and close
    as ever (the dropped aggregate's buffers may be seeded over)."""
    core, rng = _kept_core(_SlowRangeSGD(1.0))
    for it in (1, 2):
        _fold_close(core, rng, it)
    grads = _random_grads(rng, KEPT)
    closer = threading.Thread(
        target=lambda: core.receive_gradients(0, 3, grads))
    closer.start()
    time.sleep(0.02)  # the closer is inside its range tasks
    restored = _random_grads(rng, KEPT)
    want = {name: value.copy() for name, value in restored.items()}
    core.restore(epoch=0, iteration=0, params=restored)
    closer.join(timeout=5.0)
    assert not closer.is_alive()
    for name, value in want.items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)
    for it in (1, 2, 3):
        step = _random_grads(rng, KEPT)
        assert core.receive_gradients(0, it, step).aggregation_complete
        for name in want:
            want[name] = want[name] - step[name]
    for name, value in want.items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)


def test_the_seed_push_becomes_the_store_and_is_never_written_again():
    """Bootstrap: the first aggregate BECOMES the parameters, so its
    accumulator is the served store.  Two real rounds later a reader of
    that store still reads the seed's bytes, no later sum lies in its
    buffers, and the rounds allocated one accumulator in its place."""
    watched = _WatchedSGD(0.5)
    core = ParameterServerCore(total_workers=1, stripes=3,
                               optimizer=watched, aggregation="streaming")
    rng = np.random.default_rng(41)
    seed = _random_grads(rng, KEPT)
    before = _fresh_fold_bytes.value
    assert core.receive_gradients(0, 0, seed).aggregation_complete
    assert _fresh_fold_bytes.value - before == KEPT_BYTES
    reader = core.get_parameters()
    store_at = {name: value.ctypes.data for name, value in reader.items()}
    for name, value in seed.items():
        np.testing.assert_array_equal(reader[name], value)
    want = {name: value.copy() for name, value in seed.items()}
    allocated = []
    for it in (1, 2):
        step = _random_grads(rng, KEPT)
        before = _fresh_fold_bytes.value
        assert core.receive_gradients(0, it, step).aggregation_complete
        allocated.append(_fresh_fold_bytes.value - before)
        for name in want:
            want[name] = want[name] - np.float32(0.5) * step[name]
        for name, value in seed.items():            # under the reader
            np.testing.assert_array_equal(reader[name], value)
    assert allocated == [KEPT_BYTES, 0]
    for seen in watched.addresses:
        assert not set(seen.values()) & set(store_at.values())
    for name, value in want.items():
        np.testing.assert_array_equal(core.get_parameters()[name], value)


def _refilling(frames):
    """The request iterator of a ring: a frame's buffer is overwritten
    as soon as the handler asks for the next chunk."""
    last = None
    for buf, chunk in frames:
        if last is not None:
            last[:] = b"\xff" * len(last)
        last = buf
        yield chunk
        del chunk
    if last is not None:
        last[:] = b"\xff" * len(last)


def _tier_core():
    from parameter_server_distributed_tpu.tiers import messages as tmsg
    agg = tmsg.aggregate_id_for(0)
    core = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                               contributions_fn=lambda: {agg: (2, (0, 1))})
    return core, agg


@pytest.mark.parametrize("kind", ["streaming", "buffered", "group", "async",
                                  "freerun"])
def test_a_sink_that_keeps_what_it_is_given_still_gets_arrays_of_its_own(
        numpy_only, frame_chunks, kind):
    """Through the real stream handler: every sink that stages its chunks
    until the commit (buffered, a tier group, async, free-run) is handed
    owned, writable arrays, so a frame refilled after its fold returned
    moves nothing; only the streaming single-member sink, which has
    summed the chunk by then, borrows the frame's views
    (``rpc.server.decode.copied_bytes`` says which)."""
    rng = np.random.default_rng(41)
    worker = 0
    if kind == "group":
        core, worker = _tier_core()
    else:
        core = ParameterServerCore(
            total_workers=1, optimizer=SGD(1.0),
            aggregation="buffered" if kind == "buffered" else "streaming",
            staleness_bound=2 if kind == "async" else 0,
            freerun=(kind == "freerun"))
    init = _random_grads(rng, KEPT)
    core.initialize_parameters(init)
    sink = core.begin_push(worker, 1)
    assert sink.folds_at_once is (kind == "streaming")
    service = _make_service(core)
    grads = _random_grads(rng, KEPT)
    before = _decode_copied.value
    response = service.PushGradientsStream(
        _refilling(frame_chunks(worker, 1, grads, chunks=3)), None)
    assert response.success, response.message
    assert _decode_copied.value - before == (
        0 if kind == "streaming" else KEPT_BYTES)
    # a group's one push is its two members' SUM: the mean halves it
    scale = np.float32(0.5 if kind == "group" else 1.0)
    for name, value in init.items():
        np.testing.assert_array_equal(
            core.get_parameters()[name], value - scale * grads[name],
            err_msg=name)


def test_the_quorum_forward_fold_reads_borrowed_views_and_lands_todays_bits(
        numpy_only, monkeypatch, frame_chunks):
    """A straggler sealed out of its iteration folds forward, damped,
    through ``StalenessDamping.damp``, which only reads its input: from
    the frame's read-only views it lands the bits it lands from owned
    arrays, and the frame refilled after the fold moves nothing."""
    monkeypatch.delenv("PSDT_STALENESS_BETA", raising=False)
    rng = np.random.default_rng(41)
    init = _random_grads(rng, KEPT)
    pushes = {key: _random_grads(rng, KEPT)
              for key in ("w0", "w1", "late", "next")}
    cores = []
    for borrowed in (False, True):
        core = ParameterServerCore(total_workers=3, optimizer=SGD(1.0),
                                   quorum=0.5, quorum_grace_ms=0.0,
                                   stripes=3)
        core.initialize_parameters(init)
        core.receive_gradients(0, 1, pushes["w0"])
        core.receive_gradients(1, 1, pushes["w1"])
        assert core.check_sync_status(1)[1]     # closed without worker 2
        if borrowed:
            response = _make_service(core).PushGradientsStream(
                _refilling(frame_chunks(2, 1, pushes["late"])), None)
            message = response.message
        else:
            message = core.receive_gradients(2, 1, pushes["late"]).message
        assert "folded into iteration 2" in message
        core.receive_gradients(0, 2, pushes["next"])
        assert core.check_sync_status(2)[1]
        cores.append(core)
    for name in KEPT:
        np.testing.assert_array_equal(cores[0].get_parameters()[name],
                                      cores[1].get_parameters()[name])
    assert not np.array_equal(cores[1].get_parameters()["w1"], init["w1"])


def test_a_replayed_chunk_and_a_wrong_shape_leave_the_kept_sums_as_today(
        frame_chunks):
    """From borrowed views, into kept buffers: a replayed chunk folds
    once; a contributor's tensor of another shape raises, its name stays
    unmarked, the sums taken so far are whole and nothing is allocated
    for it; the retry with the right shape contributes."""
    core = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                               stripes=3, aggregation="streaming")
    rng = np.random.default_rng(41)
    init = _random_grads(rng, KEPT)
    core.initialize_parameters(init)
    first, second = (_random_grads(rng, KEPT) for _ in range(2))
    service = _make_service(core)
    frames = list(frame_chunks(0, 1, first, chunks=2))
    replayed = frames + [next(frame_chunks(0, 1, first, chunks=2))]
    assert service.PushGradientsStream(_refilling(replayed), None).success
    sums = {n: a.copy() for n, a in core._iteration_states[1].accum.items()}
    for name, value in first.items():
        np.testing.assert_array_equal(sums[name], value)    # folded once
    before = _fresh_fold_bytes.value
    bad = dict(second, w1=np.ones((2, 2), np.float32))
    with pytest.raises(ValueError):
        service.PushGradientsStream(
            _refilling(frame_chunks(1, 1, bad, chunks=1)), None)
    assert _fresh_fold_bytes.value == before
    state = core._iteration_states[1]
    assert "w1" not in state.folded.get(1, ())
    np.testing.assert_array_equal(state.accum["w1"], sums["w1"])
    response = service.PushGradientsStream(
        _refilling(frame_chunks(1, 1, second, chunks=2)), None)
    assert response.aggregation_complete, response.message
    for name, value in init.items():
        mean = (first[name] + second[name]) * np.float32(0.5)
        np.testing.assert_array_equal(core.get_parameters()[name],
                                      value - mean, err_msg=name)


@pytest.mark.lockcheck
def test_a_retire_mid_fold_drops_the_moved_sum_as_today(frame_chunks):
    """A reshard RETIRE landing while a borrowed chunk's adds run outside
    the state lock (reserved before the fence, so the push is not stale):
    the moved tensor's sum, kept buffer and all, is dropped when the fold
    publishes, the rest of the chunk is in the accumulator, the NEXT push
    of the name answers the stale-shard-map rejection, and the store no
    longer holds it."""
    from parameter_server_distributed_tpu.replication.messages import (
        STALE_SHARD_MAP)

    core = ParameterServerCore(total_workers=2, optimizer=SGD(1.0),
                               stripes=3, aggregation="streaming")
    rng = np.random.default_rng(41)
    core.initialize_parameters(_random_grads(rng, KEPT))
    grads = _random_grads(rng, KEPT)
    inside, go = threading.Event(), threading.Event()
    take = core._fold_buffers.take

    def gated_take(name, shape):
        if name == "w1":
            inside.set()
            assert go.wait(5.0)
        return take(name, shape)

    core._fold_buffers.take = gated_take
    result = []
    service = _make_service(core)
    pusher = threading.Thread(target=lambda: result.append(
        service.PushGradientsStream(
            _refilling(frame_chunks(0, 1, grads, chunks=1)), None)))
    pusher.start()
    assert inside.wait(5.0)         # the fold is between its two locks
    core.retire_tensors(["w1"], map_epoch=7)
    go.set()
    pusher.join(timeout=5.0)
    assert not pusher.is_alive()
    assert result[0].success, result[0].message
    state = core._iteration_states[1]
    assert "w1" not in state.accum and "w1" not in state.counts
    assert set(state.accum) == set(KEPT) - {"w1"}
    assert "w1" not in state.folded[0]
    assert "w1" not in core.get_parameters()
    core._fold_buffers.take = take
    late = service.PushGradientsStream(
        _refilling(frame_chunks(1, 1, grads, chunks=2)), None)
    assert not late.success and STALE_SHARD_MAP in late.message


# --------------------------------------------------- barrier_width TTL lock

@pytest.mark.lockcheck
def test_barrier_width_ttl_refresh_is_single_flight():
    """Concurrent expiry must issue ONE provider call (the old unlocked
    cache issued one per racing thread and could publish torn pairs)."""
    calls = []
    barrier = threading.Barrier(6)

    def provider():
        calls.append(threading.get_ident())
        time.sleep(0.05)  # widen the race window
        return 3

    ps = ParameterServerCore(total_workers=5, live_workers_fn=provider,
                             live_workers_ttl_s=60.0)
    widths = []

    def read():
        barrier.wait()
        widths.append(ps.barrier_width())

    threads = [threading.Thread(target=read) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5.0)
    assert widths == [3] * 6
    assert len(calls) == 1, f"{len(calls)} provider calls for one expiry"


# --------------------------------------------------- encode-once serve cache

def _make_service(core):
    import tempfile

    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServerService)

    return ParameterServerService(core, CheckpointManager(
        core, directory=tempfile.mkdtemp(prefix="psdt-aggtest-"),
        checkpoint_interval=10**9, check_period_s=3600.0))


def _cache_counters():
    snap = obs_stats.REGISTRY.snapshot()["counters"]
    return (snap.get("ps.serve.cache_hit", 0),
            snap.get("ps.serve.cache_miss", 0))


def _decode_serve(service, iteration=0, wire_dtype=0):
    chunks = list(service._parameter_chunks(iteration, wire_dtype))
    tensors = []
    for chunk in chunks:
        decoded = m.ParameterUpdate.decode(chunk.encode())
        assert decoded.ready
        tensors.extend(decoded.parameters)
    return {t.name: t.to_array() for t in tensors}


def test_serve_cache_hits_and_invalidation_on_apply():
    rng = np.random.default_rng(1)
    params = {"w": rng.standard_normal((64, 8)).astype(np.float32)}
    core = ParameterServerCore(total_workers=1, aggregation="streaming")
    core.initialize_parameters(params)
    service = _make_service(core)

    h0, m0 = _cache_counters()
    first = _decode_serve(service)
    np.testing.assert_array_equal(first["w"], params["w"])
    for _ in range(3):
        _decode_serve(service)
    h1, m1 = _cache_counters()
    assert m1 - m0 == 1 and h1 - h0 == 3  # one encode, three replays

    # an aggregation apply bumps the store version -> cache invalidated
    core.receive_gradients(0, 1, {"w": np.ones_like(params["w"])})
    after = _decode_serve(service)
    h2, m2 = _cache_counters()
    assert m2 - m1 == 1
    np.testing.assert_allclose(after["w"], params["w"] - 1.0, rtol=1e-6)


def test_serve_cache_invalidation_on_initialize_and_restore():
    core = ParameterServerCore(total_workers=1)
    core.initialize_parameters(store(w=[1.0, 2.0]))
    service = _make_service(core)
    np.testing.assert_allclose(_decode_serve(service)["w"], [1.0, 2.0])

    core.initialize_parameters(store(w=[7.0, 8.0]))
    np.testing.assert_allclose(_decode_serve(service)["w"], [7.0, 8.0])

    core.restore(epoch=3, iteration=5, params=store(w=[-1.0, -2.0]))
    h0, m0 = _cache_counters()
    np.testing.assert_allclose(_decode_serve(service)["w"], [-1.0, -2.0])
    np.testing.assert_allclose(_decode_serve(service)["w"], [-1.0, -2.0])
    h1, m1 = _cache_counters()
    assert m1 - m0 == 1 and h1 - h0 == 1


def test_serve_cache_keys_on_wire_dtype():
    rng = np.random.default_rng(2)
    w = rng.standard_normal(512).astype(np.float32)
    core = ParameterServerCore(total_workers=1)
    core.initialize_parameters({"w": w})
    service = _make_service(core)
    h0, m0 = _cache_counters()
    f32 = _decode_serve(service, wire_dtype=m.WIRE_F32)
    bf16 = _decode_serve(service, wire_dtype=m.WIRE_BF16)
    _decode_serve(service, wire_dtype=m.WIRE_F32)
    _decode_serve(service, wire_dtype=m.WIRE_BF16)
    # lossy pull requests serve bf16 (the serve guard) and share its entry
    topk = _decode_serve(service, wire_dtype=m.WIRE_TOPK)
    h1, m1 = _cache_counters()
    assert m1 - m0 == 2 and h1 - h0 == 3
    np.testing.assert_array_equal(f32["w"], w)
    np.testing.assert_allclose(bf16["w"], w, rtol=8e-3)
    np.testing.assert_array_equal(topk["w"], bf16["w"])


def test_serve_cache_fill_never_resurrects_superseded_version():
    """A builder whose encode landed on a version the cache has already
    moved past must not re-register its (dead) bytes; and a stale probe
    must not evict a newer version's entry (versions are monotone)."""
    from parameter_server_distributed_tpu.server.ps_service import (
        EncodedServeCache)

    cache = EncodedServeCache()
    e1, b1 = cache.lookup((1, 0, 32))
    assert b1
    e3, b3 = cache.lookup((3, 0, 32))  # newer version: v1 entry evicted
    assert b3
    cache.fill((3, 0, 32), e3, [b"v3"], 3)
    # a probe that read version 2 BEFORE the v3 serve registered arrives
    # late: it must not evict the newer entry
    cache.lookup((2, 0, 32))
    assert (3, 0, 32) in cache._entries
    # the v1 builder's encode actually captured v2 — superseded by v3, so
    # fill must NOT re-register its dead bytes
    cache.fill((1, 0, 32), e1, [b"v2"], 2)
    assert (2, 0, 32) not in [k for k in cache._entries
                              if cache._entries[k] is e1]
    assert e1.event.is_set()  # its own waiters still get served
    entry, builder = cache.lookup((3, 0, 32))
    assert not builder and entry.bodies == [b"v3"]


def _body_addresses(service):
    """kind -> the addresses of the buffers its newest entry's bodies
    lie in."""
    from parameter_server_distributed_tpu.rpc.shm_transport import _address

    entries = service._serve_cache._entries
    return {key[1:]: [_address(buf) for buf in entries[key].buffers]
            for key in sorted(entries)}


@pytest.mark.parametrize("wire_dtype", [m.WIRE_F32, m.WIRE_BF16],
                         ids=["f32", "bf16"])
def test_serve_cache_builds_each_version_into_the_same_buffers(
        monkeypatch, wire_dtype):
    """The store's shapes do not change, so every version's bodies have
    the last one's sizes: the second version and every later one is built
    into the first one's buffers, and no encoder output goes to new
    memory after that."""
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", "4096")   # 3 or 2 bodies
    rng = np.random.default_rng(4)
    params = {f"w{i}": rng.standard_normal((40, 20)).astype(np.float32)
              for i in range(3)}
    core = ParameterServerCore(total_workers=1, aggregation="streaming")
    core.initialize_parameters(params)
    service = _make_service(core)
    fresh = obs_stats.counter("rpc.wire.fresh_bytes")
    served = service._encoded_parameter_chunks(0, wire_dtype)
    places = len(served)
    assert places > 1 and all(
        isinstance(body, memoryview) and body.readonly for body in served)
    first = _body_addresses(service)
    assert [len(held) for held in first.values()] == [places]
    del served
    after_first = fresh.value
    step = {name: np.ones_like(value) for name, value in params.items()}
    for version in range(1, 4):
        core.receive_gradients(0, version, step)
        got = _decode_serve(service, wire_dtype=wire_dtype)
        for name, value in params.items():
            np.testing.assert_allclose(got[name], value - version,
                                       rtol=8e-3)
        assert _body_addresses(service) == first
    # _decode_serve's own encode() of each chunk is gRPC's: new bytes, as
    # at the parent; the bodies under it added nothing
    bodies = service._encoded_parameter_chunks(0, wire_dtype)
    per_serve = sum(len(body) for body in bodies) + places * 4  # it, ready
    assert fresh.value - after_first == 3 * per_serve


def test_serve_cache_reader_of_a_retired_version_keeps_its_bytes(
        monkeypatch):
    """A puller still streaming version N-1 while version N+1 is built
    holds views of N-1's bodies: those buffers are its own from then on
    (the bytes do not change under it), and the cache puts new ones in
    their places."""
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", "4096")
    rng = np.random.default_rng(6)
    params = {f"w{i}": rng.standard_normal((40, 20)).astype(np.float32)
              for i in range(2)}
    core = ParameterServerCore(total_workers=1, aggregation="streaming")
    core.initialize_parameters(params)
    service = _make_service(core)
    held = service._encoded_parameter_chunks(0, 0)     # version N-1
    snapshot = [bytes(body) for body in held]
    (first,) = _body_addresses(service).values()
    step = {name: np.ones_like(value) for name, value in params.items()}
    core.receive_gradients(0, 1, step)
    _decode_serve(service)                              # version N
    (second,) = _body_addresses(service).values()
    assert len(second) == len(first)
    assert not set(second) & set(first)
    core.receive_gradients(0, 2, step)
    got = _decode_serve(service)                        # version N+1
    np.testing.assert_allclose(got["w0"], params["w0"] - 2.0, rtol=1e-6)
    assert list(_body_addresses(service).values()) == [second]  # N's, free
    assert [bytes(body) for body in held] == snapshot
    decoded = m.ParameterUpdate.decode(snapshot[0])
    np.testing.assert_array_equal(decoded.parameters[0].to_array(),
                                  params["w0"])


def test_serve_cache_keeps_two_kinds_and_lets_go_of_an_idle_one(
        monkeypatch):
    """Two wire dtypes pulled side by side both build into their last
    version's buffers, whichever is asked for first; a dtype nobody pulls
    for two versions gives its buffers back, and so do the places past a
    smaller store's."""
    monkeypatch.setenv("PSDT_STREAM_CHUNK_BYTES", "4096")
    rng = np.random.default_rng(8)
    params = {f"w{i}": rng.standard_normal((40, 20)).astype(np.float32)
              for i in range(2)}
    core = ParameterServerCore(total_workers=1, aggregation="streaming")
    core.initialize_parameters(params)
    service = _make_service(core)
    _decode_serve(service, wire_dtype=m.WIRE_BF16)
    _decode_serve(service)
    first = _body_addresses(service)
    assert {kind[0] for kind in first} == {m.WIRE_F32, m.WIRE_BF16}
    step = {name: np.ones_like(value) for name, value in params.items()}
    core.receive_gradients(0, 1, step)
    _decode_serve(service)
    _decode_serve(service, wire_dtype=m.WIRE_BF16)
    assert _body_addresses(service) == first
    for version in (2, 3):
        core.receive_gradients(0, version, step)
        _decode_serve(service)
    (kind,) = _body_addresses(service)
    assert kind[0] == m.WIRE_F32
    assert _body_addresses(service)[kind] == first[kind]
    core.initialize_parameters({"w0": params["w0"]})    # a smaller store
    _decode_serve(service)
    assert _body_addresses(service)[kind] == first[kind][:1]


def test_serve_cache_empty_store_single_empty_chunk():
    core = ParameterServerCore(total_workers=1)
    service = _make_service(core)
    chunks = list(service._parameter_chunks(0, 0))
    assert len(chunks) == 1
    decoded = m.ParameterUpdate.decode(chunks[0].encode())
    assert decoded.ready and not decoded.parameters


def test_preencoded_parameter_update_is_byte_identical():
    """The cache's replayed message must encode byte-identically to the
    plain ParameterUpdate a reference-shaped peer expects."""
    from parameter_server_distributed_tpu.rpc.data_plane import (
        PreEncodedParameterUpdate, encode_parameter_records)

    rng = np.random.default_rng(3)
    tensors = to_wire({"a": rng.standard_normal((5, 3)).astype(np.float32),
                       "b": rng.standard_normal(7).astype(np.float32)})
    plain = m.ParameterUpdate(iteration=9, parameters=tensors,
                              ready=True).encode()
    def take(size):
        return memoryview(bytearray(size))

    pre = PreEncodedParameterUpdate(
        9, True, [encode_parameter_records(tensors, take)]).encode()
    assert plain == pre
    # default elision: iteration 0 / ready False elide exactly alike
    assert (m.ParameterUpdate(iteration=0, parameters=tensors,
                              ready=False).encode()
            == PreEncodedParameterUpdate(
                0, False,
                [encode_parameter_records(tensors, take)]).encode())


def test_fanout_runs_one_encode_per_version_and_dtype(tmp_path):
    """Acceptance: N in-process workers' post-barrier fan-out performs
    exactly ONE to_wire encode per (params version, wire dtype), verified
    by the cache counters — the other N-1 serves replay cached bytes."""
    from parameter_server_distributed_tpu.config import ParameterServerConfig
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    n = 4
    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=n,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=1.0, autosave_period_s=600.0))
    port = server.start()
    w0 = np.linspace(-1, 1, 2048).astype(np.float32)
    server.core.initialize_parameters({"w": w0})
    results = {}

    def worker(wid):
        with PSClient(f"127.0.0.1:{port}") as client:
            grads = [m.Tensor.from_array("w", np.full_like(w0, 0.5))]
            results[wid] = client.push_pull(wid, 1, grads)

    try:
        h0, m0 = _cache_counters()
        threads = [threading.Thread(target=worker, args=(wid,))
                   for wid in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert all(not t.is_alive() for t in threads)
        h1, m1 = _cache_counters()
        assert m1 - m0 == 1, f"{m1 - m0} encodes for the fan-out"
        assert h1 - h0 == n - 1
        for wid in range(n):
            push, params = results[wid]
            assert push.success and params is not None and params.ready
            np.testing.assert_allclose(params.parameters[0].to_array(),
                                       w0 - 0.5, rtol=1e-6)
    finally:
        server.stop()


# ------------------------------------- reference-shaped client equivalence

@pytest.mark.parametrize("mode", ["streaming", "buffered"])
def test_reference_shaped_unary_client_trains_identically(tmp_path, mode,
                                                          numpy_only):
    """A reference-shaped client (the 5 unary RPCs, repeated-float
    payloads, poll loop) must train to the same parameters in both
    aggregation modes."""
    from parameter_server_distributed_tpu.config import ParameterServerConfig
    from parameter_server_distributed_tpu.rpc.service import RpcClient
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    server = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=2,
        checkpoint_interval=100, checkpoint_dir=str(tmp_path),
        learning_rate=1.0, autosave_period_s=600.0, aggregation=mode))
    port = server.start()
    rng = np.random.default_rng(7)
    w0 = rng.standard_normal(128).astype(np.float32)
    server.core.initialize_parameters({"w": w0})
    expected = w0.copy()
    try:
        with RpcClient(f"127.0.0.1:{port}", m.PARAMETER_SERVER_SERVICE,
                       m.PARAMETER_SERVER_METHODS) as client:
            for it in (1, 2, 3):
                grads = [rng.standard_normal(128).astype(np.float32)
                         for _ in range(2)]
                for wid in (0, 1):
                    push = client.call("ReceiveGradients", m.GradientUpdate(
                        worker_id=wid, iteration=it,
                        gradients=[m.Tensor.from_array("w", grads[wid])]))
                    assert push.success
                assert push.aggregation_complete
                sync = client.call("CheckSyncStatus",
                                   m.SyncStatusRequest(iteration=it))
                assert sync.ready
                expected = expected - (grads[0] + grads[1]) * np.float32(0.5)
                pulled = client.call("ServeParameters",
                                     m.PullRequest(worker_id=0, iteration=it))
                np.testing.assert_array_equal(
                    pulled.parameters[0].to_array(), expected)
    finally:
        server.stop()
