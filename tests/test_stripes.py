"""Stripe-parallel PS hot path (ISSUE 5).

Covers: the stripe partition + shared executor primitives, bit-for-bit
striped==serial equivalence across optimizers / stripe counts / chunked
pushes, the in-flight-fold drain at barrier close, checkpoint round-trip
of striped optimizer state, lock-checked concurrent push/close/restore
races, the in-place optimizer peak-allocation regression, the
error-feedback gate + convergence property, the striped serve-cache
encode's byte identity, and the stripe observability metrics.

The barrier close's sweep is cut by ELEMENT RANGES (PR 39): range-cut ==
serial bit for bit for every host optimizer on both arithmetic paths over
a miniature of the benchmark's store, AdamW's mask under a cut, and the
cut itself.
"""

from __future__ import annotations

import threading
import time
import tracemalloc

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.core import stripes as st
from parameter_server_distributed_tpu.core.optimizer import (
    SGD, Adam, AdamW, Lion, Momentum, make_optimizer)
from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
from parameter_server_distributed_tpu.core.tensor import to_wire
from parameter_server_distributed_tpu.obs import stats as obs_stats
from parameter_server_distributed_tpu.rpc import messages as m
from parameter_server_distributed_tpu.rpc.data_plane import (
    decode_gradients, encode_parameter_record_groups, split_tensors)


@pytest.fixture
def numpy_only():
    """Pin the numpy paths: the bit-for-bit contracts are defined on the
    numpy semantics (the native kernels associate sums differently)."""
    native.set_enabled(False)
    yield
    native.set_enabled(True)


def _grads(rng, shapes):
    return {name: rng.standard_normal(shape).astype(np.float32)
            for name, shape in shapes.items()}


SHAPES = {f"layer{i}/w": (23, 7) for i in range(6)}
SHAPES.update({"bias": (11,), "scale": ()})


# ------------------------------------------------------------- primitives

def test_stripe_of_is_stable_and_total():
    # crc32-based: stable across processes (hash() is salted) and total
    # over any stripe count
    assert st.stripe_of("layer0/w", 1) == 0
    for s in (2, 3, 8):
        for name in SHAPES:
            assert 0 <= st.stripe_of(name, s) < s
            assert st.stripe_of(name, s) == st.stripe_of(name, s)


def test_partition_names_covers_everything_in_order():
    names = list(SHAPES)
    groups = st.partition_names(names, 3)
    flat = [n for g in groups for n in g]
    assert sorted(flat) == sorted(names)
    for group in groups:
        # input order preserved within a stripe
        assert group == [n for n in names if n in set(group)]
        owners = {st.stripe_of(n, 3) for n in group}
        assert len(owners) == 1


def test_stripe_count_env_and_override(monkeypatch):
    monkeypatch.setenv(st.ENV_STRIPES, "5")
    assert st.stripe_count() == 5
    assert st.stripe_count(3) == 3  # explicit override beats env
    monkeypatch.delenv(st.ENV_STRIPES)
    assert st.stripe_count() >= 1
    with pytest.raises(ValueError):
        st.stripe_count(0)


def test_run_striped_orders_results_and_propagates_errors():
    assert st.run_striped([]) == []
    assert st.run_striped([lambda: 7]) == [7]
    results = st.run_striped([(lambda i=i: i * i) for i in range(8)])
    assert results == [i * i for i in range(8)]

    finished = []

    def ok(i):
        time.sleep(0.01)
        finished.append(i)
        return i

    def boom():
        raise RuntimeError("stripe failed")

    with pytest.raises(RuntimeError, match="stripe failed"):
        # the error propagates only after every sibling finished — the
        # quiescence guarantee ps_core's put-back paths rely on
        st.run_striped([boom] + [(lambda i=i: ok(i)) for i in range(4)])
    assert sorted(finished) == [0, 1, 2, 3]


# ------------------------------------------------------------ equivalence

@pytest.mark.parametrize("make_opt", [
    lambda: SGD(1.0), lambda: Momentum(0.1, momentum=0.9),
    lambda: Adam(0.01), lambda: AdamW(0.01), lambda: Lion(0.01)])
@pytest.mark.parametrize("n_stripes", [2, 3, 8])
def test_striped_matches_serial_bit_for_bit(numpy_only, n_stripes,
                                            make_opt):
    """PSDT_STRIPES=1 is the exact pre-stripe serial path; S>1 must land
    bit-identical parameters — stripes never split a tensor's reduction
    and the per-tensor ufunc sequences are unchanged."""
    rng = np.random.default_rng(7)
    init = _grads(rng, SHAPES)
    cores = {s: ParameterServerCore(total_workers=3, optimizer=make_opt(),
                                    stripes=s)
             for s in (1, n_stripes)}
    for core in cores.values():
        core.initialize_parameters(init)
    for it in range(1, 4):
        pushes = [_grads(rng, SHAPES) for _ in range(3)]
        for core in cores.values():
            for wid, grads in enumerate(pushes):
                r = core.receive_gradients(wid, it, grads)
            assert r.aggregation_complete, r.message
    serial = cores[1].get_parameters()
    striped = cores[n_stripes].get_parameters()
    for name in SHAPES:
        np.testing.assert_array_equal(serial[name], striped[name])


# The benchmark's store (a scanned GPT-2 medium) in miniature: 660
# elements in 11 names, two of them a quarter of it each, in the model's
# order.  No equal cut of it falls on a tensor's or a row's boundary.
MINI = {"embed/tok": (13, 7), "embed/pos": (3,),
        "blocks/attn/wq": (3, 3, 4), "blocks/attn/wk": (3, 3, 4),
        "blocks/attn/wv": (3, 3, 4), "blocks/attn/wo": (3, 4, 3),
        "blocks/mlp/w1": (3, 5, 11), "blocks/mlp/w2": (3, 11, 5),
        "ln_f/scale": (), "lm_head/w": (7, 13)}
MINI_SIZES = [int(np.prod(shape)) for shape in MINI.values()]


@pytest.fixture(params=["native", "numpy"])
def arithmetic(request):
    """Both paths of every host rule: the kernels of the native library
    and the numpy ufuncs on the same slices."""
    if request.param == "native" and native.lib() is None:
        pytest.skip("native lib unavailable (no g++)")
    native.set_enabled(request.param == "native")
    yield request.param
    native.set_enabled(True)


@pytest.mark.parametrize("parts", [2, 3, 7, 13])
def test_partition_ranges_cuts_the_store_not_the_names(parts):
    sizes = MINI_SIZES
    ranges = st.partition_ranges(sizes, parts)
    assert len(ranges) == parts
    covered = [[] for _ in sizes]
    for pieces in ranges:
        for i, lo, hi in pieces:
            assert 0 <= lo < hi <= sizes[i]
            covered[i].append((lo, hi))
    for size, spans in zip(sizes, covered):     # every element once
        assert [lo for lo, _ in spans] == [0] + [hi for _, hi in spans[:-1]]
        assert spans[-1][1] == size
    lengths = [sum(hi - lo for _, lo, hi in ps) for ps in ranges]
    assert max(lengths) - min(lengths) <= 1     # nearly equal
    # where the cut by name cannot pass total / largest (4.0 here)
    assert sum(sizes) / max(lengths) >= 0.99 * parts
    if parts > 2:
        # a large tensor is split, small ones ride together, and a cut
        # falls inside a row
        assert any(len({i for i, _, _ in ps}) > 1 for ps in ranges)
        shapes = list(MINI.values())
        assert any(lo % shapes[i][-1] for ps in ranges for i, lo, _ in ps
                   if len(shapes[i]) >= 2)


def test_partition_ranges_edges():
    assert st.partition_ranges([], 4) == []
    assert st.partition_ranges([0, 0], 4) == []
    assert st.partition_ranges([5], 8) == [[(0, i, i + 1)] for i in range(5)]
    assert st.partition_ranges([4, 0, 2], 1) == [[(0, 0, 4), (2, 0, 2)]]


every_host_rule = pytest.mark.parametrize("make_opt", [
    lambda: SGD(0.5), lambda: Momentum(0.1, momentum=0.9),
    lambda: Adam(0.01), lambda: AdamW(0.01, weight_decay=0.1),
    lambda: Lion(0.01)], ids=["sgd", "momentum", "adam", "adamw", "lion"])


@every_host_rule
@pytest.mark.parametrize("tasks", [2, 3, 7])
@pytest.mark.parametrize("contributors", [1, 3])
def test_range_cut_close_matches_serial_bit_for_bit(arithmetic, make_opt,
                                                    tasks, contributors):
    """The close cut by element ranges lands the parameters, the slots and
    the state_dict of the serial close (PSDT_STRIPES=1) bit for bit after
    three closes, whatever the task count, on either arithmetic path, with
    one contributor (no scale pass) and with three (a real multiply)."""
    rng = np.random.default_rng(39)
    init = _grads(rng, MINI)
    cores = {s: ParameterServerCore(total_workers=contributors,
                                    optimizer=make_opt(), stripes=s)
             for s in (1, tasks)}
    for core in cores.values():
        core.initialize_parameters(init)
    for it in range(1, 4):
        pushes = [_grads(rng, MINI) for _ in range(contributors)]
        for core in cores.values():
            for wid, grads in enumerate(pushes):
                r = core.receive_gradients(wid, it, grads)
            assert r.aggregation_complete, r.message
    serial, cut = cores[1], cores[tasks]
    for name, shape in MINI.items():
        got = cut.get_parameters()[name]
        assert got.shape == shape and got.dtype == np.float32
        np.testing.assert_array_equal(serial.get_parameters()[name], got)
    want, got = serial.optimizer_state(), cut.optimizer_state()
    assert want.keys() == got.keys()
    for key, value in want.items():
        if isinstance(value, dict):
            assert value.keys() == got[key].keys() and (
                not value or value.keys() == MINI.keys())
            for name in value:
                np.testing.assert_array_equal(value[name], got[key][name])
        else:
            assert value == got[key]


def _assert_same_state(want_core, got_core, shapes):
    for name, shape in shapes.items():
        got = got_core.get_parameters()[name]
        assert got.shape == shape and got.dtype == np.float32
        np.testing.assert_array_equal(want_core.get_parameters()[name], got)
    want, got = want_core.optimizer_state(), got_core.optimizer_state()
    assert want.keys() == got.keys()
    for key, value in want.items():
        if isinstance(value, dict):
            assert value.keys() == got[key].keys()
            for name in value:
                np.testing.assert_array_equal(value[name], got[key][name])
        else:
            assert value == got[key]


@pytest.mark.parametrize("make_opt", [
    lambda: SGD(0.5), lambda: Momentum(0.1, momentum=0.9),
    lambda: Adam(0.01)], ids=["sgd", "momentum", "adam"])
@pytest.mark.parametrize("tasks", [1, 3], ids=["serial", "striped"])
@pytest.mark.parametrize("contributors", [1, 3])
def test_a_push_folded_from_read_only_views_lands_the_owned_path_bit_for_bit(
        arithmetic, frame_chunks, make_opt, tasks, contributors):
    """The streaming sink is lent the frames' own read-only views
    (``decode_gradients(borrow=True)``) and sums them into kept buffers;
    its twin is handed owned arrays, whole.  Parameters, slots and
    ``state_dict`` agree bit for bit after three closes, and a frame
    overwritten the moment its fold returned reaches nothing."""
    rng = np.random.default_rng(41)
    init = _grads(rng, MINI)
    owned, lent = (ParameterServerCore(total_workers=contributors,
                                       optimizer=make_opt(), stripes=tasks)
                   for _ in range(2))
    owned.initialize_parameters(init)
    lent.initialize_parameters(init)
    for it in range(1, 4):
        pushes = [_grads(rng, MINI) for _ in range(contributors)]
        for wid, grads in enumerate(pushes):
            assert owned.receive_gradients(wid, it, dict(grads)).success
            sink = lent.begin_push(wid, it)
            assert sink.folds_at_once
            for buf, chunk in frame_chunks(wid, it, grads):
                views = decode_gradients(chunk.gradients, borrow=True)
                assert not any(v.flags.writeable for v in views.values())
                sink.fold(views)
                del views, chunk
                buf[:] = bytes(len(buf))    # the ring refills the buffer
            r = sink.commit()
        assert r.aggregation_complete, r.message
    _assert_same_state(owned, lent, MINI)


@every_host_rule
def test_a_store_of_one_tensor_is_cut_as_wide_as_any(arithmetic, make_opt):
    """The cut reads sizes only: ONE tensor runs as S range tasks (a cut
    by name cannot split it), two contributors' scale included, and lands
    the serial close's bits.  A -0.0 in the first gradient is the
    witness that a new velocity is seeded with the gradient itself."""
    rng = np.random.default_rng(7)
    init = {"w": rng.standard_normal((9, 11)).astype(np.float32)}
    cores = {s: ParameterServerCore(total_workers=2, optimizer=make_opt(),
                                    stripes=s) for s in (1, 7)}
    for core in cores.values():
        core.initialize_parameters(init)
    tasks_seen = obs_stats.histogram("ps.apply.stripe_ms").count
    for it in range(1, 3):
        pushes = [_grads(rng, {"w": (9, 11)}) for _ in range(2)]
        for grads in pushes:
            grads["w"][0, :2] = (-0.0, 0.0)
        for core in cores.values():
            for wid, grads in enumerate(pushes):
                r = core.receive_gradients(wid, it, grads)
            assert r.aggregation_complete, r.message
    assert obs_stats.histogram("ps.apply.stripe_ms").count == tasks_seen + 14
    np.testing.assert_array_equal(cores[1].get_parameters()["w"],
                                  cores[7].get_parameters()["w"])
    want, got = cores[1].optimizer_state(), cores[7].optimizer_state()
    for key, value in want.items():
        if isinstance(value, dict):
            # (array_equal alone takes -0.0 for 0.0)
            np.testing.assert_array_equal(value["w"], got[key]["w"])
            np.testing.assert_array_equal(np.signbit(value["w"]),
                                          np.signbit(got[key]["w"]))
        else:
            assert value == got[key]


def test_momentum_seeds_a_new_velocity_with_the_gradient(arithmetic):
    """v starts as -0.0, the additive identity, so the first step's
    mu * v + g is g bit for bit on both arithmetic paths: a -0.0 keeps its
    sign (a seed of zeros gives +0.0), and the slot is an owned copy."""
    opt = Momentum(0.5, momentum=0.9)
    g = np.array([-0.0, 0.0, 1.5, -2.0, np.inf], np.float32)
    p = np.arange(5, dtype=np.float32)
    out = opt.apply({"w": p}, {"w": g})
    v = opt.velocity["w"]
    assert v is not g and not np.shares_memory(v, g)
    np.testing.assert_array_equal(v, g)
    np.testing.assert_array_equal(np.signbit(v), np.signbit(g))
    np.testing.assert_array_equal(out["w"][:4],
                                  p[:4] - np.float32(0.5) * g[:4])


def test_adamw_decays_a_cut_matrix_and_never_a_vector(arithmetic):
    """The matrices-only mask reads the TENSOR's rank: a range of a 2-D
    tensor is a 1-D slice and still decays, a 1-D tensor cut into as
    many ranges never does."""
    lr, wd = 0.5, 0.25
    core = ParameterServerCore(
        total_workers=1, stripes=7,
        optimizer=AdamW(lr, weight_decay=wd))
    init = {"matrix": np.arange(1, 181, dtype=np.float32).reshape(20, 9),
            "vector": np.arange(1, 151, dtype=np.float32)}
    core.initialize_parameters(init)
    zero = {name: np.zeros_like(value) for name, value in init.items()}
    assert core.receive_gradients(0, 1, zero).aggregation_complete
    after = core.get_parameters()
    # a zero gradient leaves Adam's term at 0: what moved is the decay
    np.testing.assert_array_equal(after["vector"], init["vector"])
    np.testing.assert_array_equal(
        after["matrix"],
        init["matrix"] - np.float32(lr) * (np.float32(wd) * init["matrix"]))


def test_striped_chunked_fold_equals_whole_push(numpy_only):
    """A chunk-streamed push through begin_push folds stripe-parallel and
    must land exactly what one whole-store push lands."""
    rng = np.random.default_rng(3)
    init = _grads(rng, SHAPES)
    grads = [_grads(rng, SHAPES) for _ in range(2)]
    whole = ParameterServerCore(total_workers=2, stripes=1)
    chunked = ParameterServerCore(total_workers=2, stripes=4)
    for core in (whole, chunked):
        core.initialize_parameters(init)
    for wid in range(2):
        whole.receive_gradients(wid, 1, grads[wid])
        sink = chunked.begin_push(wid, 1)
        items = list(grads[wid].items())
        for lo in range(0, len(items), 3):
            sink.fold(dict(items[lo:lo + 3]))
        r = sink.commit()
    assert r.aggregation_complete
    a, b = whole.get_parameters(), chunked.get_parameters()
    for name in SHAPES:
        np.testing.assert_array_equal(a[name], b[name])


def test_striped_retry_replay_folds_each_tensor_once(numpy_only):
    """The reservation set must dedup a replayed chunk exactly like the
    serial folded set: retries converge to one contribution."""
    core = ParameterServerCore(total_workers=2, stripes=4)
    core.initialize_parameters({"w": np.zeros(4, np.float32)})
    payload = {"w": np.full(4, 6.0, np.float32)}
    sink = core.begin_push(0, 1)
    sink.fold(payload)
    sink.fold(payload)  # replayed chunk (RPC retry): must not double-add
    sink.commit()
    core.receive_gradients(1, 1, {"w": np.full(4, 2.0, np.float32)})
    # mean of {6, 2} = 4; lr 1.0 SGD from 0 => -4
    np.testing.assert_array_equal(core.get_parameters()["w"],
                                  np.full(4, -4.0, np.float32))


class _GatedArray:
    """Array-like whose materialization parks on an event — pins a
    striped fold inside its numpy conversion, outside _state_lock."""

    def __init__(self, value: np.ndarray, gate: threading.Event,
                 entered: threading.Event):
        self._value = value
        self._gate = gate
        self._entered = entered

    def __array__(self, dtype=None, copy=None):
        self._entered.set()
        assert self._gate.wait(10.0), "test gate never released"
        return np.asarray(self._value, dtype or np.float32)


def test_close_drains_inflight_striped_folds(numpy_only):
    """A fold whose numpy add is still running when the barrier fills
    must be drained into the aggregate before the close scales it — the
    mid-stream worker's values stay in their per-name means (the
    documented fold-on-arrival semantics), never torn or dropped."""
    core = ParameterServerCore(total_workers=2, stripes=2)
    core.initialize_parameters({"w": np.zeros(3, np.float32)})
    gate, entered = threading.Event(), threading.Event()
    slow = _GatedArray(np.full(3, 9.0, np.float32), gate, entered)

    def slow_fold():
        sink = core.begin_push(0, 1)
        sink.fold({"w": slow})  # blocks in __array__ inside the stripe

    folder = threading.Thread(target=slow_fold, name="test-slow-fold",
                              daemon=True)
    folder.start()
    assert entered.wait(5.0)

    done = threading.Event()

    def closing_pushes():
        core.receive_gradients(1, 1, {"w": np.full(3, 3.0, np.float32)})
        core.receive_gradients(2, 1, {"w": np.full(3, 6.0, np.float32)})
        done.set()

    closer = threading.Thread(target=closing_pushes, name="test-closer",
                              daemon=True)
    closer.start()
    time.sleep(0.3)
    # the barrier is full (workers 1+2) but the close must still be
    # draining worker 0's in-flight fold
    assert not done.is_set()
    gate.set()
    folder.join(5.0)
    closer.join(5.0)
    assert done.is_set()
    # all three folds are in the mean: (9 + 3 + 6) / 3 = 6, SGD lr 1.0
    np.testing.assert_array_equal(core.get_parameters()["w"],
                                  np.full(3, -6.0, np.float32))


class _GatedSGD(SGD):
    """SGD whose range tasks park on an event — pins the striped
    apply's compute window open for race tests."""

    def __init__(self, gate: threading.Event, entered: threading.Event):
        super().__init__(1.0)
        self._gate = gate
        self._entered = entered

    def update_range(self, *args):
        self._entered.set()
        assert self._gate.wait(10.0), "test gate never released"
        return super().update_range(*args)


def test_initialize_during_striped_apply_wins(numpy_only):
    """An initialize_parameters() landing while the striped apply is
    computing must not be clobbered by the swap — the serial path's
    outcome for that interleaving (apply under the lock, then the
    initialize overwrites) is 'the initialize wins'."""
    gate, entered = threading.Event(), threading.Event()
    core = ParameterServerCore(total_workers=1, stripes=2,
                               optimizer=_GatedSGD(gate, entered))
    core.initialize_parameters({"w": np.zeros(4, np.float32),
                                "b": np.zeros(2, np.float32)})

    pusher = threading.Thread(
        target=core.receive_gradients, name="test-apply-pusher",
        args=(0, 1, {"w": np.full(4, 5.0, np.float32),
                     "b": np.full(2, 5.0, np.float32)}), daemon=True)
    pusher.start()
    assert entered.wait(5.0)
    fresh = {"w": np.full(4, 42.0, np.float32),
             "b": np.full(2, 42.0, np.float32)}
    core.initialize_parameters(fresh)
    gate.set()
    pusher.join(10.0)
    assert not pusher.is_alive()
    params = core.get_parameters()
    np.testing.assert_array_equal(params["w"], fresh["w"])
    np.testing.assert_array_equal(params["b"], fresh["b"])


# ------------------------------------------------------------- checkpoint

@pytest.mark.parametrize("shapes", [SHAPES, MINI], ids=["small", "mini"])
def test_checkpoint_roundtrip_of_striped_optimizer_state(tmp_path,
                                                         numpy_only, shapes):
    """Optimizer state written by stripe-parallel applies must survive a
    CheckpointManager save/load into ANY stripe count (the slots are
    keyed by tensor name and whole, whatever ranges wrote them) and
    continue bit-identically."""
    from parameter_server_distributed_tpu.checkpoint.manager import (
        CheckpointManager)

    rng = np.random.default_rng(11)
    init = _grads(rng, shapes)
    steps = [_grads(rng, shapes) for _ in range(4)]

    core = ParameterServerCore(total_workers=1, optimizer=Adam(0.05),
                               stripes=3)
    core.initialize_parameters(init)
    for it, grads in enumerate(steps[:2], start=1):
        core.receive_gradients(0, it, grads)
    mgr = CheckpointManager(core, directory=str(tmp_path),
                            checkpoint_interval=10**9)
    path = mgr.save(epoch=1)

    finals = {}
    for restore_stripes in (1, 2, 3, 7):
        restored = ParameterServerCore(total_workers=1,
                                       optimizer=Adam(0.05),
                                       stripes=restore_stripes)
        CheckpointManager(restored, directory=str(tmp_path),
                          checkpoint_interval=10**9).load(path)
        for it, grads in enumerate(steps[2:], start=3):
            restored.receive_gradients(0, it, grads)
        finals[restore_stripes] = restored.get_parameters()
    for it, grads in enumerate(steps[2:], start=3):
        core.receive_gradients(0, it, grads)
    live = core.get_parameters()
    for s, params in finals.items():
        for name in shapes:
            np.testing.assert_array_equal(live[name], params[name])


# --------------------------------------------------------------- lockcheck

@pytest.mark.lockcheck
def test_concurrent_striped_push_close_restore_races(numpy_only):
    """Pushers, chunk folders, sync pollers, and a restorer hammering a
    striped core under PSDT_LOCK_CHECK=1: every stripe/pool/core lock is
    an order-asserting CheckedLock, so an ordering bug raises instead of
    deadlocking.  The store must stay structurally intact throughout."""
    rng = np.random.default_rng(5)
    init = _grads(rng, SHAPES)
    core = ParameterServerCore(total_workers=3, optimizer=Adam(0.01),
                               stripes=3)
    core.initialize_parameters(init)
    errors: list[BaseException] = []
    stop = threading.Event()

    def pusher(wid: int):
        try:
            it = 1
            while not stop.is_set():
                sink = core.begin_push(wid, it)
                items = list(_grads(rng, SHAPES).items())
                sink.fold(dict(items[:4]))
                sink.fold(dict(items[4:]))
                sink.commit()
                core.check_sync_status(it)
                it += 1
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    def restorer():
        try:
            while not stop.is_set():
                time.sleep(0.02)
                epoch, it, params = core.snapshot()
                state = core.optimizer_state()
                core.restore(epoch, it, params, optimizer_state=state)
        except BaseException as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=pusher, args=(wid,),
                                name=f"test-pusher-{wid}", daemon=True)
               for wid in range(3)]
    threads.append(threading.Thread(target=restorer, name="test-restorer",
                                    daemon=True))
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(10.0)
        assert not t.is_alive()
    assert not errors, errors
    params = core.get_parameters()
    assert set(params) == set(SHAPES)
    for name, value in params.items():
        assert np.all(np.isfinite(value)), name


# ------------------------------------------------- in-place optimizer path

@pytest.mark.parametrize("make_opt", [lambda: Adam(0.01),
                                      lambda: Momentum(0.1)])
def test_optimizer_numpy_path_peak_allocation(numpy_only, make_opt):
    """The in-place numpy paths must allocate ~(output + scratch) per
    tensor, not one temporary per sub-op: a steady-state apply over a
    4 MB tensor stays under 2.5 tensor-sizes of peak traced allocation
    (the old expression-per-line Adam peaked well past 4x)."""
    n = 1_000_000
    params = {"w": np.zeros(n, np.float32)}
    grads = {"w": np.full(n, 0.5, np.float32)}
    opt = make_opt()
    params = opt.apply(params, grads)  # warm: slots + scratch allocate
    tracemalloc.start()
    params = opt.apply(params, grads)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert peak <= 2.5 * 4 * n, f"peak {peak / 4 / n:.2f}x tensor size"


def test_inplace_adam_is_bitwise_the_pre_inplace_formula(numpy_only):
    """The in-place rewrite must preserve the ORIGINAL expression's
    evaluation order exactly — `p - lr * (m/bc1) / denom` associates as
    ((lr * (m/bc1)) / denom), and reordering it costs 1-ulp drift that
    breaks PSDT_STRIPES=1 bit-compatibility with pre-stripe checkpoints."""
    rng = np.random.default_rng(2)
    p0 = rng.standard_normal(257).astype(np.float32)
    opt = Adam(0.01, b1=0.9, b2=0.999, eps=1e-8)
    params = {"w": p0.copy()}
    m_ref = np.zeros_like(p0)
    v_ref = np.zeros_like(p0)
    p_ref = p0.copy()
    for step in range(1, 4):
        g = rng.standard_normal(257).astype(np.float32)
        params = opt.apply(params, {"w": g})
        b1, b2 = np.float32(0.9), np.float32(0.999)
        m_ref = b1 * m_ref + (1 - b1) * g
        v_ref = b2 * v_ref + (1 - b2) * (g * g)
        bc1 = 1.0 - 0.9 ** step
        bc2 = 1.0 - 0.999 ** step
        # verbatim pre-in-place expression, original precedence
        p_ref = p_ref - np.float32(0.01) * (m_ref / bc1) / (
            np.sqrt(v_ref / bc2) + 1e-8)
    np.testing.assert_array_equal(params["w"], p_ref)


def test_striping_declarations():
    """Host optimizers are name-sliceable; device-resident jit programs
    are not and must fall back to the serial whole-store apply."""
    for name in ("sgd", "momentum", "adam", "adamw", "lion"):
        assert make_optimizer(name, 0.1).supports_striping, name
    from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
        DeviceOptimizer, PallasOptimizer)
    assert DeviceOptimizer.supports_striping is False
    assert PallasOptimizer.supports_striping is False


def test_pallas_optimizer_on_striped_sync_path():
    """optimizer=pallas_* on the synchronous barrier path: the striped
    close must fall back to the (device-resident) whole-store apply and
    land the correct SGD result even with stripes configured."""
    core = ParameterServerCore(total_workers=2, stripes=2,
                               optimizer=make_optimizer("pallas_sgd", 1.0))
    init = {"w": np.arange(8, dtype=np.float32),
            "b": np.ones(3, np.float32)}
    core.initialize_parameters(init)
    core.receive_gradients(0, 1, {"w": np.full(8, 2.0, np.float32),
                                  "b": np.full(3, 4.0, np.float32)})
    r = core.receive_gradients(1, 1, {"w": np.full(8, 4.0, np.float32),
                                      "b": np.full(3, 2.0, np.float32)})
    assert r.aggregation_complete, r.message
    params = core.get_parameters()
    np.testing.assert_allclose(np.asarray(params["w"]),
                               np.arange(8, dtype=np.float32) - 3.0)
    np.testing.assert_allclose(np.asarray(params["b"]),
                               np.ones(3, np.float32) - 3.0)


# ---------------------------------------------------------- error feedback

def _make_worker(wire_dtype: str, topk_density: float = 0.25):
    from parameter_server_distributed_tpu.config import WorkerConfig
    from parameter_server_distributed_tpu.worker.worker import Worker

    worker = Worker(WorkerConfig(wire_dtype=wire_dtype,
                                 topk_density=topk_density),
                    trainer=None, batches=iter(()), start_heartbeat=False)
    worker._peer_packed_ok = True  # packed support proven, for the test
    return worker


@pytest.mark.parametrize("wire", ["int8", "topk"])
def test_lossy_with_error_feedback_tracks_f32_closer(numpy_only, wire):
    """The convergence property the residual exists for: over a run of
    lossy pushes, carrying the quantization error forward keeps the
    parameter trajectory strictly closer to the exact-f32 trajectory than
    dropping it (PSDT_ERROR_FEEDBACK=0)."""
    rng = np.random.default_rng(13)
    shapes = {"w": (64, 16), "b": (32,)}
    init = _grads(rng, shapes)
    steps = [_grads(rng, shapes) for _ in range(20)]
    wire_id = m.WIRE_DTYPE_NAMES[wire]

    worker = _make_worker(wire)

    def run(mode: str) -> dict:
        core = ParameterServerCore(total_workers=1, optimizer=SGD(0.05))
        core.initialize_parameters(init)
        worker._ef_residual = {}
        for it, grads in enumerate(steps, start=1):
            if mode == "f32":
                seen = grads
            elif mode == "ef":
                tensors, residual = worker._compress_with_feedback(
                    grads, wire_id)
                worker._ef_residual = residual
                seen = {t.name: t.to_array() for t in tensors}
            else:  # lossy, no feedback
                tensors = to_wire(grads, wire_id, topk_density=0.25)
                seen = {t.name: t.to_array() for t in tensors}
            core.receive_gradients(0, it, seen)
        return core.get_parameters()

    exact = run("f32")
    with_ef = run("ef")
    without = run("lossy")

    def dist(a):
        return sum(float(np.linalg.norm(a[k] - exact[k])) for k in shapes)

    assert dist(with_ef) < dist(without), (
        f"{wire}: EF {dist(with_ef):.4f} !< no-EF {dist(without):.4f}")


def test_error_feedback_env_gate(monkeypatch):
    """PSDT_ERROR_FEEDBACK=0 disables the residual carry on both push
    paths (the A/B knob); the default carries it."""
    worker = _make_worker("int8")
    grads = {"w": np.linspace(-1, 1, 64, dtype=np.float32)}

    tensors_fn, box = worker._wire_tensors(grads)
    list(tensors_fn())
    assert box is not None and "w" in box  # default: residual carried

    monkeypatch.setenv("PSDT_ERROR_FEEDBACK", "0")
    tensors_fn, box = worker._wire_tensors(grads)
    tensors = list(tensors_fn())
    assert box is None
    # and the payload is the PLAIN compression of g (no residual added)
    plain = to_wire(grads, m.WIRE_INT8)
    np.testing.assert_array_equal(tensors[0].to_array(),
                                  plain[0].to_array())


# -------------------------------------------------------- encode + metrics

def test_striped_encode_is_byte_identical(monkeypatch):
    rng = np.random.default_rng(17)
    store = {f"t{i}": rng.standard_normal((256, 33)).astype(np.float32)
             for i in range(7)}
    budget = 64 << 10  # several tensors per group, several groups

    def bodies(stripes: str) -> list[bytes]:
        monkeypatch.setenv(st.ENV_STRIPES, stripes)
        tensors = to_wire(store, wire_dtype=m.WIRE_BF16)
        return encode_parameter_record_groups(
            list(split_tensors(tensors, budget)),
            lambda i, size: memoryview(bytearray(size)))

    serial = bodies("1")
    striped = bodies("4")
    assert len(serial) > 1
    assert serial == striped


def test_striped_apply_metrics_and_rollup(numpy_only):
    """The striped close must publish ps.apply.stripe_ms observations and
    the ps.apply.parallelism gauge, and the pst-status rollup must carry
    them."""
    from parameter_server_distributed_tpu.obs.export import (
        render_rollup, worker_rollup)

    rng = np.random.default_rng(23)
    init = _grads(rng, SHAPES)
    core = ParameterServerCore(total_workers=1, optimizer=Adam(0.01),
                               stripes=2)
    core.initialize_parameters(init)
    before = obs_stats.REGISTRY.snapshot()["histograms"].get(
        "ps.apply.stripe_ms", {"count": 0})["count"]
    core.receive_gradients(0, 1, _grads(rng, SHAPES))
    snap = obs_stats.REGISTRY.snapshot()
    after = snap["histograms"]["ps.apply.stripe_ms"]["count"]
    assert after >= before + 2  # one observation per stripe
    assert snap["gauges"]["ps.apply.parallelism"] > 0
    rollup = worker_rollup(snap)
    assert "apply_stripe_ms" in rollup["ps"]
    assert rollup["ps"]["apply_parallelism"] > 0
    text = render_rollup({"per_worker": {0: rollup}, "cluster": {}})
    assert "apply stripes" in text
