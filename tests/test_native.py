"""Native C++ kernel tests (skipped when g++ is unavailable)."""

import numpy as np
import pytest

from parameter_server_distributed_tpu import native


# The sticky-failure/retry tests live in tests/test_codec.py: this
# module's pytestmark skips EVERYTHING on no-g++ hosts, which is exactly
# where the retry machinery matters.
pytestmark = pytest.mark.skipif(native.lib() is None,
                                reason="native lib unavailable (no g++)")


def test_native_mean_matches_numpy(rng):
    arrays = [rng.standard_normal((33, 7)).astype(np.float32)
              for _ in range(5)]
    out = native.mean_over_workers_native(arrays)
    assert out is not None
    np.testing.assert_allclose(out, np.mean(arrays, axis=0), rtol=1e-6)


def test_native_sgd_out_of_place(rng):
    p = rng.standard_normal(1000).astype(np.float32)
    g = rng.standard_normal(1000).astype(np.float32)
    expect = p - 0.25 * g
    served, out = p.copy(), np.empty_like(p)
    assert native.sgd_native(p, g, out, 0.25)
    np.testing.assert_allclose(out, expect, rtol=1e-6)
    np.testing.assert_array_equal(p, served)  # the old store is read only


def test_native_mean_sgd_fused(rng):
    p = rng.standard_normal(512).astype(np.float32)
    grads = [rng.standard_normal(512).astype(np.float32) for _ in range(3)]
    expect = p - 0.1 * np.mean(grads, axis=0)
    assert native.mean_sgd_native(p, grads, 0.1)
    np.testing.assert_allclose(p, expect, rtol=1e-5)


def test_native_rejects_unsuitable_inputs(rng):
    # float64 param -> fallback requested
    p = rng.standard_normal(10)  # float64
    g = rng.standard_normal(10).astype(np.float32)
    assert not native.sgd_native(p, g, np.empty(10, np.float32), 0.1)
    # the kernels declare their output __restrict__: writing over an
    # input is refused, not undefined
    p32 = p.astype(np.float32)
    assert not native.sgd_native(p32, g, p32, 0.1)
    assert not native.sgd_native(p32, g, p32[::2], 0.1)
    assert native.mean_over_workers_native([]) is None


def test_native_momentum_matches_numpy(rng):
    p = rng.standard_normal(513).astype(np.float32)
    g = rng.standard_normal(513).astype(np.float32)
    v = rng.standard_normal(513).astype(np.float32)
    expect_v = 0.9 * v + g
    expect_p = p - 0.05 * expect_v
    out = np.empty_like(p)
    assert native.momentum_native(p, g, v, out, 0.05, 0.9)
    np.testing.assert_allclose(v, expect_v, rtol=1e-6)
    np.testing.assert_allclose(out, expect_p, rtol=1e-5, atol=1e-6)


def test_native_adam_matches_numpy(rng):
    p = rng.standard_normal(257).astype(np.float32)
    g = rng.standard_normal(257).astype(np.float32)
    m = rng.standard_normal(257).astype(np.float32) * 0.1
    v = np.abs(rng.standard_normal(257)).astype(np.float32) * 0.1
    step, lr, b1, b2, eps = 3, 1e-3, 0.9, 0.999, 1e-8
    em = b1 * m + (1 - b1) * g
    ev = b2 * v + (1 - b2) * g * g
    ep = p - lr * (em / (1 - b1**step)) / (np.sqrt(ev / (1 - b2**step)) + eps)
    out = np.empty_like(p)
    assert native.adam_native(p, g, m, v, out, lr, b1, b2, eps, step)
    np.testing.assert_allclose(m, em, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(v, ev, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(out, ep, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam"])
def test_host_optimizer_native_and_numpy_paths_agree(rng, name):
    """Multi-step optimizer trajectories must be identical (to f32 tolerance)
    with the native path on and off."""
    from parameter_server_distributed_tpu.core.optimizer import make_optimizer

    params = {"w": rng.standard_normal((17, 9)).astype(np.float32),
              "b": rng.standard_normal(23).astype(np.float32)}
    grad_seq = [{"w": rng.standard_normal((17, 9)).astype(np.float32),
                 "b": rng.standard_normal(23).astype(np.float32)}
                for _ in range(4)]
    results = {}
    for enabled in (True, False):
        native.set_enabled(enabled)
        try:
            opt = make_optimizer(name, 0.1)
            cur = dict(params)
            for grads in grad_seq:
                cur = opt.apply(cur, grads)
            results[enabled] = cur
        finally:
            native.set_enabled(True)
    for key in params:
        np.testing.assert_allclose(results[True][key], results[False][key],
                                   rtol=1e-4, atol=1e-6)


def test_ps_core_fused_mean_sgd_agrees_with_numpy_path(rng):
    """The sync barrier (fused psdt_mean_sgd apply) must produce the same
    parameters with the native path on and off."""
    from parameter_server_distributed_tpu.core.optimizer import SGD
    from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore

    init = {"w": rng.standard_normal(128).astype(np.float32)}
    grads = [{"w": rng.standard_normal(128).astype(np.float32)}
             for _ in range(3)]
    results = {}
    for enabled in (True, False):
        native.set_enabled(enabled)
        try:
            ps = ParameterServerCore(total_workers=3,
                                     optimizer=SGD(learning_rate=0.5))
            ps.initialize_parameters(init)
            for wid, g in enumerate(grads):
                ps.receive_gradients(wid, 1, g)
            results[enabled] = ps.get_parameters()
        finally:
            native.set_enabled(True)
    np.testing.assert_allclose(results[True]["w"], results[False]["w"],
                               rtol=1e-5, atol=1e-6)
    expect = init["w"] - 0.5 * np.mean([g["w"] for g in grads], axis=0)
    np.testing.assert_allclose(results[True]["w"], expect, rtol=1e-5,
                               atol=1e-6)


def test_ps_core_native_mean_agrees_with_numpy_path(rng):
    """Aggregation through ParameterServerCore must be identical whether or
    not the native kernel is in play (same inputs, compare against a
    hand-computed numpy mean)."""
    from parameter_server_distributed_tpu.core.ps_core import ParameterServerCore
    ps = ParameterServerCore(total_workers=3)
    ps.initialize_parameters({"w": np.zeros(64, np.float32)})
    grads = [rng.standard_normal(64).astype(np.float32) for _ in range(3)]
    for wid, g in enumerate(grads):
        ps.receive_gradients(wid, 1, {"w": g})
    expect = -np.mean(grads, axis=0)  # lr=1.0, params started at 0
    np.testing.assert_allclose(ps.get_parameters()["w"], expect, rtol=1e-5,
                               atol=1e-6)


def test_native_adamw_matches_numpy(rng):
    p = rng.standard_normal(257).astype(np.float32)
    g = rng.standard_normal(257).astype(np.float32)
    m = rng.standard_normal(257).astype(np.float32) * 0.1
    v = np.abs(rng.standard_normal(257)).astype(np.float32) * 0.1
    step, lr, b1, b2, eps, wd = 3, 1e-3, 0.9, 0.999, 1e-8, 0.1
    em = b1 * m + (1 - b1) * g
    ev = b2 * v + (1 - b2) * g * g
    adam_term = (em / (1 - b1**step)) / (np.sqrt(ev / (1 - b2**step)) + eps)
    ep = p - lr * (adam_term + wd * p)
    out = np.empty_like(p)
    assert native.adam_native(p, g, m, v, out, lr, b1, b2, eps, step, wd)
    np.testing.assert_allclose(m, em, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(v, ev, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(out, ep, rtol=1e-4, atol=1e-6)


def test_host_adamw_native_and_numpy_paths_agree(rng):
    from parameter_server_distributed_tpu.core.optimizer import make_optimizer

    params = {"w": rng.standard_normal((17, 9)).astype(np.float32),
              "b": rng.standard_normal(23).astype(np.float32)}
    grad_seq = [{"w": rng.standard_normal((17, 9)).astype(np.float32),
                 "b": rng.standard_normal(23).astype(np.float32)}
                for _ in range(4)]
    results = {}
    for enabled in (True, False):
        native.set_enabled(enabled)
        try:
            opt = make_optimizer("adamw", 0.01, weight_decay=0.1)
            cur = dict(params)
            for grads in grad_seq:
                cur = opt.apply(cur, grads)
            results[enabled] = cur
        finally:
            native.set_enabled(True)
    for key in params:
        np.testing.assert_allclose(results[True][key], results[False][key],
                                   rtol=1e-4, atol=1e-6)


def test_optimizer_state_snapshot_isolated_from_in_place_applies(rng):
    """The hot path updates m/v in place; state_dict must deep-copy so a
    checkpoint snapshot taken between applies stays frozen."""
    from parameter_server_distributed_tpu.core.optimizer import make_optimizer

    opt = make_optimizer("adamw", 0.01)
    params = {"w": rng.standard_normal((8, 4)).astype(np.float32)}
    grads = {"w": rng.standard_normal((8, 4)).astype(np.float32)}
    params = opt.apply(params, grads)
    snap = opt.state_dict()
    frozen_m = snap["m"]["w"].copy()
    opt.apply(params, grads)  # mutates internal m/v in place
    np.testing.assert_array_equal(snap["m"]["w"], frozen_m)
    # load_state_dict must also own its buffers
    opt2 = make_optimizer("adamw", 0.01)
    opt2.load_state_dict(snap)
    opt2.apply(params, grads)
    np.testing.assert_array_equal(snap["m"]["w"], frozen_m)


def test_a_library_without_an_entry_point_means_numpy_for_that_rule(
        monkeypatch, rng):
    """An old build (a read-only install ``_build`` cannot replace) lacks
    the out-of-place sweeps: the rule whose entry point is missing runs
    its numpy ufuncs, the others keep their kernels, nothing raises."""
    from parameter_server_distributed_tpu.core.optimizer import Adam

    real = native.lib()

    class OldBuild:
        psdt_sgd_out = real.psdt_sgd_out        # has SGD's, lacks Adam's

    params = {"w": rng.standard_normal((17, 9)).astype(np.float32)}
    grads = {"w": rng.standard_normal((17, 9)).astype(np.float32)}
    native.set_enabled(False)
    try:
        want = Adam(0.01).apply(params, grads)
    finally:
        native.set_enabled(True)
    monkeypatch.setattr(native, "lib", lambda: OldBuild())
    p, g = params["w"], grads["w"]
    out = np.empty_like(p)
    assert native.sgd_native(p, g, out, 0.25)
    assert not native.adam_native(p, g, np.zeros_like(p), np.zeros_like(p),
                                  out, 0.01, 0.9, 0.999, 1e-8, 1)
    np.testing.assert_array_equal(Adam(0.01).apply(params, grads)["w"],
                                  want["w"])


# ------------------------------------------------- the ring's wide move

def _ring_and_span(rng, cap, pos, n, offset):
    """A ring of ``cap`` bytes, a span of ``n`` starting at ``pos`` (it may
    wrap), the caller's memory at an odd ``offset`` into its buffer, and
    what the ring must hold after the span went in."""
    mem = rng.integers(0, 256, n + offset, dtype=np.uint8)[offset:]
    want = np.zeros(cap, np.uint8)
    want[(pos + np.arange(n)) % cap] = mem
    return np.zeros(cap, np.uint8), mem, want


@pytest.mark.parametrize("width", range(1, 9))
def test_wide_ring_move_equals_memcpy(rng, width):
    """Sizes 0 to 9 MB, the span flat, wrapping inside its first piece and
    inside a later one, the caller's memory at odd alignments: into the
    ring and back out, byte for byte, whatever the width."""
    move = native.copy_fn()
    for n in (0, 1, 4095, 4096, 4097, (1 << 20) + 1, (9 << 20) + 5):
        cap = n + 12345
        for pos, offset in ((0, 0), (cap - 7, 3), (cap - n // 2 - 1, 1)):
            ring, mem, want = _ring_and_span(rng, cap, pos, n, offset)
            move(ring.ctypes.data, cap, pos, mem.ctypes.data, n, 1, width)
            np.testing.assert_array_equal(ring, want)
            out = np.zeros(n + offset, np.uint8)[offset:]
            move(ring.ctypes.data, cap, pos, out.ctypes.data, n, 0, width)
            np.testing.assert_array_equal(out, mem)


def test_wide_ring_moves_from_two_threads_at_once_finish_and_agree(rng):
    """Both ring ends of one process share the library's helpers: two
    callers at once each get their own bytes, and both come back."""
    import threading

    cap, n = 10 << 20, 8 << 20
    move = native.copy_fn()
    jobs = [_ring_and_span(np.random.default_rng(i), cap, (7 << 20) + i, n, i)
            for i in range(2)]
    failed = []

    def caller(i):
        ring, mem, want = jobs[i]
        try:
            for _ in range(20):
                ring[:] = 0
                move(ring.ctypes.data, cap, (7 << 20) + i, mem.ctypes.data,
                     n, 1, 4)
                np.testing.assert_array_equal(ring, want)
        except AssertionError as exc:
            failed.append(exc)

    threads = [threading.Thread(target=caller, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads) and not failed


def test_wide_ring_move_releases_the_gil():
    """A Python thread makes progress beside a 400 MB call."""
    import threading

    n = 400 << 20
    mem = np.ones(n, np.uint8)
    ring = np.empty(n, np.uint8)    # untouched: the call faults it in
    move = native.copy_fn()
    started, done = threading.Event(), threading.Event()

    def call():
        started.set()
        move(ring.ctypes.data, n, 0, mem.ctypes.data, n, 1, 2)
        done.set()

    thread = threading.Thread(target=call, daemon=True)
    thread.start()
    started.wait(10)
    turns = 0
    while not done.is_set():
        turns += 1
    thread.join(timeout=60)
    assert done.is_set() and ring[-1] == 1
    assert turns > 1000, turns
