"""The fused-update pallas kernels (interpret mode on CPU; compiled on
TPU).  The attention kernel's tests are tests/test_fused_attention.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.ops.pallas.fused_update import (
    fused_adam, fused_momentum, fused_sgd)


def test_fused_sgd_matches_reference(rng):
    params = {"w": rng.standard_normal((13, 7)).astype(np.float32),
              "b": rng.standard_normal(5).astype(np.float32)}
    grads = {"w": rng.standard_normal((13, 7)).astype(np.float32),
             "b": rng.standard_normal(5).astype(np.float32)}
    out = fused_sgd(params, grads, lr=0.3)
    for k in params:
        np.testing.assert_allclose(np.asarray(out[k]),
                                   params[k] - 0.3 * grads[k], rtol=1e-5, atol=1e-7)
        assert out[k].shape == params[k].shape


def test_fused_momentum_matches_reference(rng):
    p = {"w": rng.standard_normal((9, 11)).astype(np.float32)}
    g = {"w": rng.standard_normal((9, 11)).astype(np.float32)}
    vel = {"w": rng.standard_normal((9, 11)).astype(np.float32)}
    new_p, new_v = fused_momentum(p, g, vel, lr=0.1, mu=0.9)
    v_ref = 0.9 * vel["w"] + g["w"]
    np.testing.assert_allclose(np.asarray(new_v["w"]), v_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_p["w"]), p["w"] - 0.1 * v_ref,
                               rtol=1e-5, atol=1e-7)


def test_fused_adam_matches_host_adam(rng):
    from parameter_server_distributed_tpu.core.optimizer import Adam
    shape = (17, 5)
    p = {"w": rng.standard_normal(shape).astype(np.float32)}
    g = {"w": rng.standard_normal(shape).astype(np.float32)}
    m = {"w": np.zeros(shape, np.float32)}
    v = {"w": np.zeros(shape, np.float32)}

    host = Adam(0.01)
    host_out = host.apply(dict(p), dict(g))

    new_p, new_m, new_v = fused_adam(p, g, m, v, step=1, lr=0.01)
    np.testing.assert_allclose(np.asarray(new_p["w"]), host_out["w"],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_m["w"]), host.m["w"], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new_v["w"]), host.v["w"], rtol=1e-5, atol=1e-7)


def test_fused_adam_traced_step_no_recompile(rng):
    """step is data (SMEM), not a compile-time constant: the jitted apply
    must not retrace across steps and must match the host Adam trajectory."""
    import jax

    from parameter_server_distributed_tpu.core.optimizer import Adam

    shape = (12, 6)
    p = {"w": rng.standard_normal(shape).astype(np.float32)}
    host = Adam(0.01)
    host_p = dict(p)

    traces = 0

    @jax.jit
    def apply(params, grads, m, v, step):
        nonlocal traces
        traces += 1
        return fused_adam(params, grads, m, v, step, lr=0.01)

    m = {"w": jnp.zeros(shape, jnp.float32)}
    v = {"w": jnp.zeros(shape, jnp.float32)}
    cur = {k: jnp.asarray(x) for k, x in p.items()}
    for step in range(1, 4):
        g = {"w": rng.standard_normal(shape).astype(np.float32)}
        host_p = host.apply(host_p, g)
        cur, m, v = apply(cur, {"w": jnp.asarray(g["w"])}, m, v,
                          jnp.int32(step))
    assert traces == 1
    np.testing.assert_allclose(np.asarray(cur["w"]), host_p["w"],
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("rule", ["sgd", "momentum", "adam"])
def test_pallas_optimizer_matches_host_in_ps_core(rng, rule):
    """PallasOptimizer (the fused kernels' production caller) must drive
    ParameterServerCore to the same parameters as the host optimizer."""
    from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
        PallasOptimizer)
    from parameter_server_distributed_tpu.core.optimizer import make_optimizer
    from parameter_server_distributed_tpu.core.ps_core import (
        ParameterServerCore)

    init = {"w": rng.standard_normal((6, 10)).astype(np.float32),
            "b": rng.standard_normal(4).astype(np.float32)}
    grad_seq = [{"w": rng.standard_normal((6, 10)).astype(np.float32),
                 "b": rng.standard_normal(4).astype(np.float32)}
                for _ in range(3)]

    stores = {}
    for name, opt in (("pallas", PallasOptimizer(rule, 0.1)),
                      ("host", make_optimizer(rule, 0.1))):
        ps = ParameterServerCore(total_workers=1, optimizer=opt,
                                 staleness_bound=2)
        ps.initialize_parameters(init)
        for it, g in enumerate(grad_seq, start=1):
            assert ps.receive_gradients(0, it, g).success
        stores[name] = ps.get_parameters()
    for key in init:
        np.testing.assert_allclose(np.asarray(stores["pallas"][key]),
                                   np.asarray(stores["host"][key]),
                                   rtol=1e-4, atol=1e-6)


def test_pallas_optimizer_state_roundtrip(rng):
    """state_dict/load_state_dict round-trips slots + step (the checkpoint
    sidecar contract)."""
    from parameter_server_distributed_tpu.async_sgd.device_optimizer import (
        PallasOptimizer)

    p = {"w": rng.standard_normal((5, 5)).astype(np.float32)}
    g = {"w": rng.standard_normal((5, 5)).astype(np.float32)}
    opt = PallasOptimizer("adam", 0.01)
    p2 = opt.apply(p, g)

    clone = PallasOptimizer("adam", 0.01)
    clone.load_state_dict(opt.state_dict())
    assert clone.step == opt.step
    out_a = opt.apply(p2, g)
    out_b = clone.apply(p2, g)
    np.testing.assert_allclose(np.asarray(out_a["w"]), np.asarray(out_b["w"]),
                               rtol=1e-5, atol=1e-7)
