"""The seam between the program and the device, on the CPU: chip_smoke.py's
platform rule and its --tiny run, where the compile cache goes, who decides
pallas interpret mode, the gridded fused-update kernels against the numpy
optimizers, and the host-keyed native build."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from parameter_server_distributed_tpu import native
from parameter_server_distributed_tpu.core import optimizer as host_opt
from parameter_server_distributed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args: str, timeout: float):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as on a laptop
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          timeout=timeout)


def test_chip_smoke_refuses_a_cpu_backend():
    """Without --tiny the script exits non-zero before any phase and
    prints no result."""
    proc = _run_smoke(timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == b""
    assert b"not a TPU" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_tiny_runs_every_phase():
    proc = _run_smoke("--tiny", timeout=900)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.decode().splitlines()]
    assert all(ln.get("platform", "cpu") == "cpu" for ln in lines)
    phases = [ln for ln in lines if "ok" in ln and "phase" in ln]
    assert [ln["phase"] for ln in phases] == [
        "ps_round", "ps_round_device_close", "spmd_train", "serve", "kernels"]
    assert all(ln["ok"] for ln in phases)
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


# ---------------------------------------------------------- compile cache
@pytest.fixture
def restore_cache_dir():
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_set_means_the_package_sets_nothing(
        monkeypatch, restore_cache_dir):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache.enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_the_checkout_from_any_cwd(
        monkeypatch, tmp_path, restore_cache_dir):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for name in ("a", "b"):
        (tmp_path / name).mkdir()
        monkeypatch.chdir(tmp_path / name)
        seen.append(compile_cache.enable_compile_cache())
        assert jax.config.jax_compilation_cache_dir == seen[-1]
    assert seen == [os.path.join(REPO, ".jax_cache")] * 2


def test_only_the_helper_names_the_cache_directory():
    """No code path may set a cache directory of its own: the config key
    appears in utils/compile_cache.py and nowhere else in what ships."""
    owners = []
    roots = [os.path.join(REPO, "parameter_server_distributed_tpu"),
             os.path.join(REPO, "scripts"), os.path.join(REPO, "examples")]
    files = [os.path.join(REPO, name)
             for name in ("chip_smoke.py", "__graft_entry__.py")]
    for root in roots:
        for directory, _, names in os.walk(root):
            files += [os.path.join(directory, n) for n in names
                      if n.endswith((".py", ".sh"))]
    for path in files:
        with open(path) as f:
            if "compilation_cache_dir" in f.read().lower():
                owners.append(os.path.relpath(path, REPO))
    assert owners == [os.path.join("parameter_server_distributed_tpu",
                                   "utils", "compile_cache.py")]


# -------------------------------------------------------- interpret mode
def test_interpret_mode_follows_the_devices(monkeypatch):
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.ops.pallas import interpret_mode

    assert interpret_mode()                      # CPU backend: interpret
    assert interpret_mode(jnp.ones(3), np.ones(3))
    assert jax.jit(lambda x: x * interpret_mode(x))(jnp.ones(3))[0] == 1
    chip = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    assert not interpret_mode()                  # would lower via Mosaic
    assert interpret_mode(jnp.ones(3))           # a concrete CPU operand


# ------------------------------------------- gridded fused-update kernels
# 133,900 elements: 1,047 rows of 128 lanes, padded to two 1,024-row blocks
FUSED_SHAPE = (1030, 130)


@pytest.fixture
def numpy_rules():
    """core/optimizer.py's numpy update rules (not the C++ kernels)."""
    native.set_enabled(False)
    yield
    native.set_enabled(os.environ.get("PSDT_NATIVE", "1").lower()
                       not in ("0", "false"))


def _fused_inputs(rng):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.ops.pallas import fused_update

    assert np.prod(FUSED_SHAPE) > fused_update.BLOCK_ROWS * fused_update.LANE
    p = rng.standard_normal(FUSED_SHAPE).astype(np.float32)
    grads = [rng.standard_normal(FUSED_SHAPE).astype(np.float32)
             for _ in range(2)]
    return {"w": p}, [{"w": g} for g in grads], lambda s: {
        k: jnp.asarray(v) for k, v in s.items()}


def test_fused_sgd_grid_matches_numpy_rule(rng, numpy_rules):
    from parameter_server_distributed_tpu.ops.pallas.fused_update import (
        fused_sgd)

    params, grads, dev = _fused_inputs(rng)
    host = host_opt.SGD(0.3)
    fused = dev(params)
    for g in grads:
        params = host.apply(params, g)
        fused = fused_sgd(fused, dev(g), lr=0.3)
        np.testing.assert_allclose(np.asarray(fused["w"]), params["w"],
                                   rtol=0, atol=1e-6)


def test_fused_momentum_grid_matches_numpy_rule(rng, numpy_rules):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.ops.pallas.fused_update import (
        fused_momentum)

    params, grads, dev = _fused_inputs(rng)
    host = host_opt.Momentum(0.1, 0.9)
    fused, velocity = dev(params), {"w": jnp.zeros(FUSED_SHAPE)}
    for g in grads:
        params = host.apply(params, g)
        fused, velocity = fused_momentum(fused, dev(g), velocity, lr=0.1,
                                         mu=0.9)
        np.testing.assert_allclose(np.asarray(fused["w"]), params["w"],
                                   rtol=0, atol=1e-6)


def test_fused_adam_grid_matches_numpy_rule(rng, numpy_rules):
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.ops.pallas.fused_update import (
        fused_adam)

    params, grads, dev = _fused_inputs(rng)
    host = host_opt.Adam(0.01)
    fused = dev(params)
    m, v = {"w": jnp.zeros(FUSED_SHAPE)}, {"w": jnp.zeros(FUSED_SHAPE)}
    for step, g in enumerate(grads, start=1):
        params = host.apply(params, g)
        fused, m, v = fused_adam(fused, dev(g), m, v, step, lr=0.01)
        np.testing.assert_allclose(np.asarray(fused["w"]), params["w"],
                                   rtol=0, atol=1e-6)


def test_pallas_optimizer_that_cannot_be_built_raises(monkeypatch):
    from parameter_server_distributed_tpu.async_sgd import device_optimizer

    def broken(self, *args, **kwargs):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(device_optimizer.PallasOptimizer, "__init__", broken)
    with pytest.raises(RuntimeError, match="Mosaic refused") as caught:
        host_opt.make_optimizer("pallas_adam", 0.01)
    assert "use 'adam'" in str(caught.value)


# ------------------------------------------------------------ native build
def test_native_build_key_is_a_fixed_function_of_the_host():
    key = native.build_key()
    assert key == native.build_key()
    assert len(key) == 12 and int(key, 16) >= 0
    assert os.path.join("build", key) in native._so_path()
