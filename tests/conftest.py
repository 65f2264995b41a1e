"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip behavior (DP/TP/SP shardings, collectives) is tested on host CPU
with XLA's forced device count, mirroring how the reference exercised its
multi-node protocol with multi-process-on-localhost
(reference: scripts/test_local.sh).  Must run before any jax import.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on the CPU whatever the machine offers: forced here through
# jax.config (before any backend is initialized), so a bare `pytest` on a
# TPU host does not take the chip.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=["python", "native"])
def each_codec(request):
    """Parametrize a data-plane test across the wire codec backends
    (PSDT_NATIVE=0/1 — rpc/codec.py): the ``python`` leg pins the
    pure-numpy oracle so the fallback path can never rot, the ``native``
    leg exercises the C++ kernels (skipped cleanly when no compiler can
    build them).  Yields the active backend name."""
    from parameter_server_distributed_tpu import native

    if request.param == "native":
        native.set_enabled(True)
        if native.lib() is None:
            pytest.skip("native lib unavailable (no g++)")
    else:
        native.set_enabled(False)
    try:
        yield request.param
    finally:
        # restore the process default (PSDT_NATIVE env, read at import)
        native.set_enabled(
            os.environ.get("PSDT_NATIVE", "1").lower()
            not in ("0", "false"))


@pytest.fixture(params=["0", "1"])
def each_arena(request, monkeypatch):
    """Parametrize a device-apply test across the flat-arena layout
    (PSDT_ARENA=0/1 — core/arena.py, ISSUE 15): the ``0`` leg pins the
    PR 11 per-tensor device path, the ``1`` leg runs the same closes
    through the per-stripe mega-array layout (skipped cleanly when no
    jax backend owns a device).  Yields the flag value; cores read it
    at construction, so construct the core inside the test body."""
    if request.param == "1":
        from parameter_server_distributed_tpu.core import device_apply

        if not device_apply.available():
            pytest.skip("no jax backend/device for the arena leg")
    monkeypatch.setenv("PSDT_ARENA", request.param)
    yield request.param


@pytest.fixture(autouse=True)
def _lockcheck_env(request, monkeypatch):
    """Opt-in runtime lock-discipline checking: tests marked
    ``@pytest.mark.lockcheck`` run with PSDT_LOCK_CHECK=1, so the known
    locks (core/ps_core.py, checkpoint/manager.py, server/ps_service.py,
    obs/export.py) are constructed as order-asserting proxies and any
    lock-order violation raises LockOrderError instead of deadlocking
    (analysis/lock_order.py, docs/analysis.md).  The env var is read at
    lock construction, which happens inside the test body — after this
    fixture has set it."""
    if request.node.get_closest_marker("lockcheck"):
        monkeypatch.setenv("PSDT_LOCK_CHECK", "1")


@pytest.fixture
def frame_chunks():
    """A push as the server meets it: ``frame_chunks(worker_id, iteration,
    grads, chunks)`` yields ``(buffer, chunk)`` per chunk, the chunk a
    GradientUpdate decoded from a READ-ONLY view of a buffer its reader
    will refill (the float32 wire, so its tensors are views of it)."""
    from parameter_server_distributed_tpu.rpc import messages as m

    def make(worker_id, iteration, grads, chunks=3):
        names = list(grads)
        per = -(-len(names) // chunks)
        for lo in range(0, len(names), per):
            buf = bytearray(m.GradientUpdate(
                worker_id=worker_id, iteration=iteration,
                gradients=[m.Tensor.from_array(n, grads[n])
                           for n in names[lo:lo + per]]).encode())
            yield buf, m.GradientUpdate.decode(memoryview(buf).toreadonly())

    return make
