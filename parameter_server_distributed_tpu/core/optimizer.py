"""Host-side optimizers for the parameter-server update path.

The reference applies a bare SGD step with an implicit learning rate of 1.0
inside its aggregation routine ("param -= avg_grad",
reference: src/parameter_server.cpp:77-91 with the comment "can add learning
rate here" at :87).  Here the update rule is factored out and extended with
momentum and Adam.  These run on the PS host over numpy stores — the
device-side SPMD train path uses optax under jit instead
(see parallel/train_step.py).

Each optimizer applies its update through the fused native C++ kernels
(native/psdt_native.cpp — the analogue of the reference's C++ hot loop at
src/parameter_server.cpp:40-91) when the library is available, falling back
to numpy otherwise.  Both passes are in-place: the native kernel is
single-sweep and GIL-free; the numpy path runs ``out=`` ufuncs over the
owned optimizer slots plus ONE thread-local scratch buffer reused across
tensors (:func:`_scratch_like`), so a step allocates exactly the output
array per tensor instead of one temporary per sub-op.  Outputs are always
fresh arrays — previously served parameter copies are never mutated.

Striping protocol (core/stripes.py, ISSUE 5): optimizer state is keyed
per tensor name, so an update is **name-sliceable** — the striped barrier
close calls :meth:`HostOptimizer.tick` once per logical step and then
:meth:`HostOptimizer.apply_shard` concurrently over disjoint name
subsets.  ``apply_shard`` over disjoint names is thread-safe by
construction: each tensor touches only its own slot entries (per-key dict
writes are GIL-atomic) and the scratch buffer is thread-local.
``apply()`` (tick + one whole-store shard) remains the serial entry
point, bit-for-bit unchanged.  The whole-store device-resident jit
programs (DeviceOptimizer/PallasOptimizer,
async_sgd/device_optimizer.py) are NOT name-sliceable and leave
``supports_striping`` False — the PS falls back to the serial
whole-store apply for them; the sharded device family
(ShardedDeviceOptimizer, ISSUE 11) IS name-sliceable and takes the
striped close like the host optimizers, with each stripe's update
running as jit-compiled device programs over that stripe's
device-resident partition.
"""

from __future__ import annotations

import threading
from typing import Mapping

import numpy as np

from ..native import (adam_native, adamw_native, lib as native_lib,
                      momentum_native, sgd_native)
from .tensor import TensorStore


_scratch_tls = threading.local()

# Retained-scratch ceiling: buffers up to this size are cached per thread
# and reused across tensors/steps (the common transformer-block sizes);
# anything larger gets a fresh allocation instead — an outlier tensor
# (a 500 MB embedding) must not pin outlier-sized buffers on every pool
# and handler thread for the process lifetime.
_SCRATCH_CAP_BYTES = 64 << 20


def _scratch_like(a: np.ndarray) -> np.ndarray:
    """A float32 scratch view shaped like ``a``, backed by a thread-local
    flat buffer reused across sub-ops, tensors, and steps (fresh for
    tensors above ``_SCRATCH_CAP_BYTES``).  Thread-local so
    stripe-parallel ``apply_shard`` calls never share a buffer."""
    if 4 * a.size > _SCRATCH_CAP_BYTES:
        return np.empty(a.shape, np.float32)
    buf = getattr(_scratch_tls, "buf", None)
    if buf is None or buf.size < a.size:
        buf = _scratch_tls.buf = np.empty(max(1, a.size), np.float32)
    return buf[:a.size].reshape(a.shape)


class HostOptimizer:
    """Stateful optimizer over a named-tensor store."""

    #: True when state is per-tensor-name and :meth:`apply_shard` may run
    #: concurrently over disjoint name subsets (the striped PS hot path).
    supports_striping = False

    def __init__(self, learning_rate: float = 1.0):
        self.learning_rate = learning_rate

    def tick(self) -> None:
        """Advance per-logical-step state (Adam's bias-correction step
        counter) ONCE per barrier apply.  The striped closer calls
        ``tick()`` once, then ``apply_shard()`` per stripe; calling
        :meth:`apply` does both."""

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        """Apply the update rule to a (sub)store WITHOUT advancing the
        step counter.  Same-name slot state updates in place; returned
        params are fresh arrays."""
        raise NotImplementedError

    def apply(self, params: TensorStore, grads: Mapping[str, np.ndarray]) -> TensorStore:
        self.tick()
        return self.apply_shard(params, grads)

    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, state: dict) -> None:
        pass


class SGD(HostOptimizer):
    """param -= lr * grad — the reference's rule at lr=1.0."""

    supports_striping = True

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        lr = np.float32(self.learning_rate)
        use_native = native_lib() is not None
        out: TensorStore = {}
        for name, p in params.items():
            if name not in grads:
                out[name] = np.asarray(p, np.float32)
                continue
            g = np.asarray(grads[name], np.float32)
            if use_native:
                p_new = np.array(p, np.float32)  # fresh contiguous copy
                if sgd_native(p_new, g, float(lr)):
                    out[name] = p_new
                    continue
            p = np.asarray(p, np.float32)
            scratch = _scratch_like(g)
            np.multiply(g, lr, out=scratch)
            out[name] = np.subtract(p, scratch)
        return out


class Momentum(HostOptimizer):
    supports_striping = True

    def __init__(self, learning_rate: float = 1.0, momentum: float = 0.9):
        super().__init__(learning_rate)
        self.momentum = momentum
        self.velocity: TensorStore = {}

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        lr = np.float32(self.learning_rate)
        mu = np.float32(self.momentum)
        use_native = native_lib() is not None
        out: TensorStore = {}
        for name, p in params.items():
            p = np.asarray(p, np.float32)
            if name not in grads:
                out[name] = p
                continue
            g = np.asarray(grads[name], np.float32)
            v_prev = self.velocity.get(name)
            if use_native:
                # fresh params buffer (served dicts hold references to the
                # old one); velocity updates in place — state_dict
                # deep-copies on snapshot
                p_new = np.array(p, np.float32)
                v_new = (_owned_f32(v_prev) if v_prev is not None
                         else np.zeros_like(g))
                if momentum_native(p_new, g, v_new, float(lr), float(mu)):
                    self.velocity[name] = v_new
                    out[name] = p_new
                    continue
            if v_prev is None:
                # owned copy: the slot updates in place from now on and
                # must never alias the caller's gradient array
                v = np.array(g, np.float32)
            else:
                # v = mu * v + g, in place on the owned slot
                v = _owned_f32(v_prev)
                np.multiply(v, mu, out=v)
                np.add(v, g, out=v)
            self.velocity[name] = v
            scratch = _scratch_like(v)
            np.multiply(v, lr, out=scratch)
            out[name] = np.subtract(p, scratch)  # the one fresh array
        return out

    def state_dict(self) -> dict:
        # deep copy — the apply path updates velocity in place
        return {"velocity": {k: np.array(v)
                             for k, v in self.velocity.items()}}

    def load_state_dict(self, state: dict) -> None:
        self.velocity = {k: np.array(v, np.float32)
                         for k, v in state.get("velocity", {}).items()}


def _owned_f32(a: np.ndarray) -> np.ndarray:
    """Contiguous writable float32 view of an optimizer slot, copying only
    when the stored array is not already kernel-ready (e.g. right after a
    checkpoint load of a float64 or read-only array)."""
    out = np.asarray(a, np.float32)
    if not (out.flags.c_contiguous and out.flags.writeable):
        out = np.array(out, np.float32)
    return out


class Adam(HostOptimizer):
    supports_striping = True

    def __init__(self, learning_rate: float = 1e-3, b1: float = 0.9,
                 b2: float = 0.999, eps: float = 1e-8):
        super().__init__(learning_rate)
        self.b1, self.b2, self.eps = b1, b2, eps
        self.m: TensorStore = {}
        self.v: TensorStore = {}
        self.step = 0

    def tick(self) -> None:
        self.step += 1

    def _moments(self, name: str, g: np.ndarray,
                 scratch: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """In-place EMA update of the (owned) m/v slots for one tensor:
        m = b1*m + (1-b1)*g, v = b2*v + (1-b2)*g², via out= ufuncs and the
        shared scratch — no full-size temporaries."""
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        m = _owned_f32(self.m.get(name, np.zeros_like(g)))
        v = _owned_f32(self.v.get(name, np.zeros_like(g)))
        np.multiply(g, np.float32(1.0) - b1, out=scratch)
        np.multiply(m, b1, out=m)
        np.add(m, scratch, out=m)
        np.multiply(g, g, out=scratch)
        np.multiply(scratch, np.float32(1.0) - b2, out=scratch)
        np.multiply(v, b2, out=v)
        np.add(v, scratch, out=v)
        self.m[name], self.v[name] = m, v
        return m, v

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        lr = np.float32(self.learning_rate)
        bc1 = 1.0 - self.b1 ** self.step
        bc2 = 1.0 - self.b2 ** self.step
        use_native = native_lib() is not None
        out: TensorStore = {}
        for name, p in params.items():
            p = np.asarray(p, np.float32)
            if name not in grads:
                out[name] = p
                continue
            g = np.asarray(grads[name], np.float32)
            if use_native:
                # params must NOT mutate in place (served param dicts hold
                # references — RCU-style immutability), so the new params
                # get a fresh buffer; m/v are private to the optimizer and
                # update in place (state_dict deep-copies on snapshot).
                m = _owned_f32(self.m.get(name, np.zeros_like(g)))
                v = _owned_f32(self.v.get(name, np.zeros_like(g)))
                p_new = np.array(p, np.float32)
                if adam_native(p_new, g, m, v, float(lr), self.b1,
                               self.b2, self.eps, self.step):
                    self.m[name], self.v[name] = m, v
                    out[name] = p_new
                    continue
            scratch = _scratch_like(g)
            m, v = self._moments(name, g, scratch)
            # denom = sqrt(v / bc2) + eps, staged in scratch
            np.divide(v, bc2, out=scratch)
            np.sqrt(scratch, out=scratch)
            np.add(scratch, self.eps, out=scratch)
            # p - lr * (m / bc1) / denom, staged in the fresh output —
            # lr multiplied BEFORE the denom divide, preserving the
            # pre-in-place expression's evaluation order bit for bit
            # (explicit empty_like: ufuncs on 0-d arrays without out=
            # return scalars, which cannot chain as out= targets)
            p_new = np.empty_like(p)
            np.divide(m, bc1, out=p_new)
            np.multiply(p_new, lr, out=p_new)
            np.divide(p_new, scratch, out=p_new)
            np.subtract(p, p_new, out=p_new)
            out[name] = p_new
        return out

    def state_dict(self) -> dict:
        # deep copy: the hot apply path updates m/v IN PLACE, so a
        # checkpoint snapshot must own its buffers (copy-on-snapshot is
        # per checkpoint; the old copy-on-apply cost 2 state-sized sweeps
        # on every push at 1B scale)
        return {"m": {k: np.array(v) for k, v in self.m.items()},
                "v": {k: np.array(v) for k, v in self.v.items()},
                "step": self.step}

    def load_state_dict(self, state: dict) -> None:
        # deep copy so in-place applies never mutate the caller's dict
        self.m = {k: np.array(v, np.float32)
                  for k, v in state.get("m", {}).items()}
        self.v = {k: np.array(v, np.float32)
                  for k, v in state.get("v", {}).items()}
        self.step = int(state.get("step", 0))


class AdamW(Adam):
    """Adam with decoupled weight decay on matrices only (sub-2D params —
    norm scales, biases — are excluded, matching the device-side optax
    mask in parallel/train_step.make_optimizer)."""

    def __init__(self, learning_rate: float = 1e-3,
                 weight_decay: float = 1e-4, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.weight_decay = weight_decay

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        lr = np.float32(self.learning_rate)
        bc1 = 1.0 - self.b1 ** self.step
        bc2 = 1.0 - self.b2 ** self.step
        use_native = native_lib() is not None
        out: TensorStore = {}
        for name, p in params.items():
            p = np.asarray(p, np.float32)
            if name not in grads:
                out[name] = p
                continue
            # decay from the PRE-update param, matrices only
            # (optax.adamw convention: update = adam_term + wd * p,
            # applied together; decaying norm scales/biases is a quality
            # bug — mask matches parallel/train_step.make_optimizer)
            wd = self.weight_decay if p.ndim >= 2 else 0.0
            g = np.asarray(grads[name], np.float32)
            if use_native:
                # fresh params buffer (served dicts hold references to the
                # old one); m/v update in place — see Adam.apply_shard
                m = _owned_f32(self.m.get(name, np.zeros_like(g)))
                v = _owned_f32(self.v.get(name, np.zeros_like(g)))
                p_new = np.array(p, np.float32)
                if adamw_native(p_new, g, m, v, float(lr), self.b1,
                                self.b2, self.eps, self.step, wd):
                    self.m[name], self.v[name] = m, v
                    out[name] = p_new
                    continue
            scratch = _scratch_like(g)
            m, v = self._moments(name, g, scratch)
            np.divide(v, bc2, out=scratch)
            np.sqrt(scratch, out=scratch)
            np.add(scratch, self.eps, out=scratch)
            p_new = np.empty_like(p)
            np.divide(m, bc1, out=p_new)
            np.divide(p_new, scratch, out=p_new)  # adam_term
            if wd:
                np.multiply(p, np.float32(wd), out=scratch)
                np.add(p_new, scratch, out=p_new)
            np.multiply(p_new, lr, out=p_new)
            np.subtract(p, p_new, out=p_new)
            out[name] = p_new
        return out


class Lion(HostOptimizer):
    """Sign-momentum optimizer (Chen et al. 2023): ONE slot instead of
    Adam's two — half the PS optimizer-state memory, which on the
    aggregation server is host RAM holding the full model.  Update:
    p -= lr * (sign(b1*m + (1-b1)*g) + wd*p); m <- b2*m + (1-b2)*g.
    Decoupled decay on matrices only, same mask as AdamW and the
    device-side optax menu (parallel/train_step.make_optimizer)."""

    supports_striping = True

    def __init__(self, learning_rate: float = 1e-4, b1: float = 0.9,
                 b2: float = 0.99, weight_decay: float = 1e-4):
        super().__init__(learning_rate)
        self.b1, self.b2 = b1, b2
        self.weight_decay = weight_decay
        self.m: TensorStore = {}

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        lr = np.float32(self.learning_rate)
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        out: TensorStore = {}
        for name, p in params.items():
            p = np.asarray(p, np.float32)
            if name not in grads:
                out[name] = p
                continue
            g = np.asarray(grads[name], np.float32)
            m = _owned_f32(self.m.get(name, np.zeros_like(g)))
            scratch = _scratch_like(g)
            # update = sign(b1*m + (1-b1)*g), staged in the fresh output
            # (m itself is still needed for its own EMA below)
            p_new = np.empty_like(p)
            np.multiply(m, b1, out=p_new)
            np.multiply(g, one - b1, out=scratch)
            np.add(p_new, scratch, out=p_new)
            np.sign(p_new, out=p_new)
            # m = b2*m + (1-b2)*g, in place on the owned slot
            np.multiply(m, b2, out=m)
            np.multiply(g, one - b2, out=scratch)
            np.add(m, scratch, out=m)
            self.m[name] = m
            wd = self.weight_decay if p.ndim >= 2 else 0.0
            if wd:
                np.multiply(p, np.float32(wd), out=scratch)
                np.add(p_new, scratch, out=p_new)
            np.multiply(p_new, lr, out=p_new)
            np.subtract(p, p_new, out=p_new)
            out[name] = p_new
        return out

    def state_dict(self) -> dict:
        # deep copy — the apply path updates m in place
        return {"m": {k: np.array(v) for k, v in self.m.items()}}

    def load_state_dict(self, state: dict) -> None:
        self.m = {k: np.array(v, np.float32)
                  for k, v in state.get("m", {}).items()}


def _make_accelerator_optimizer(kind: str, rule: str, learning_rate: float,
                                momentum: float,
                                weight_decay: float) -> HostOptimizer | None:
    """Construct a ``device_*`` / ``pallas_*`` / ``sharded_*`` optimizer;
    None for a rule the family does not implement (the caller raises the
    unknown-optimizer error — a config typo must not silently train with
    a different rule)."""
    from ..async_sgd.device_optimizer import (DeviceOptimizer,
                                              PallasOptimizer,
                                              ShardedDeviceOptimizer)
    if kind == "sharded":
        if rule not in ShardedDeviceOptimizer.RULES:
            return None
        return ShardedDeviceOptimizer(rule, learning_rate,
                                      momentum=momentum,
                                      weight_decay=weight_decay)
    if kind == "pallas":
        if rule not in PallasOptimizer.RULES:
            return None  # unknown-rule typo must RAISE, not degrade
        return PallasOptimizer(rule, learning_rate, momentum)
    if rule == "sgd":
        return DeviceOptimizer.sgd(learning_rate)
    if rule == "momentum":
        return DeviceOptimizer.momentum(learning_rate, momentum)
    if rule == "adamw":
        return DeviceOptimizer.adamw(learning_rate, weight_decay)
    if rule == "adamw_bf16":
        # bf16 moment slots: half the optimizer-state HBM
        return DeviceOptimizer.adamw_bf16(learning_rate, weight_decay)
    if rule == "adam":
        return DeviceOptimizer.adam(learning_rate)
    return None


def make_optimizer(name: str, learning_rate: float, momentum: float = 0.9,
                   weight_decay: float = 1e-4) -> HostOptimizer:
    """PS optimizer by name.  Plain names (`sgd|momentum|adam|adamw|lion`)
    are the host-side numpy/native-C++ optimizers above; `device_*`
    selects the accelerator-resident optax path, `pallas_*` the fused
    pallas-kernel path, and `sharded_*` the stripe-sliceable
    device-resident family (async_sgd/device_optimizer.py
    ShardedDeviceOptimizer — ``supports_striping=True``, so the striped
    barrier close runs it stripe-parallel; ISSUE 11).  With
    ``PSDT_DEVICE_APPLY=1`` a ``device_<rule>`` name the sharded family
    implements resolves to it, so existing configs pick up the
    accelerator-resident apply without renaming (flag off: exactly the
    pre-existing optax family, whole-store serial).

    A name that asks for an accelerator optimizer and cannot be built —
    no jax backend, no device, an import error, a constructor failure —
    RAISES, with the cause: the matching host optimizer is what the
    plain name is for, and a PS that quietly trains on the host under a
    device name reports numbers nobody asked for.  An unknown RULE
    raises too — a typo must never train with a different update rule."""
    name = name.lower()
    if name == "sgd":
        return SGD(learning_rate)
    if name == "momentum":
        return Momentum(learning_rate, momentum)
    if name == "adam":
        return Adam(learning_rate)
    if name == "adamw":
        return AdamW(learning_rate, weight_decay)
    if name == "lion":
        return Lion(learning_rate, weight_decay=weight_decay)
    kind, _, rule = name.partition("_")
    if rule and kind in ("device", "pallas", "sharded"):
        from . import device_apply

        try:
            if not device_apply.available():
                raise RuntimeError("the jax backend has no device")
            if kind == "device" and device_apply.enabled():
                from ..async_sgd.device_optimizer import (
                    ShardedDeviceOptimizer)
                if rule in ShardedDeviceOptimizer.RULES:
                    kind = "sharded"
            opt = _make_accelerator_optimizer(kind, rule, learning_rate,
                                              momentum, weight_decay)
        except Exception as exc:
            raise RuntimeError(
                f"optimizer {name!r} was requested but cannot be built "
                f"({type(exc).__name__}: {exc}); use {rule!r} for the "
                f"host optimizer") from exc
        if opt is not None:
            return opt
    raise ValueError(f"unknown optimizer {name!r}")
