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
to numpy otherwise.  Both passes read the old parameters and write a
SEPARATE output (previously served parameter copies are never mutated,
and nothing is copied before the sweep); slots update in place.  The
native kernel is single-sweep and GIL-free; the numpy path runs ``out=``
ufuncs over the owned optimizer slots, the output and ONE thread-local
scratch buffer reused across tensors (:func:`_scratch_like`), so a step
allocates nothing per sub-op.

Striping protocol (core/stripes.py; ISSUE 5, PR 39): every host rule is
elementwise and its state is keyed per tensor name, so an update is
sliceable by ELEMENT RANGE — the striped barrier close calls
:meth:`HostOptimizer.tick` once per logical step, :meth:`~HostOptimizer.
prepare` once (serial: the slots exist before the fan-out), and then
:meth:`HostOptimizer.update_range` concurrently over disjoint ranges of
the store.  That is thread-safe by construction: a call touches its own
range of the output and of the tensor's slots, and the scratch buffer is
thread-local.  ``apply()`` (tick + one whole-store ``apply_shard``, the
same rule over whole tensors into fresh arrays) remains the serial entry
point.  The whole-store device-resident jit
programs (DeviceOptimizer/PallasOptimizer,
async_sgd/device_optimizer.py) are NOT sliceable and leave
``supports_striping`` False — the PS falls back to the serial
whole-store apply for them; the sharded device family
(ShardedDeviceOptimizer, ISSUE 11) is sliceable by NAME and takes the
striped close with ``apply_shard`` per stripe of names, each stripe's
update running as jit-compiled device programs over that stripe's
device-resident partition.
"""

from __future__ import annotations

import threading
from typing import Mapping

import numpy as np

from ..native import adam_native, momentum_native, sgd_native
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
    concurrent ``update_range`` calls never share a buffer."""
    if 4 * a.size > _SCRATCH_CAP_BYTES:
        return np.empty(a.shape, np.float32)
    buf = getattr(_scratch_tls, "buf", None)
    if buf is None or buf.size < a.size:
        buf = _scratch_tls.buf = np.empty(max(1, a.size), np.float32)
    return buf[:a.size].reshape(a.shape)


def _owned_f32(a: np.ndarray) -> np.ndarray:
    """Contiguous writable float32 view of an optimizer slot, copying only
    when the stored array is not already kernel-ready (e.g. right after a
    checkpoint load of a float64 or read-only array)."""
    out = np.asarray(a, np.float32)
    if not (out.flags.c_contiguous and out.flags.writeable):
        out = np.array(out, np.float32)
    return out


def _flat(a: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Elements [lo, hi) of a C-contiguous array in storage order: a view."""
    return a.reshape(-1)[lo:hi]


class HostOptimizer:
    """Stateful optimizer over a named-tensor store.

    A host rule is :meth:`update_range`: elements [lo, hi) of ONE tensor,
    read from the old parameters and written to a separate output, the
    slots looked up by name and updated in place over the same range.
    Every rule is elementwise, so any cut of a tensor into ranges gives
    the whole tensor's result bit for bit: the barrier close cuts the
    store by element ranges (core/ps_core.py), and :meth:`apply_shard`
    is the same rule over whole tensors."""

    #: True when state is per-tensor-name and one logical step may run as
    #: concurrent calls over disjoint pieces of the store (the striped PS
    #: hot path): :meth:`update_range` over disjoint ranges for a host
    #: rule, :meth:`apply_shard` over disjoint names for a device one.
    supports_striping = False

    def __init__(self, learning_rate: float = 1.0):
        self.learning_rate = learning_rate

    def tick(self) -> None:
        """Advance per-logical-step state (Adam's bias-correction step
        counter) ONCE per barrier apply.  The striped closer calls
        ``tick()`` once, then fans the step out; calling :meth:`apply`
        does both."""

    def _slot_tables(self) -> tuple[TensorStore, ...]:
        """The per-name slot dicts of this rule (Adam: m and v)."""
        return ()

    def prepare(self, grads: Mapping[str, np.ndarray]) -> None:
        """Make every slot of every name in ``grads`` an owned float32
        array of the gradient's shape (zeros where the name is new).
        Serial, before :meth:`update_range` fans out: two ranges of one
        tensor must find its slots, not both create them."""
        for table in self._slot_tables():
            for name, g in grads.items():
                slot = table.get(name)
                slot = (np.zeros(np.shape(g), np.float32) if slot is None
                        else _owned_f32(slot))
                if slot.size != np.size(g):
                    raise ValueError(
                        f"optimizer slot of {name!r} has shape "
                        f"{slot.shape}, its gradient {np.shape(g)}")
                table[name] = slot

    def update_range(self, name: str, p: np.ndarray, g: np.ndarray,
                     out: np.ndarray, lo: int, hi: int) -> None:
        """The rule over elements [lo, hi) (storage order) of tensor
        ``name``: ``p`` the old parameters, ``g`` the gradient, ``out``
        the new parameters' array, all whole, float32, C-contiguous and
        of one shape; :meth:`prepare` has run.  Thread-safe over disjoint
        ranges: slots update in place over the range only, and the
        scratch buffer is thread-local."""
        raise NotImplementedError

    def apply_shard(self, params: TensorStore,
                    grads: Mapping[str, np.ndarray]) -> TensorStore:
        """Apply the update rule to a (sub)store WITHOUT advancing the
        step counter.  Same-name slot state updates in place; returned
        params are fresh arrays."""
        out, todo = split_updates(params, grads)
        self.prepare({name: g for name, _, g in todo})
        for name, p, g in todo:
            out[name] = np.empty_like(p)
            self.update_range(name, p, g, out[name], 0, p.size)
        return out

    def apply(self, params: TensorStore, grads: Mapping[str, np.ndarray]) -> TensorStore:
        self.tick()
        return self.apply_shard(params, grads)

    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, state: dict) -> None:
        pass


def split_updates(params: TensorStore, grads: Mapping[str, np.ndarray]
                  ) -> tuple[TensorStore, list[tuple]]:
    """One step's work over a host store: ``params`` as float32 arrays
    (the new store wherever a name has no gradient: it passes through),
    and a ``(name, p, g)`` for every name that has one, both C-contiguous
    float32 of the parameter's shape, which is what
    :meth:`HostOptimizer.update_range` indexes in storage order.  A
    gradient of another shape (axes of one aside: a scalar comes back
    from a checkpoint or the wire as (1,)) is an error."""
    out: TensorStore = {}
    todo = []
    for name, p in params.items():
        out[name] = p = np.asarray(p, np.float32)
        if name not in grads:
            continue
        # not ascontiguousarray: that makes a 0-d array 1-d
        p = np.asarray(p, order="C")
        g = np.asarray(grads[name], np.float32, order="C")
        if _squeezed(g.shape) != _squeezed(p.shape):
            raise ValueError(f"gradient of {name!r} has shape {g.shape}, "
                             f"the parameter {p.shape}")
        todo.append((name, p, g.reshape(p.shape)))
    return out, todo


def _squeezed(shape: tuple) -> tuple:
    return tuple(d for d in shape if d != 1)


class SGD(HostOptimizer):
    """param -= lr * grad — the reference's rule at lr=1.0."""

    supports_striping = True

    def update_range(self, name, p, g, out, lo, hi) -> None:
        p, g, out = _flat(p, lo, hi), _flat(g, lo, hi), _flat(out, lo, hi)
        lr = np.float32(self.learning_rate)
        if sgd_native(p, g, out, float(lr)):
            return
        scratch = _scratch_like(g)
        np.multiply(g, lr, out=scratch)
        np.subtract(p, scratch, out=out)


class Momentum(HostOptimizer):
    supports_striping = True

    def __init__(self, learning_rate: float = 1.0, momentum: float = 0.9):
        super().__init__(learning_rate)
        self.momentum = momentum
        self.velocity: TensorStore = {}

    def _slot_tables(self):
        return (self.velocity,)

    def prepare(self, grads) -> None:
        # a new name's velocity starts as -0.0, the additive identity:
        # mu * -0.0 + g is g bit for bit (a -0.0 stays -0.0, where a seed
        # of zeros gives +0.0), so the first step copies the gradient
        # into an owned slot by the rule of every later step
        for name, g in grads.items():
            if name not in self.velocity:
                self.velocity[name] = np.full(np.shape(g), -0.0, np.float32)
        super().prepare(grads)

    def update_range(self, name, p, g, out, lo, hi) -> None:
        p, g, out = _flat(p, lo, hi), _flat(g, lo, hi), _flat(out, lo, hi)
        v = _flat(self.velocity[name], lo, hi)
        lr = np.float32(self.learning_rate)
        mu = np.float32(self.momentum)
        if momentum_native(p, g, v, out, float(lr), float(mu)):
            return
        # v = mu * v + g, in place on the owned slot
        np.multiply(v, mu, out=v)
        np.add(v, g, out=v)
        scratch = _scratch_like(v)
        np.multiply(v, lr, out=scratch)
        np.subtract(p, scratch, out=out)

    def state_dict(self) -> dict:
        # deep copy — the apply path updates velocity in place
        return {"velocity": {k: np.array(v)
                             for k, v in self.velocity.items()}}

    def load_state_dict(self, state: dict) -> None:
        self.velocity = {k: np.array(v, np.float32)
                         for k, v in state.get("velocity", {}).items()}


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

    def _slot_tables(self):
        return (self.m, self.v)

    def _decay(self, p: np.ndarray) -> float | None:
        """Decoupled weight decay for tensor ``p`` (whole: the mask reads
        the TENSOR's rank); None is plain Adam, whose expression differs
        from AdamW's at decay 0 in its order of operations."""
        return None

    def update_range(self, name, p, g, out, lo, hi) -> None:
        wd = self._decay(p)
        p, g, out = _flat(p, lo, hi), _flat(g, lo, hi), _flat(out, lo, hi)
        m, v = _flat(self.m[name], lo, hi), _flat(self.v[name], lo, hi)
        lr = np.float32(self.learning_rate)
        # params never mutate in place (served param dicts hold
        # references — RCU-style immutability); m/v are private to the
        # optimizer and update in place (state_dict deep-copies).
        if adam_native(p, g, m, v, out, float(lr), self.b1, self.b2,
                       self.eps, self.step, wd):
            return
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        bc1 = 1.0 - self.b1 ** self.step
        bc2 = 1.0 - self.b2 ** self.step
        scratch = _scratch_like(g)
        # m = b1*m + (1-b1)*g, v = b2*v + (1-b2)*g², via out= ufuncs and
        # the shared scratch — no full-size temporaries
        np.multiply(g, np.float32(1.0) - b1, out=scratch)
        np.multiply(m, b1, out=m)
        np.add(m, scratch, out=m)
        np.multiply(g, g, out=scratch)
        np.multiply(scratch, np.float32(1.0) - b2, out=scratch)
        np.multiply(v, b2, out=v)
        np.add(v, scratch, out=v)
        # denom = sqrt(v / bc2) + eps, staged in scratch
        np.divide(v, bc2, out=scratch)
        np.sqrt(scratch, out=scratch)
        np.add(scratch, self.eps, out=scratch)
        np.divide(m, bc1, out=out)
        if wd is None:
            # p - lr * (m / bc1) / denom — lr multiplied BEFORE the
            # denom divide, preserving the pre-in-place expression's
            # evaluation order bit for bit
            np.multiply(out, lr, out=out)
            np.divide(out, scratch, out=out)
        else:
            # optax.adamw convention: update = adam_term + wd * p,
            # applied together from the PRE-update param
            np.divide(out, scratch, out=out)
            if wd:
                np.multiply(p, np.float32(wd), out=scratch)
                np.add(out, scratch, out=out)
            np.multiply(out, lr, out=out)
        np.subtract(p, out, out=out)

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
    mask in parallel/train_step.make_optimizer; decaying them is a
    quality bug)."""

    def __init__(self, learning_rate: float = 1e-3,
                 weight_decay: float = 1e-4, **kwargs):
        super().__init__(learning_rate, **kwargs)
        self.weight_decay = weight_decay

    def _decay(self, p: np.ndarray) -> float:
        return self.weight_decay if p.ndim >= 2 else 0.0


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

    def _slot_tables(self):
        return (self.m,)

    def update_range(self, name, p, g, out, lo, hi) -> None:
        wd = self.weight_decay if p.ndim >= 2 else 0.0
        p, g, out = _flat(p, lo, hi), _flat(g, lo, hi), _flat(out, lo, hi)
        m = _flat(self.m[name], lo, hi)
        lr = np.float32(self.learning_rate)
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        scratch = _scratch_like(g)
        # update = sign(b1*m + (1-b1)*g), staged in the output (m itself
        # is still needed for its own EMA below)
        np.multiply(m, b1, out=out)
        np.multiply(g, one - b1, out=scratch)
        np.add(out, scratch, out=out)
        np.sign(out, out=out)
        # m = b2*m + (1-b2)*g, in place on the owned slot
        np.multiply(m, b2, out=m)
        np.multiply(g, one - b2, out=scratch)
        np.add(m, scratch, out=m)
        if wd:
            np.multiply(p, np.float32(wd), out=scratch)
            np.add(out, scratch, out=out)
        np.multiply(out, lr, out=out)
        np.subtract(p, out, out=out)

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
