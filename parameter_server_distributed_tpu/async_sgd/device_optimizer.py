"""Device-resident optimizers for the async parameter server.

In bounded-staleness mode updates apply on arrival (no barrier), so the
apply path is the PS hot loop.  The host optimizers in core/optimizer.py
walk numpy arrays on the CPU — fine for MNIST, not for a 1B-param store.
These optimizers keep parameters and slots as jax Arrays on the accelerator
and apply updates under jit, donating the optimizer slot buffers.  Params
are deliberately NOT donated: ps_core keeps serving previously-returned
param dicts concurrently and those may alias the apply inputs, so each
apply transiently holds old+new param buffers (~2x the store) before the
old copy is released.

Two apply backends (which one wins is not measured on the chip):

- :class:`DeviceOptimizer` — optax transformation under jit (XLA fuses it).
- :class:`PallasOptimizer` — the hand-fused pallas kernels from
  ops/pallas/fused_update.py (one VMEM-tiled pass per tensor).

Both drop into `ParameterServerCore(optimizer=...)` unchanged — they satisfy
the HostOptimizer protocol (apply/state_dict/load_state_dict) and are
selected by name through `core.optimizer.make_optimizer`
(``device_*`` / ``pallas_*``).

They are equally valid on the SYNCHRONOUS barrier path (opt in with
``--optimizer pallas_sgd`` etc. on the PS): the streaming close hands the
contributor mean to ``apply`` exactly as it would a host optimizer, and
the whole-store jit program runs the update on the accelerator.  Both
keep ``supports_striping = False`` — a jit-compiled whole-store program
is not name-sliceable, and splitting it into S programs would recompile
per stripe and serialize on the device queue anyway, so the striped
barrier close (core/ps_core.py, PSDT_STRIPES) deliberately falls back to
this serial whole-store apply for them.  The accelerator IS the
parallelism in that configuration.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..analysis.lock_order import checked_lock
from ..core import device_apply
from ..core.optimizer import HostOptimizer


def _stochastic_round_bf16(x: jax.Array, key: jax.Array) -> jax.Array:
    """Unbiased f32 -> bf16 rounding: add uniform noise to the 16 bits
    being dropped, then truncate.  E[result] == x, so a narrow EMA keeps
    tracking even when its per-step change is below the bf16 half-ulp —
    deterministic round-to-nearest would freeze it there (an EMA with
    decay 0.999 moves ~0.1%/step; bf16's half-ulp is ~0.2%)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, x.shape, jnp.uint16).astype(jnp.uint32)
    # carry from the low 16 bits rounds up to the next representable bf16
    # with probability = dropped-fraction; NaN/inf inputs don't occur here
    # (moments are finite EMAs of finite gradients)
    rounded = ((bits + noise) >> 16).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(rounded, jnp.bfloat16)


def _adam_with_bf16_slots(b1: float, b2: float,
                          eps: float) -> optax.GradientTransformation:
    """scale_by_adam with BOTH moment slots stored in bfloat16 (half the
    optimizer-state HBM: 8 GB -> 4 GB for a 1B-param store).

    All arithmetic runs in f32 — only the carried state is narrowed, and
    the narrowing uses STOCHASTIC rounding (:func:`_stochastic_round_bf16`)
    so the EMAs stay unbiased: with round-to-nearest, b2=0.999's ~0.1%
    per-step change is below bf16's ~0.2% half-ulp and the second moment
    would freeze at a stale value the moment gradients shrink (exactly why
    optax's own ``mu_dtype`` narrows only the FIRST moment).  The PRNG key
    rides in the optimizer state."""

    def init_fn(params):
        zeros = lambda p: jnp.zeros(jnp.shape(p), jnp.bfloat16)  # noqa: E731
        # old-style uint32 key: the checkpoint sidecar snapshots state
        # leaves via np.asarray, which typed key arrays reject
        return {"count": jnp.zeros((), jnp.int32),
                "key": jax.random.PRNGKey(0),
                "mu": jax.tree.map(zeros, params),
                "nu": jax.tree.map(zeros, params)}

    def update_fn(updates, state, params=None):
        del params
        count = state["count"] + 1
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        mu = jax.tree.map(lambda m, g: b1 * f32(m) + (1 - b1) * f32(g),
                          state["mu"], updates)
        nu = jax.tree.map(
            lambda v, g: b2 * f32(v) + (1 - b2) * jnp.square(f32(g)),
            state["nu"], updates)
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)
        out = jax.tree.map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps), mu, nu)
        key, sub = jax.random.split(state["key"])
        leaves, treedef = jax.tree.flatten({"mu": mu, "nu": nu})
        narrowed = jax.tree.unflatten(treedef, [
            _stochastic_round_bf16(leaf, k)
            for leaf, k in zip(leaves,
                               jax.random.split(sub, len(leaves)))])
        return out, {"count": count, "key": key,
                     "mu": narrowed["mu"], "nu": narrowed["nu"]}

    return optax.GradientTransformation(init_fn, update_fn)


class DeviceOptimizer(HostOptimizer):
    # whole-store jit program — not name-sliceable (see module docstring)
    supports_striping = False

    def __init__(self, transformation: optax.GradientTransformation,
                 learning_rate: float = 0.0):
        super().__init__(learning_rate)
        self._tx = transformation
        self._opt_state = None

        def apply(params, grads, opt_state):
            updates, new_opt = self._tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        # Donate the opt state (private to this object) but NOT params:
        # ps_core keeps serving previously-returned param dicts concurrently,
        # and under async pushes those alias the apply inputs — donating
        # them would invalidate in-flight pull snapshots.
        self._apply = jax.jit(apply, donate_argnums=(2,))

    @classmethod
    def sgd(cls, learning_rate: float = 1.0) -> "DeviceOptimizer":
        return cls(optax.sgd(learning_rate), learning_rate)

    @classmethod
    def momentum(cls, learning_rate: float = 1.0,
                 momentum: float = 0.9) -> "DeviceOptimizer":
        return cls(optax.sgd(learning_rate, momentum=momentum), learning_rate)

    @classmethod
    def adam(cls, learning_rate: float = 1e-3) -> "DeviceOptimizer":
        return cls(optax.adam(learning_rate), learning_rate)

    @classmethod
    def adamw(cls, learning_rate: float = 1e-3,
              weight_decay: float = 1e-4) -> "DeviceOptimizer":
        # matrices-only decay mask, matching parallel/train_step and the
        # host AdamW (decaying norm scales/biases is a quality bug)
        return cls(optax.adamw(
            learning_rate, weight_decay=weight_decay,
            mask=lambda params: jax.tree.map(
                lambda p: p.ndim >= 2, params)), learning_rate)

    @classmethod
    def adamw_bf16(cls, learning_rate: float = 1e-3,
                   weight_decay: float = 1e-4) -> "DeviceOptimizer":
        """AdamW with both moment slots carried in bfloat16 (stochastic
        rounding keeps the EMAs unbiased) — half the optimizer-state HBM
        of :meth:`adamw`; same matrices-only decoupled decay."""
        return cls(optax.chain(
            _adam_with_bf16_slots(0.9, 0.999, 1e-8),
            optax.add_decayed_weights(
                weight_decay, mask=lambda params: jax.tree.map(
                    lambda p: p.ndim >= 2, params)),
            optax.scale(-learning_rate)), learning_rate)

    def apply(self, params: Mapping[str, np.ndarray],
              grads: Mapping[str, np.ndarray]) -> dict:
        device_params = {k: jnp.asarray(v) for k, v in params.items()}
        device_grads = {k: jnp.asarray(np.asarray(grads[k], np.float32))
                        if k in grads else jnp.zeros_like(device_params[k])
                        for k in device_params}
        if self._opt_state is None:
            self._opt_state = self._tx.init(device_params)
        new_params, self._opt_state = self._apply(device_params, device_grads,
                                                  self._opt_state)
        return new_params

    def state_dict(self) -> dict:
        """Checkpoint-codec-friendly: a single uint8 'pickle' entry holding
        (leaves-as-numpy, treedef) so the optimizer sidecar (an npz) can
        store it without knowing optax's pytree structure."""
        import pickle

        if self._opt_state is None:
            return {}
        leaves, treedef = jax.tree.flatten(self._opt_state)
        blob = pickle.dumps(([np.asarray(leaf) for leaf in leaves], treedef))
        return {"pickle": np.frombuffer(blob, dtype=np.uint8)}

    def load_state_dict(self, state: dict) -> None:
        import pickle

        if not state or "pickle" not in state:
            self._opt_state = None
            return
        leaves, treedef = pickle.loads(np.asarray(state["pickle"],
                                                  np.uint8).tobytes())
        self._opt_state = jax.tree.unflatten(
            treedef, [jnp.asarray(leaf) for leaf in leaves])


class PallasOptimizer(HostOptimizer):
    """Device-resident PS optimizer whose apply path is the fused pallas
    update kernels (ops/pallas/fused_update.py) instead of an optax chain.
    One jit-compiled, buffer-donating program per rule; Adam's per-step bias
    corrections ride in as data (SMEM scalars), so stepping never
    recompiles."""

    # whole-store jit program — not name-sliceable (see module docstring)
    supports_striping = False

    RULES = ("sgd", "momentum", "adam")

    def __init__(self, rule: str = "sgd", learning_rate: float = 1.0,
                 momentum: float = 0.9, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8):
        super().__init__(learning_rate)
        if rule not in self.RULES:
            raise ValueError(f"unknown pallas rule {rule!r}; options {self.RULES}")
        self.rule = rule
        self.momentum = momentum
        self.b1, self.b2, self.eps = b1, b2, eps
        self._slots: dict[str, jax.Array] = {}   # vel/<n>, m/<n>, v/<n>
        self.step = 0
        from ..ops.pallas import fused_update as fu

        # Donate slot buffers (private to this object) but NOT params — see
        # DeviceOptimizer: served param snapshots may alias apply inputs.
        if rule == "sgd":
            def apply_fn(params, grads):
                return fu.fused_sgd(params, grads, lr=learning_rate), {}
            donate = ()
        elif rule == "momentum":
            def apply_fn(params, grads, velocity):
                new_p, new_v = fu.fused_momentum(
                    params, grads, velocity, lr=learning_rate, mu=momentum)
                return new_p, {"vel": new_v}
            donate = (2,)
        else:
            def apply_fn(params, grads, m, v, step):
                new_p, new_m, new_v = fu.fused_adam(
                    params, grads, m, v, step, lr=learning_rate, b1=b1,
                    b2=b2, eps=eps)
                return new_p, {"m": new_m, "v": new_v}
            donate = (2, 3)
        self._apply = jax.jit(apply_fn, donate_argnums=donate)

    def apply(self, params: Mapping[str, np.ndarray],
              grads: Mapping[str, np.ndarray]) -> dict:
        device_params = {k: jnp.asarray(v) for k, v in params.items()}
        device_grads = {k: jnp.asarray(np.asarray(v, np.float32))
                        for k, v in grads.items() if k in device_params}
        self.step += 1
        if self.rule == "sgd":
            new_params, _ = self._apply(device_params, device_grads)
        elif self.rule == "momentum":
            vel = {k: self._slots.get(f"vel/{k}")
                   if f"vel/{k}" in self._slots
                   else jnp.zeros(np.shape(p), jnp.float32)
                   for k, p in device_params.items()}
            new_params, slots = self._apply(device_params, device_grads, vel)
            self._slots = {f"vel/{k}": v for k, v in slots["vel"].items()}
        else:
            # independent zero buffers per slot — both m and v are donated,
            # so they must never alias
            m = {k: self._slots.get(f"m/{k}")
                 if f"m/{k}" in self._slots
                 else jnp.zeros(np.shape(p), jnp.float32)
                 for k, p in device_params.items()}
            v = {k: self._slots.get(f"v/{k}")
                 if f"v/{k}" in self._slots
                 else jnp.zeros(np.shape(p), jnp.float32)
                 for k, p in device_params.items()}
            new_params, slots = self._apply(device_params, device_grads, m, v,
                                            jnp.int32(self.step))
            self._slots = {
                **{f"m/{k}": x for k, x in slots["m"].items()},
                **{f"v/{k}": x for k, x in slots["v"].items()},
            }
        return new_params

    def state_dict(self) -> dict:
        out = {k: np.asarray(v) for k, v in self._slots.items()
               if v is not None}
        if self.step:
            out["step"] = np.asarray([self.step], np.int64)
        return out

    def load_state_dict(self, state: dict) -> None:
        state = dict(state or {})
        step = state.pop("step", None)
        self.step = int(np.asarray(step)[0]) if step is not None else 0
        self._slots = {k: jnp.asarray(np.asarray(v, np.float32))
                       for k, v in state.items()}


# --------------------------------------------------------------------------
# ISSUE 11: the accelerator-resident SHARDED apply family.  Unlike the
# whole-store optax/pallas programs above, these are name-sliceable
# (supports_striping = True): slot state is keyed per tensor name exactly
# like the host optimizers', so the striped barrier close runs
# apply_shard concurrently over disjoint name subsets, each tensor's
# update executing as a short chain of jit-compiled FUSED device stages
# (core/device_apply.py).  Each stage obeys the no-product-into-add rule
# that makes it bit-identical to the numpy oracle while sweeping memory
# once instead of once per ufunc — see that module's docstring for the
# XLA:CPU FMA-contraction story.  Retired slot buffers and intermediates
# are DONATED through the stage chain; parameters and gradients never
# are — ps_core keeps serving previously-returned param dicts (and the
# PR-10 delta sink reads old stores), so old param buffers must stay
# valid.
# --------------------------------------------------------------------------


class ShardedDeviceOptimizer(HostOptimizer):
    """Device-resident, stripe-sliceable PS optimizer (ISSUE 11).

    Update rules mirror core/optimizer.py's numpy sequences rounding for
    rounding (same f32 scalars, same operation order), so a device apply
    is bit-identical to the host apply at f32 — the oracle tests pin it.
    State layout matches the host optimizers' ``state_dict`` exactly
    (``velocity`` / ``m``+``v``+``step`` / ``m``), so checkpoints
    round-trip between host and device optimizers through the existing
    .ckpt sidecar layout bit-identically, across restore stripe counts
    (per-name slots make the state stripe-count independent by
    construction).

    Thread-safety matches the host optimizers: ``apply_shard`` over
    disjoint name subsets is safe by construction (each tensor touches
    only its own slot entries; per-key dict writes are GIL-atomic), the
    caller serializes logical steps, and ``_lock`` only fences the
    checkpoint snapshot/restore paths, whose D2H slot readback may block
    under it (analysis/lock_order.py: rank 45, BLOCKING_ALLOWED)."""

    supports_striping = True
    device_resident = True
    # flat-arena apply (core/arena.py, ISSUE 15): the five rules also run
    # as ONE fused kernel per stage per stripe over per-stripe mega-array
    # slabs when the core arms PSDT_ARENA — see apply_arena below
    supports_arena = True

    RULES = ("sgd", "momentum", "adam", "adamw", "lion")
    _RULE_SLOTS = {"sgd": (), "momentum": ("velocity",),
                   "adam": ("m", "v"), "adamw": ("m", "v"), "lion": ("m",)}

    def __init__(self, rule: str, learning_rate: float,
                 momentum: float = 0.9, weight_decay: float = 1e-4,
                 b1: float | None = None, b2: float | None = None,
                 eps: float = 1e-8):
        if rule not in self.RULES:
            raise ValueError(
                f"unknown sharded device rule {rule!r}; options {self.RULES}")
        super().__init__(learning_rate)
        self.rule = rule
        self.momentum = momentum
        self.weight_decay = weight_decay
        self.b1 = 0.9 if b1 is None else b1
        self.b2 = ((0.99 if rule == "lion" else 0.999) if b2 is None
                   else b2)
        self.eps = eps
        self.step = 0
        # slot: name -> device f32 array, per slot kind — the same
        # per-name keying as the host optimizers (stripe-sliceable)
        self._slots: dict[str, dict] = {
            s: {} for s in self._RULE_SLOTS[rule]}
        # retained per-tensor scratch for short-lived update
        # intermediates (kind -> name -> device array): recycled through
        # kernel donation every close (core/device_apply.py "scratch
        # recycling"), the device analogue of the host optimizers'
        # thread-local scratch.  NOT optimizer state — never
        # checkpointed; holds garbage values between closes by design.
        # Space cost: up to 3 extra store-sized buffers for adam/adamw,
        # 3 for lion, 0 for sgd/momentum — the same space-for-page-fault
        # trade the host scratch makes.
        self._scr: dict[str, dict] = {}
        self._bc_step = -1
        self._bc1 = np.float32(1.0)
        self._bc2 = np.float32(1.0)
        # flat-arena slot state (core/arena.py, ISSUE 15): when the core
        # runs the arena close, each slot kind lives as ONE flat device
        # slab per stripe instead of the per-name tables above —
        # `_arena_slots[kind][stripe]`, packed for `_arena_table`'s
        # epoch.  The per-name `_slots` tables then hold STALE entries;
        # every per-tensor consumer (apply_shard fallback closes,
        # checkpoint snapshots) goes through _spill_arena_locked /
        # _arena_state_dict first, so the slabs are always the single
        # source of truth while they exist.
        self._arena_slots: dict[str, dict[int, object]] = {}
        self._arena_table = None
        self._arena_scr: dict[tuple, object] = {}  # (kind, stripe) slabs
        # fences checkpoint snapshot/restore of the slot tables; the D2H
        # slot readback runs under it (rank 45, BLOCKING_ALLOWED —
        # analysis/lock_order.py).  The apply path does NOT take it:
        # stripe applies are disjoint by name and serialized against
        # state_dict by the core's _apply_lock, like the host optimizers.
        self._lock = checked_lock("ShardedDeviceOptimizer._lock")

    # ------------------------------------------------------------- steps
    def tick(self) -> None:
        if self.rule in ("adam", "adamw"):
            self.step += 1

    def _bias_corrections(self) -> tuple[np.float32, np.float32]:
        if self._bc_step != self.step:
            # python-float powers then ONE f32 round — exactly the numpy
            # path's cast-on-use of `1.0 - b1 ** step`.  Benign if two
            # stripes race here: both write identical values.
            self._bc1 = np.float32(1.0 - self.b1 ** self.step)
            self._bc2 = np.float32(1.0 - self.b2 ** self.step)
            self._bc_step = self.step
        return self._bc1, self._bc2

    # ------------------------------------------------------------- apply
    def apply_shard(self, params, grads) -> dict:
        """One shard's update as BATCHED per-stripe device programs: the
        shard's tensors run through each update stage as ONE jit
        dispatch over the tensor list (lists are pytrees, so programs
        are shape-bucketed by the shard's shape-signature — a fixed set
        per stripe config), with per-tensor arithmetic identical to the
        host optimizers' ufunc sequences."""
        if self._arena_slots:
            # a per-tensor apply while arena slot slabs are live (a
            # fallback close, a mode flip): the slabs are the source of
            # truth — spill them back into the per-name tables first
            with self._lock:
                self._spill_arena_locked()
        out: dict = {}
        todo: list[str] = []
        for name, p in params.items():
            if name not in grads:
                # pass-through, like the host optimizers' np.asarray —
                # a device-resident value stays device-resident
                out[name] = (p if device_apply.is_device_array(p)
                             else np.asarray(p, np.float32))
            else:
                todo.append(name)
        if todo:
            # deterministic order => one program signature per shard
            todo.sort()
            ps = [device_apply.owned_f32(params[n]) for n in todo]
            gs = [device_apply.owned_f32(grads[n]) for n in todo]
            # validate slot shapes BEFORE any stage runs: the batched
            # kernels DONATE slot buffers, so a shape mismatch surfacing
            # at trace time after a donation would leave self._slots
            # holding deleted arrays (every later step bricked) — and a
            # broadcast-compatible mismatch would not surface at all.
            # Raising here mirrors the host optimizers: error out with
            # the slot tables untouched and the apply retryable.
            for name, p, g in zip(todo, ps, gs):
                if p.shape != g.shape:
                    raise ValueError(
                        f"param/gradient shape mismatch for {name!r}: "
                        f"{p.shape} vs {g.shape}")
            for slot, table in self._slots.items():
                for name, g in zip(todo, gs):
                    s = table.get(name)
                    if s is not None and s.shape != g.shape:
                        raise ValueError(
                            f"slot {slot!r} shape mismatch for {name!r}: "
                            f"{s.shape} vs gradient {g.shape}")
            for name, newp in zip(todo, self._apply_batch(todo, ps, gs)):
                out[name] = newp
        return out

    def _scratch_list(self, kind: str, names, gs) -> list:
        """The retained scratch buffers for (kind, each name) — a
        one-time zeros seed on first touch / shape change (elastic
        reshard).  Callers stash the stage outputs back via
        :meth:`_stash` so the buffers recycle through donation."""
        table = self._scr.setdefault(kind, {})
        out = []
        for name, g in zip(names, gs):
            s = table.get(name)
            if s is None or s.shape != g.shape:
                s = _zeros_f32(g.shape)
            out.append(s)
        return out

    def _stash(self, kind: str, names, arrs) -> None:
        table = self._scr[kind]
        for name, arr in zip(names, arrs):
            table[name] = arr

    def _apply_batch(self, names: list[str], ps: list, gs: list) -> list:
        k = device_apply.k
        false = np.bool_(False)  # runtime pred: XLA cannot fold the select
        lr = np.float32(self.learning_rate)
        if self.rule == "sgd":
            # us = g*lr are the close's fresh buffers; b_psub donates
            # them and their buffers leave as the new params
            return k("b_psub")(ps, k("b_mul")(gs, lr))
        if self.rule == "momentum":
            return self._momentum_batch(names, ps, gs, lr)
        if self.rule == "lion":
            return self._lion_batch(names, ps, gs, lr, false)
        return self._adam_batch(names, ps, gs, lr, false)

    def _momentum_batch(self, names, ps, gs, lr) -> list:
        k = device_apply.k
        slots = self._slots["velocity"]
        out: list = [None] * len(names)
        seed = [i for i, n in enumerate(names) if n not in slots]
        upd = [i for i, n in enumerate(names) if n in slots]
        if seed:
            # first touch: v = g (a bit-copy, the numpy `np.array(g)`
            # seed — a FRESH buffer, because the slot is donated on the
            # next step), step = v * lr (not donated: v2 is the slot)
            v2s = [device_apply.owned_copy(gs[i]) for i in seed]
            news = k("b_psub")([ps[i] for i in seed],
                               k("b_mul")(v2s, lr))
            for j, i in enumerate(seed):
                slots[names[i]] = v2s[j]
                out[i] = news[j]
        if upd:
            # v2 = mu*v + g and step = v2*lr in two fused stages; the
            # old slot buffers are donated into the products
            ts = k("b_mul_d0")([slots[names[i]] for i in upd],
                               np.float32(self.momentum))
            v2s, steps = k("b_mom_pair")(ts, [gs[i] for i in upd], lr)
            news = k("b_psub")([ps[i] for i in upd], steps)
            for j, i in enumerate(upd):
                slots[names[i]] = v2s[j]
                out[i] = news[j]
        return out

    def _lion_batch(self, names, ps, gs, lr, false) -> list:
        k = device_apply.k
        b1 = np.float32(self.b1)
        b2 = np.float32(self.b2)
        one = np.float32(1.0)
        slots = self._slots["m"]
        ms = [slots.get(n) for n in names]
        ms = [m if m is not None else _zeros_f32(g.shape)
              for m, g in zip(ms, gs)]
        t1s, t2s, t3s, t4s = k("b_lion_mul4")(
            ms, gs, b1, one - b1, b2, one - b2,
            self._scratch_list("t2", names, gs),
            self._scratch_list("t4", names, gs), false)
        self._stash("t2", names, t2s)
        self._stash("t4", names, t4s)
        us = k("b_sign_add")(t1s, t2s)
        for name, m2 in zip(names, k("b_add_d0")(t3s, t4s)):
            slots[name] = m2
        # decoupled decay on matrices only (the host mask): split the
        # shard into the decayed and plain lanes, each one batch
        wd = np.float32(self.weight_decay)
        dec = [i for i, p in enumerate(ps)
               if self.weight_decay and getattr(p, "ndim", 0) >= 2]
        plain = [i for i in range(len(ps)) if i not in dec]
        if dec:
            dnames = [names[i] for i in dec]
            dgs = [gs[i] for i in dec]
            ts = k("b_wd_mul")([ps[i] for i in dec], wd,
                               self._scratch_list("wd", dnames, dgs),
                               false)
            self._stash("wd", dnames, ts)
            for j, u in zip(dec, k("b_addmul")([us[i] for i in dec],
                                               ts, lr)):
                us[j] = u
        if plain:
            for j, u in zip(plain,
                            k("b_mul_d0")([us[i] for i in plain], lr)):
                us[j] = u
        return k("b_psub")(ps, us)

    def _adam_batch(self, names, ps, gs, lr, false) -> list:
        k = device_apply.k
        b1 = np.float32(self.b1)
        b2 = np.float32(self.b2)
        one = np.float32(1.0)
        ms_t, vs_t = self._slots["m"], self._slots["v"]
        ms = [ms_t.get(n) for n in names]
        ms = [m if m is not None else _zeros_f32(g.shape)
              for m, g in zip(ms, gs)]
        vs = [vs_t.get(n) for n in names]
        vs = [v if v is not None else _zeros_f32(g.shape)
              for v, g in zip(vs, gs)]
        t1s, t2s, t3s, t4s = k("b_adam_mul4")(
            ms, vs, gs, b1, one - b1, b2, one - b2,
            self._scratch_list("t2", names, gs),
            self._scratch_list("t4", names, gs), false)
        self._stash("t2", names, t2s)
        self._stash("t4", names, t4s)
        m2s, v2s = k("b_add2")(t1s, t2s, t3s, t4s)
        for name, m2, v2 in zip(names, m2s, v2s):
            ms_t[name], vs_t[name] = m2, v2
        bc1, bc2 = self._bias_corrections()
        eps = np.float32(self.eps)
        if self.rule == "adam":
            # single-sweep tail (see b_adam_fin1): no den/mh
            # materialization, the output is the fresh params buffer
            return k("b_adam_fin1")(ps, m2s, v2s, bc1, bc2, eps, lr)
        # adamw: decoupled decay from the PRE-update param, matrices
        # only (the host mask), lr LAST
        dens, mhs = k("b_adamw_den_mh")(
            v2s, bc2, eps, m2s, bc1,
            self._scratch_list("den", names, gs), false)
        self._stash("den", names, dens)
        us: list = [None] * len(names)
        dec = [i for i, p in enumerate(ps)
               if self.weight_decay and getattr(p, "ndim", 0) >= 2]
        plain = [i for i in range(len(ps)) if i not in dec]
        if dec:
            dnames = [names[i] for i in dec]
            dgs = [gs[i] for i in dec]
            ts = k("b_wd_mul")([ps[i] for i in dec],
                               np.float32(self.weight_decay),
                               self._scratch_list("wd", dnames, dgs),
                               false)
            self._stash("wd", dnames, ts)
            for j, u in zip(dec, k("b_adamw_fin_wd")(
                    [mhs[i] for i in dec], [dens[i] for i in dec],
                    ts, lr)):
                us[j] = u
        if plain:
            for j, u in zip(plain, k("b_adamw_fin")(
                    [mhs[i] for i in plain],
                    [dens[i] for i in plain], lr)):
                us[j] = u
        return k("b_psub")(ps, us)

    # ------------------------------------------------------------ arena
    # Flat-arena apply (core/arena.py, ISSUE 15): the same five update
    # rules over per-stripe mega-array slabs — one fused kernel per
    # STAGE per STRIPE regardless of tensor count, reusing the batched
    # stage kernels above with single-slab operand lists (plus the
    # masked a_* tails for the AdamW/Lion decay lanes).  Per-element
    # arithmetic is untouched, so the numpy oracle holds bit for bit.

    def arena_ready(self, table) -> bool:
        """True when this optimizer can run ``table`` flat.  Only
        Momentum can refuse: its first-touch slot seed is a BIT COPY of
        the gradient (not ``mu*0 + g`` — that flips -0.0), so a MIXED
        velocity table (some names seeded, some not — reshard merges)
        cannot flatten and takes the per-tensor close instead.  Slabs
        short-circuit the check only at the SAME table epoch: slabs
        packed for an older layout (the store grew) spill back to
        per-name first, so the new name's missing velocity is seen —
        repacking it as zeros would break the copy-seed contract."""
        if self.rule != "momentum":
            return True
        if self._arena_slots:
            if (self._arena_table is not None
                    and self._arena_table.epoch == table.epoch):
                return True
            with self._lock:
                self._spill_arena_locked()
        have = set(self._slots["velocity"]) & set(table.entries)
        return not have or have == set(table.entries)

    def apply_arena(self, table, param_slabs: Mapping[int, object],
                    grad_slabs: Mapping[int, object]) -> dict:
        """One logical step over flat slabs: per stripe, the rule's
        stage chain as fused kernels over the whole slab.  Slot slabs
        update in place (donated through the chain exactly like the
        per-tensor slot buffers); param and gradient slabs are never
        donated (serves alias old stores, failed applies put sums
        back).  Returns the fresh param slabs.  Caller serializes
        logical steps (the core's _apply_lock) and has proven full
        gradient coverage and :meth:`arena_ready`."""
        from ..core.stripes import run_striped

        self._ensure_arena_slots(table)
        lr = np.float32(self.learning_rate)
        false = np.bool_(False)
        stripes = sorted(param_slabs)
        if len(stripes) <= 1:
            return {s: self._arena_stripe(table, s, param_slabs[s],
                                          grad_slabs[s], lr, false)
                    for s in stripes}
        # fan the per-stripe chains across the stripe executor: each
        # chain is a handful of dispatches over disjoint slabs (disjoint
        # slot/scratch keys, GIL-atomic dict writes), so concurrent
        # dispatch costs nothing when XLA parallelizes internally and
        # recovers the multi-core sweeps when the runtime executes a
        # call synchronously (the default thunk runtime)
        results = run_striped([
            (lambda s=s: (s, self._arena_stripe(
                table, s, param_slabs[s], grad_slabs[s], lr, false)))
            for s in stripes])
        return dict(results)

    def _arena_stripe(self, table, stripe, p, g, lr, false):
        chunk = device_apply.stage_chunk_elems()
        if chunk > 0:
            size = int(table.stripe_sizes[stripe])
            if size > chunk:
                return self._arena_stripe_chunked(table, stripe, p, g, lr,
                                                  false, chunk, size)
        k = device_apply.k
        if self.rule == "sgd":
            return k("b_psub")([p], k("b_mul")([g], lr))[0]
        if self.rule == "momentum":
            slots = self._arena_slots["velocity"]
            v = slots.get(stripe)
            if v is None:
                # unseeded stripe: the host's copy-seed, flat — a bit
                # copy into a FRESH buffer (the sums slab must survive
                # for put-back; the slot is donated next step)
                v2 = k("a_copy")(g, false)
                slots[stripe] = v2
                return k("b_psub")([p], k("b_mul")([v2], lr))[0]
            ts = k("b_mul_d0")([v], np.float32(self.momentum))
            v2s, steps = k("b_mom_pair")(ts, [g], lr)
            slots[stripe] = v2s[0]
            return k("b_psub")([p], steps)[0]
        if self.rule == "lion":
            return self._arena_lion(table, stripe, p, g, lr, false)
        return self._arena_adam(table, stripe, p, g, lr, false)

    def _arena_scratch(self, kind: str, stripe: int, g):
        s = self._arena_scr.get((kind, stripe))
        if s is None or s.shape != g.shape:
            s = _zeros_f32(g.shape)
        return s

    def _arena_adam(self, table, stripe, p, g, lr, false):
        k = device_apply.k
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        ms, vs = self._arena_slots["m"], self._arena_slots["v"]
        m = ms.get(stripe)
        v = vs.get(stripe)
        if m is None:
            m = _zeros_f32(g.shape)   # the host zeros-seed, flat
        if v is None:
            v = _zeros_f32(g.shape)
        t1s, t2s, t3s, t4s = k("b_adam_mul4")(
            [m], [v], [g], b1, one - b1, b2, one - b2,
            [self._arena_scratch("t2", stripe, g)],
            [self._arena_scratch("t4", stripe, g)], false)
        self._arena_scr[("t2", stripe)] = t2s[0]
        self._arena_scr[("t4", stripe)] = t4s[0]
        m2s, v2s = k("b_add2")(t1s, t2s, t3s, t4s)
        ms[stripe], vs[stripe] = m2s[0], v2s[0]
        bc1, bc2 = self._bias_corrections()
        eps = np.float32(self.eps)
        if self.rule == "adam":
            return k("b_adam_fin1")([p], m2s, v2s, bc1, bc2, eps, lr)[0]
        dens, mhs = k("b_adamw_den_mh")(
            v2s, bc2, eps, m2s, bc1,
            [self._arena_scratch("den", stripe, g)], false)
        self._arena_scr[("den", stripe)] = dens[0]
        if not self.weight_decay:
            us = k("b_adamw_fin")(mhs, dens, lr)
            return k("b_psub")([p], us)[0]
        mask = table.decay_mask(stripe)
        t = k("a_wd_mul")(p, np.float32(self.weight_decay), mask,
                          self._arena_scratch("wd", stripe, g), false)
        self._arena_scr[("wd", stripe)] = t
        u = k("a_adamw_fin")(mhs[0], dens[0], t, mask, lr)
        return k("b_psub")([p], [u])[0]

    def _arena_lion(self, table, stripe, p, g, lr, false):
        k = device_apply.k
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        slots = self._arena_slots["m"]
        m = slots.get(stripe)
        if m is None:
            m = _zeros_f32(g.shape)
        t1s, t2s, t3s, t4s = k("b_lion_mul4")(
            [m], [g], b1, one - b1, b2, one - b2,
            [self._arena_scratch("t2", stripe, g)],
            [self._arena_scratch("t4", stripe, g)], false)
        self._arena_scr[("t2", stripe)] = t2s[0]
        self._arena_scr[("t4", stripe)] = t4s[0]
        us = k("b_sign_add")(t1s, t2s)
        slots[stripe] = k("b_add_d0")(t3s, t4s)[0]
        if not self.weight_decay:
            return k("b_psub")([p], k("b_mul_d0")(us, lr))[0]
        mask = table.decay_mask(stripe)
        t = k("a_wd_mul")(p, np.float32(self.weight_decay), mask,
                          self._arena_scratch("wd", stripe, g), false)
        self._arena_scr[("wd", stripe)] = t
        u = k("a_lion_fin")(us[0], t, mask, lr)
        return k("b_psub")([p], [u])[0]

    # --------------------------------------- arena range apply (pure)
    # Per-[lo, hi) slices of the per-stripe stage chain: the shared
    # machinery behind intra-host stage chunking (PSDT_DEVICE_STAGE_CHUNK)
    # and the cross-replica sharded update (replication/sharded_update.py),
    # where each replica runs only its owned slices.  Every stage is
    # elementwise, so a slice-of-apply is bit-identical to the
    # apply-of-slice — pinned by tests/test_sharded_update.py.

    def _arena_stripe_chunked(self, table, stripe, p, g, lr, false,
                              chunk, size):
        """The whole-stripe apply as ceil(size/chunk) independent range
        programs (sub-chunked stage programs, ISSUE 15 leftover).  Slot
        reads all happen against the pre-close slabs (the range apply is
        pure); the fresh slot slices commit at the end, exactly like the
        one-shot path's in-place donation semantics."""
        import jax.numpy as jnp

        pieces = []
        slot_pieces: dict[str, list] = {
            kind: [] for kind in self._RULE_SLOTS[self.rule]}
        for lo in range(0, size, chunk):
            hi = min(lo + chunk, size)
            new_p, slots = self.apply_arena_range(
                table, stripe, p[lo:hi], g[lo:hi], lo, hi, false=false)
            pieces.append(new_p)
            for kind, arr in slots.items():
                slot_pieces[kind].append((lo, hi, arr))
        self.commit_arena_ranges(
            table, stripe, {k: v for k, v in slot_pieces.items() if v})
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    def apply_arena_range(self, table, stripe, p, g, lo, hi, false=None):
        """PURE per-range arena apply: run the rule's fused stage chain
        over one contiguous ``[lo, hi)`` slice of stripe ``stripe`` and
        return ``(new_param_slice, {slot_kind: new_slot_slice})``
        WITHOUT touching the arena slot slabs — the caller commits the
        slot slices via :meth:`commit_arena_ranges` once its close
        passes the point of no return (a degraded sharded close must be
        able to fall back to the full local apply against unmodified
        slots, and a backup whose install leg never arrives must drop
        the slices without trace).

        ``p``/``g`` are f32 slices of the param and fold-sum slabs
        (device or host); slot state is read as SLICES of the live
        slabs — fresh buffers, so the stage kernels' donation consumes
        the slices, never the slabs.  Caller has run
        :meth:`ensure_arena_slots` and serializes logical steps."""
        k = device_apply.k
        if false is None:
            false = np.bool_(False)
        lr = np.float32(self.learning_rate)
        p = device_apply.owned_f32(p)
        g = device_apply.owned_f32(g)
        if self.rule == "sgd":
            return k("b_psub")([p], k("b_mul")([g], lr))[0], {}
        if self.rule == "momentum":
            slab = self._arena_slots.get("velocity", {}).get(stripe)
            if slab is None:
                # unseeded stripe: the copy-seed, per slice (a bit copy,
                # so concatenated slices == the whole-slab a_copy)
                v2 = k("a_copy")(g, false)
                return (k("b_psub")([p], k("b_mul")([v2], lr))[0],
                        {"velocity": v2})
            ts = k("b_mul_d0")([slab[lo:hi]], np.float32(self.momentum))
            v2s, steps = k("b_mom_pair")(ts, [g], lr)
            return k("b_psub")([p], steps)[0], {"velocity": v2s[0]}
        if self.rule == "lion":
            return self._arena_lion_range(table, stripe, p, g, lo, hi,
                                          lr, false)
        return self._arena_adam_range(table, stripe, p, g, lo, hi, lr,
                                      false)

    def _range_scratch(self, kind: str, stripe: int, lo: int, hi: int, g):
        s = self._arena_scr.get((kind, stripe, lo, hi))
        if s is None or s.shape != g.shape:
            s = _zeros_f32(g.shape)
        return s

    def _arena_adam_range(self, table, stripe, p, g, lo, hi, lr, false):
        k = device_apply.k
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        m_slab = self._arena_slots.get("m", {}).get(stripe)
        v_slab = self._arena_slots.get("v", {}).get(stripe)
        m = _zeros_f32(g.shape) if m_slab is None else m_slab[lo:hi]
        v = _zeros_f32(g.shape) if v_slab is None else v_slab[lo:hi]
        t1s, t2s, t3s, t4s = k("b_adam_mul4")(
            [m], [v], [g], b1, one - b1, b2, one - b2,
            [self._range_scratch("t2", stripe, lo, hi, g)],
            [self._range_scratch("t4", stripe, lo, hi, g)], false)
        self._arena_scr[("t2", stripe, lo, hi)] = t2s[0]
        self._arena_scr[("t4", stripe, lo, hi)] = t4s[0]
        m2s, v2s = k("b_add2")(t1s, t2s, t3s, t4s)
        out_slots = {"m": m2s[0], "v": v2s[0]}
        bc1, bc2 = self._bias_corrections()
        eps = np.float32(self.eps)
        if self.rule == "adam":
            return (k("b_adam_fin1")([p], m2s, v2s, bc1, bc2, eps,
                                     lr)[0], out_slots)
        dens, mhs = k("b_adamw_den_mh")(
            v2s, bc2, eps, m2s, bc1,
            [self._range_scratch("den", stripe, lo, hi, g)], false)
        self._arena_scr[("den", stripe, lo, hi)] = dens[0]
        if not self.weight_decay:
            us = k("b_adamw_fin")(mhs, dens, lr)
            return k("b_psub")([p], us)[0], out_slots
        mask = table.decay_mask(stripe)[lo:hi]
        t = k("a_wd_mul")(p, np.float32(self.weight_decay), mask,
                          self._range_scratch("wd", stripe, lo, hi, g),
                          false)
        self._arena_scr[("wd", stripe, lo, hi)] = t
        u = k("a_adamw_fin")(mhs[0], dens[0], t, mask, lr)
        return k("b_psub")([p], [u])[0], out_slots

    def _arena_lion_range(self, table, stripe, p, g, lo, hi, lr, false):
        k = device_apply.k
        b1, b2 = np.float32(self.b1), np.float32(self.b2)
        one = np.float32(1.0)
        m_slab = self._arena_slots.get("m", {}).get(stripe)
        m = _zeros_f32(g.shape) if m_slab is None else m_slab[lo:hi]
        t1s, t2s, t3s, t4s = k("b_lion_mul4")(
            [m], [g], b1, one - b1, b2, one - b2,
            [self._range_scratch("t2", stripe, lo, hi, g)],
            [self._range_scratch("t4", stripe, lo, hi, g)], false)
        self._arena_scr[("t2", stripe, lo, hi)] = t2s[0]
        self._arena_scr[("t4", stripe, lo, hi)] = t4s[0]
        us = k("b_sign_add")(t1s, t2s)
        out_slots = {"m": k("b_add_d0")(t3s, t4s)[0]}
        if not self.weight_decay:
            return (k("b_psub")([p], k("b_mul_d0")(us, lr))[0],
                    out_slots)
        mask = table.decay_mask(stripe)[lo:hi]
        t = k("a_wd_mul")(p, np.float32(self.weight_decay), mask,
                          self._range_scratch("wd", stripe, lo, hi, g),
                          false)
        self._arena_scr[("wd", stripe, lo, hi)] = t
        u = k("a_lion_fin")(us[0], t, mask, lr)
        return k("b_psub")([p], [u])[0], out_slots

    def ensure_arena_slots(self, table) -> None:
        """Public face of the slot-slab pack for the range-apply
        callers (the sharded-update exchange runs it before slicing)."""
        self._ensure_arena_slots(table)

    def arena_slot_kinds(self) -> tuple:
        return self._RULE_SLOTS[self.rule]

    def arena_slot_slab(self, kind: str, stripe: int):
        """The live slot slab for (kind, stripe), or None (unseeded
        momentum / no slabs packed)."""
        return self._arena_slots.get(kind, {}).get(stripe)

    def commit_arena_ranges(self, table, stripe: int,
                            slot_pieces: Mapping[str, list]) -> None:
        """Write fresh slot slices into the arena slot slabs — the
        deferred other half of :meth:`apply_arena_range`, run only once
        a close commits.  ``slot_pieces`` maps slot kind to a list of
        ``(lo, hi, values)``; full contiguous coverage rebinds the slab
        as one concatenation (no read of the old slab), partial
        coverage scatters into the existing slab (a sharded backup
        commits only its OWNED ranges — its non-owned slot elements go
        stale by design, healed by the next flat state ship)."""
        import jax.numpy as jnp

        for kind, pieces in slot_pieces.items():
            if not pieces:
                continue
            per_stripe = self._arena_slots.setdefault(kind, {})
            pieces = sorted(pieces, key=lambda t: t[0])
            size = int(table.stripe_sizes[stripe])
            full = (pieces[0][0] == 0 and pieces[-1][1] == size
                    and all(pieces[i][1] == pieces[i + 1][0]
                            for i in range(len(pieces) - 1)))
            if full:
                vals = [device_apply.owned_f32(a) for _, _, a in pieces]
                per_stripe[stripe] = (vals[0] if len(vals) == 1
                                      else jnp.concatenate(vals))
                continue
            slab = per_stripe.get(stripe)
            if slab is None:
                slab = _zeros_f32((size,))
            for piece_lo, piece_hi, arr in pieces:
                slab = slab.at[piece_lo:piece_hi].set(
                    device_apply.owned_f32(arr))
            per_stripe[stripe] = slab

    # ------------------------------------------- arena slot slab sync
    def _ensure_arena_slots(self, table) -> None:
        """Pack the per-name slot tables into per-stripe slabs for
        ``table``'s epoch (one host concat + one H2D per (kind, stripe);
        missing names pack as zeros — exactly the host seed for every
        rule but Momentum, whose mixed case :meth:`arena_ready`
        excluded).  No-op when the slabs already match the epoch."""
        if (self._arena_table is not None
                and self._arena_table.epoch == table.epoch):
            self._arena_table = table
            return
        import jax.numpy as jnp

        with self._lock:
            if (self._arena_table is not None
                    and self._arena_table.epoch == table.epoch):
                self._arena_table = table
                return
            if self._arena_slots:
                # a REPACK (table epoch moved): spill the old slabs back
                # to per-name entries first so the new layout packs the
                # live values, not stale ones
                self._spill_arena_locked()
            slots: dict[str, dict[int, object]] = {}
            for kind in self._RULE_SLOTS[self.rule]:
                by_name = self._slots[kind]
                if self.rule == "momentum" and not by_name:
                    # unseeded: stripes seed lazily via the copy-seed
                    slots[kind] = {}
                    continue
                per_stripe: dict[int, object] = {}
                for stripe in range(table.stripes):
                    size = table.stripe_sizes[stripe]
                    if not size:
                        continue
                    host = np.zeros(size, np.float32)
                    for name in table.stripe_names[stripe]:
                        arr = by_name.get(name)
                        if arr is not None:
                            e = table.entries[name]
                            host[e.offset:e.offset + e.length] = (
                                np.asarray(np.asarray(arr),
                                           np.float32).reshape(-1))
                    per_stripe[stripe] = jnp.asarray(host)
                slots[kind] = per_stripe
                self._slots[kind] = {}
            self._arena_slots = slots
            self._arena_table = table
            self._arena_scr = {}

    def _spill_arena_locked(self) -> None:
        """Materialize the slot slabs back into the per-name tables
        (one D2H per slab, per-name device re-uploads) and drop them —
        the per-tensor consumers' escape hatch.  Caller holds _lock."""
        import jax.numpy as jnp

        table = self._arena_table
        if table is None or not self._arena_slots:
            self._arena_slots = {}
            self._arena_table = None
            return
        for kind, per_stripe in self._arena_slots.items():
            by_name = self._slots.setdefault(kind, {})
            for stripe, slab in per_stripe.items():
                host = np.asarray(slab)
                for name in table.stripe_names[stripe]:
                    e = table.entries[name]
                    by_name[name] = jnp.asarray(np.ascontiguousarray(
                        host[e.offset:e.offset + e.length])).reshape(
                            e.shape)
        self._arena_slots = {}
        self._arena_table = None
        self._arena_scr = {}

    # ------------------------------------------------------- checkpoint
    def state_dict(self) -> dict:
        with self._lock:
            if self._arena_slots:
                out = self._arena_state_dict_locked()
            else:
                out = {
                    slot: {name: np.array(np.asarray(arr))
                           for name, arr in table.items()}
                    for slot, table in self._slots.items()}
        if self.rule in ("adam", "adamw"):
            out["step"] = self.step
        return out

    def _arena_state_dict_locked(self) -> dict:
        """Per-name snapshot straight from the slot slabs (one D2H per
        slab, per-name np copies of the table views) — the checkpoint
        layout is the host optimizers', bit for bit, so .ckpt files
        round-trip across PSDT_ARENA on/off unchanged."""
        table = self._arena_table
        out: dict = {}
        for kind, per_stripe in self._arena_slots.items():
            by_name: dict = {}
            for stripe, slab in per_stripe.items():
                host = np.asarray(slab)
                for name in table.stripe_names[stripe]:
                    e = table.entries[name]
                    by_name[name] = np.array(
                        host[e.offset:e.offset + e.length],
                        np.float32).reshape(e.shape)
            out[kind] = by_name
        return out

    def load_state_dict(self, state: dict) -> None:
        import jax.numpy as jnp

        state = dict(state or {})
        with self._lock:
            # restored state supersedes any packed slabs (and their
            # scratch): the next arena close repacks from these tables
            self._arena_slots = {}
            self._arena_table = None
            self._arena_scr = {}
            for slot in self._RULE_SLOTS[self.rule]:
                self._slots[slot] = {
                    name: jnp.asarray(
                        np.ascontiguousarray(arr, np.float32))
                    for name, arr in (state.get(slot) or {}).items()}
        if self.rule in ("adam", "adamw"):
            self.step = int(state.get("step", 0))
            self._bc_step = -1


def _zeros_f32(shape):
    import jax.numpy as jnp

    return jnp.zeros(shape, jnp.float32)
