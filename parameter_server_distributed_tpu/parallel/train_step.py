"""SPMD train steps: the TPU-native data plane.

This module is the direct replacement for the reference's entire data path:

- the gRPC push/pull of float tensors (reference: src/worker.cpp:240-272,
  src/parameter_server.cpp:18-97) becomes sharding annotations on one
  jitted step — XLA inserts all-gather/reduce-scatter over ICI;
- the NCCL all-reduce (reference: src/nccl_manager.cpp:102-121) becomes the
  implicit gradient mean of a batch sharded over the data axes;
- the PS's "apply mean gradient" update (reference: src/parameter_server.cpp:77-91)
  becomes an optax update with donated buffers so HBM stays flat.

Sync-mode semantics preserved: one barrier per step (the compiled collective
itself), mean over contributors, `params <- params - lr * mean_grad` for the
SGD config.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from .mesh import batch_sharding, replicated
from .sharding import ShardingRule, store_shardings


def put_global(x, sharding) -> jax.Array:
    """Place a host (or device) value with a global sharding.  Under a
    multi-controller run device_put cannot target non-addressable devices;
    every process must hold the same value and contributes its addressable
    shards.  The single shared placement helper for batches and state."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    host = np.asarray(x)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Parameters + optimizer state + step counter, all device-resident.
    The sharded TrainState *is* the parameter server's shard table."""
    params: dict[str, jax.Array]
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params: Mapping[str, jax.Array],
               optimizer: optax.GradientTransformation) -> "TrainState":
        params = dict(params)
        return cls(params=params, opt_state=optimizer.init(params),
                   step=jnp.zeros((), jnp.int32))


def make_lr_schedule(learning_rate: float, schedule: str = "constant",
                     warmup_steps: int = 0, total_steps: int = 0):
    """LR schedule: "constant", "cosine", or "linear" decay, with optional
    linear warmup from zero.  Returns a float (constant, no warmup) or an
    optax schedule fn."""
    schedule = schedule.lower()
    if schedule not in ("constant", "cosine", "linear"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "constant":
        if warmup_steps <= 0:
            return learning_rate
        return optax.linear_schedule(0.0, learning_rate, warmup_steps)
    if total_steps <= warmup_steps:
        raise ValueError(f"{schedule} decay needs total_steps > warmup_steps "
                         f"({total_steps} vs {warmup_steps})")
    decay_steps = total_steps - warmup_steps
    if schedule == "cosine":
        decay = optax.cosine_decay_schedule(learning_rate, decay_steps)
    else:
        decay = optax.linear_schedule(learning_rate, 0.0, decay_steps)
    if warmup_steps <= 0:
        return decay
    warmup = optax.linear_schedule(0.0, learning_rate, warmup_steps)
    return optax.join_schedules([warmup, decay], [warmup_steps])


class EmaState(NamedTuple):
    """State for :func:`params_ema` — the shadow (EMA) parameter tree."""
    ema: dict


def params_ema(decay: float) -> optax.GradientTransformation:
    """Track an exponential moving average of the PARAMETERS inside the
    optimizer state (Polyak averaging): after each update,
    ``ema = decay * ema + (1 - decay) * new_params``.  Living in
    opt_state means TrainState/checkpoint structure is untouched — the
    EMA rides existing save/restore/sharding for free; read it back with
    :func:`extract_ema`.  Updates pass through unchanged (chain-neutral).
    The shadow initializes to the INITIAL params (not zeros), so no
    zero-init bias exists and no debiasing is needed anywhere."""
    if not 0.0 < decay < 1.0:
        raise ValueError(f"EMA decay must be in (0, 1), got {decay}")

    def init(params):
        # shadow in FLOAT32 regardless of param dtype: at decay 0.999
        # the per-step correction (1-decay)*(p-e) is below bf16's
        # half-ulp, so a bf16 shadow would round back to itself every
        # step and never move off the initial params
        return EmaState(ema=jax.tree.map(
            lambda p: jnp.asarray(p, jnp.float32), params))

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("params_ema needs params: call "
                             "opt.update(grads, state, params)")
        new_ema = jax.tree.map(
            lambda e, p, u: decay * e
            + (1.0 - decay) * (p.astype(jnp.float32)
                               + u.astype(jnp.float32)),
            state.ema, params, updates)
        return updates, EmaState(ema=new_ema)

    return optax.GradientTransformation(init, update)


def extract_ema(opt_state):
    """The EMA parameter tree (float32 — see :func:`params_ema`) from an
    optimizer state built with ``make_optimizer(..., ema_decay>0)``, or
    None when no EmaState is present.  Works on the nested chain states
    optax builds.  Cast back to the model dtype for eval/serving:
    ``jax.tree.map(lambda e, p: e.astype(p.dtype), ema, params)``."""
    found = [s.ema for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: isinstance(s, EmaState))
        if isinstance(s, EmaState)]
    return found[0] if found else None


def make_optimizer(name: str = "sgd", learning_rate: float = 1.0,
                   momentum: float = 0.9, *,
                   schedule: str = "constant", warmup_steps: int = 0,
                   total_steps: int = 0, clip_norm: float = 0.0,
                   weight_decay: float = 1e-4,
                   ema_decay: float = 0.0) -> optax.GradientTransformation:
    """Device-side optimizer matching the host-side ones in core/optimizer.py
    (the reference applies bare SGD at lr=1.0 — src/parameter_server.cpp:87).
    Extensions beyond the reference: LR schedules (warmup + cosine/linear
    decay) and global-norm gradient clipping, composed the optax way."""
    name = name.lower()
    lr = make_lr_schedule(learning_rate, schedule, warmup_steps, total_steps)
    if name == "sgd":
        opt = optax.sgd(lr)
    elif name == "momentum":
        opt = optax.sgd(lr, momentum=momentum)
    elif name == "adam":
        opt = optax.adam(lr)
    elif name == "adamw":
        # decay matrices only: decaying RMSNorm scales/biases toward zero
        # is a known quality bug, the standard mask excludes sub-2D params
        opt = optax.adamw(lr, weight_decay=weight_decay,
                          mask=lambda params: jax.tree.map(
                              lambda p: p.ndim >= 2, params))
    elif name == "adafactor":
        # the TPU-era memory-frugal optimizer (T5 lineage): factored
        # second moments store O(rows + cols) per matrix instead of
        # Adam's O(rows * cols) — at 1B params that is ~8 GB of slot
        # HBM back.  multiply_by_parameter_scale off so the passed
        # warmup/cosine schedule IS the effective step size; weight
        # decay honored with the same matrices-only mask as adamw/lion
        opt = optax.adafactor(
            lr, multiply_by_parameter_scale=False,
            weight_decay_rate=weight_decay if weight_decay else None,
            weight_decay_mask=lambda params: jax.tree.map(
                lambda p: p.ndim >= 2, params))
    elif name == "lion":
        # sign-momentum optimizer: one slot (momentum) instead of
        # Adam's two — half the optimizer HBM at Adam-class quality
        opt = optax.lion(lr, weight_decay=weight_decay,
                         mask=lambda params: jax.tree.map(
                             lambda p: p.ndim >= 2, params))
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if clip_norm and clip_norm > 0:
        opt = optax.chain(optax.clip_by_global_norm(clip_norm), opt)
    if ema_decay:
        # EMA LAST in the chain: it must see the final updates so the
        # shadow tree averages the actual post-step parameters
        opt = optax.chain(opt, params_ema(ema_decay))
    return opt


def split_microbatches(batch, accum_steps: int):
    """Reshape every leaf's leading dim B -> [accum_steps, B/accum_steps]
    for gradient-accumulation scans (training and eval share this split
    and its divisibility check)."""
    def _one(x):
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"batch leading dim {x.shape[0]} does not divide by "
                f"accum_steps={accum_steps}")
        return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

    return jax.tree.map(_one, batch)


def make_train_step(loss_fn: Callable,
                    optimizer: optax.GradientTransformation,
                    accum_steps: int = 1,
                    grad_fn: Callable | None = None) -> Callable:
    """Build a pure (state, batch) -> (state, metrics) step function.

    ``accum_steps > 1`` splits the batch's leading axis into that many
    microbatches and accumulates gradients in float32 under `lax.scan` —
    one optimizer update per step, activation memory of one microbatch.

    ``grad_fn`` overrides autodiff of ``loss_fn``: a (params, batch) ->
    (loss, grads) callable for models whose backward IS a schedule (the
    1F1B pipeline, parallel/pipeline.py) rather than jax.grad of their
    forward.
    """

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    grads_of = grad_fn or jax.value_and_grad(loss_fn)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        if accum_steps == 1:
            loss, grads = grads_of(state.params, batch)
        else:
            micro = split_microbatches(batch, accum_steps)

            def body(carry, mb):
                loss_sum, acc = carry
                l, g = grads_of(state.params, mb)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                return (loss_sum + l.astype(jnp.float32), acc), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            (loss_sum, gsum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            loss = loss_sum / accum_steps
            grads = jax.tree.map(
                lambda g, p: (g / accum_steps).astype(p.dtype), gsum,
                state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(params=new_params, opt_state=new_opt,
                               step=state.step + 1)
        grad_norm = optax.global_norm(grads)
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    return step


def state_shardings(state: TrainState, mesh: Mesh,
                    rule: ShardingRule) -> TrainState:
    """Sharding pytree matching a TrainState: params (and any optimizer slot
    with a matching shape) sharded by ``rule``; scalars replicated."""
    param_shardings = store_shardings(
        mesh, {k: tuple(v.shape) for k, v in state.params.items()}, rule)

    def opt_leaf(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        for name, sharding in param_shardings.items():
            if shape == tuple(state.params[name].shape):
                # momentum/adam slots mirror their parameter's sharding;
                # shape collisions across params resolve to identical specs
                # under shape-based rules, so any match is correct
                return sharding
        return replicated(mesh)

    opt_shardings = jax.tree.map(opt_leaf, state.opt_state)
    return TrainState(params=param_shardings, opt_state=opt_shardings,
                      step=replicated(mesh))


class ShardedTrainer:
    """Compiled SPMD training: state sharded per ``rule`` over ``mesh``,
    batch sharded over the data axes, donated buffers.

    This is BASELINE config 3's "4 PS shards / 8 workers" shape: mesh
    fsdp=4 x data=2 gives 4-way parameter sharding with 8-way data
    parallelism, all inside one XLA program.
    """

    def __init__(self, loss_fn: Callable, mesh: Mesh, rule: ShardingRule,
                 optimizer: optax.GradientTransformation | None = None,
                 accum_steps: int = 1, grad_fn: Callable | None = None):
        self.mesh = mesh
        self.rule = rule
        self.optimizer = optimizer or make_optimizer("sgd", 1.0)
        self._loss_fn = loss_fn
        self._accum_steps = accum_steps
        self._raw_step = make_train_step(loss_fn, self.optimizer,
                                         accum_steps=accum_steps,
                                         grad_fn=grad_fn)
        self._compiled: Callable | None = None
        self._compiled_eval: Callable | None = None
        self._shardings: TrainState | None = None

    def init_state(self, params: Mapping[str, jax.Array]) -> TrainState:
        """Create and shard the train state (host arrays OK).  Every
        process must pass identical param values (same init seed).

        Only the params cross the host<->device boundary: their shardings
        come from the rule, and the optimizer state is initialized directly
        INTO its shardings by a jitted ``optimizer.init`` — no process ever
        materializes a full unsharded optimizer-state replica (the point of
        fsdp sharding)."""
        params = dict(params)
        abstract = jax.eval_shape(
            lambda p: TrainState.create(p, self.optimizer), params)
        self._shardings = state_shardings(abstract, self.mesh, self.rule)
        placed = {name: put_global(value, self._shardings.params[name])
                  for name, value in params.items()}
        opt_state = jax.jit(
            self.optimizer.init,
            out_shardings=self._shardings.opt_state)(placed)
        step = put_global(np.zeros((), np.int32), self._shardings.step)
        return TrainState(params=placed, opt_state=opt_state, step=step)

    def step_fn(self) -> Callable:
        if self._compiled is None:
            if self._shardings is None:
                raise RuntimeError("call init_state first")
            metrics_sharding = {"loss": replicated(self.mesh),
                                "grad_norm": replicated(self.mesh)}
            self._compiled = jax.jit(
                self._raw_step,
                in_shardings=(self._shardings, batch_sharding(self.mesh)),
                out_shardings=(self._shardings, metrics_sharding),
                donate_argnums=0,
            )
        return self._compiled

    def step(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        return self.step_fn()(state, self.put_batch(batch))

    def eval_fn(self) -> Callable:
        """Compiled loss-only forward for held-out evaluation: same state
        and batch shardings as training, no gradient, no buffer donation
        (the state lives on).  Honors accum_steps — a run that needs
        microbatched training would OOM on a full-batch eval forward, so
        eval scans the same microbatch split (mean of equal-size
        microbatch means == the global mean)."""
        if self._compiled_eval is None:
            if self._shardings is None:
                raise RuntimeError("call init_state first")
            loss_fn = self._loss_fn
            accum = self._accum_steps

            def evaluate(state: TrainState, batch):
                if accum == 1:
                    return loss_fn(state.params, batch)
                micro = split_microbatches(batch, accum)

                def body(total, mb):
                    return (total
                            + loss_fn(state.params, mb).astype(jnp.float32),
                            None)

                total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                        micro)
                return total / accum

            self._compiled_eval = jax.jit(
                evaluate,
                in_shardings=(self._shardings, batch_sharding(self.mesh)),
                out_shardings=replicated(self.mesh))
        return self._compiled_eval

    def evaluate(self, state: TrainState, batch) -> jax.Array:
        return self.eval_fn()(state, self.put_batch(batch))

    def put_batch(self, batch):
        """Place a host batch with the global batch sharding (every process
        holds the same global batch — deterministic loaders)."""
        sharding = batch_sharding(self.mesh)
        return jax.tree.map(lambda x: put_global(x, sharding), batch)

    def put_batch_local(self, local_batch):
        """Assemble a global batch from PER-PROCESS rows: each host loads
        only global_batch/process_count rows (its devices' shards) and JAX
        stitches the global array — no host ever materializes the full
        batch.  The scalable multi-host data path; single-process it is
        just put_batch."""
        if jax.process_count() == 1:
            return self.put_batch(local_batch)
        sharding = batch_sharding(self.mesh)
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), local_batch)
