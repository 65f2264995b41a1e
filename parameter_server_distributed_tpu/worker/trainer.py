"""Worker-local gradient computation — jitted, packed, with local data
parallelism.

Replaces two reference components at once:

- the gradient stub (`compute_gradients` fills 0.01 —
  reference: src/worker.cpp:316-329) becomes a real jitted
  value_and_grad of the worker's model;
- the intra-node NCCL all-reduce (`NCCLManager` +
  `aggregate_gradients_multi_gpu` — reference: src/nccl_manager.cpp:102-121,
  src/worker.cpp:409-448) becomes *sharding the batch across local devices
  inside one jitted step*: the loss is a mean over the global batch, so XLA
  inserts the cross-device reduction itself.  No manager class, no explicit
  collective, no H2D round-trips per tensor.

Transfer discipline: the reference pays per-tensor cudaMalloc/H2D/D2H on
every iteration (src/worker.cpp:409-448).  Here the whole parameter store
crosses the host<->device boundary as ONE flat f32 buffer each way per
iteration — the jitted step unpacks it, differentiates, and repacks the
gradients with the loss piggybacked at offset 0, so a 60-tensor ResNet
costs the same two transfers as a 1-tensor MLP.
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.lock_order import checked_lock
from ..core.tensor import TensorStore
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from ..utils.buffers import float32_over

# One dispatch at a time per process: trainer-originated XLA work (step
# launch, bucket slice fetches) may run from several threads at once —
# the worker's train thread plus the RPC sender draining GradientBuckets,
# times N in-process workers under tests.  The XLA CPU client has
# deadlocked under that concurrency (both dispatches parked forever);
# serializing OUR dispatch entry points costs nothing in production (one
# worker per process, dispatch is microseconds) and removes the overlap
# the client cannot handle.  D2H/compute overlap is unaffected: the lock
# covers launching work, and async copies still complete in parallel.
_DISPATCH_LOCK = checked_lock("trainer._DISPATCH_LOCK")

# Bytes of served parameters that went to memory the worker had to
# allocate (an upload buffer taken in place of a held one here; every
# tensor a pull could not land, worker/worker.py), and bytes _pack copied
# because a slot was not in place.  Both stand in steady state over the
# f32 wire; both move by the payload for a store made elsewhere.
_obs_fresh_bytes = obs_stats.counter("worker.pull.fresh_bytes")
_obs_copied_bytes = obs_stats.counter("worker.pack.copied_bytes")


def _wait_for_upload(uploaded) -> None:
    """Inside ``worker/device_wait``, while spans are recorded: wait for
    the step's uploaded input first, under a span of its own, so that the
    wait for the step does not hold the transfer (``jax.device_put``
    returns before the bytes are on the device).  With recording off
    there is nothing to wait for (:meth:`Trainer._dispatch_step` hands
    out None) and no call."""
    with obs_trace.span("worker/device_wait/upload"):
        uploaded.block_until_ready()


class GradientBuckets:
    """Lazily-fetched packed gradients: the D2H leg of the pipelined data
    plane.

    ``compute_gradient_buckets`` returns one of these instead of a
    materialized gradient dict: the jitted step's flat output stays on
    device, and iterating yields ``(name, f32 array)`` per tensor while
    fetching the flat buffer host-side in bucket-sized slices on demand.
    Fed to a lazy wire-tensor iterator (worker/worker.py) under the
    chunk-stream/fused RPCs, bucket N+1's D2H copy (kicked off
    asynchronously) overlaps bucket N's compress/encode/transport — the
    whole-store fetch stall of the serial path disappears.

    Bucket 0 additionally carries the loss scalar (flat offset 0);
    reading :attr:`loss` fetches it, blocking until the step's compute is
    done.  Fetched buckets are cached, so re-iteration (the unary
    fallback replays the tensors) costs no second device round-trip.
    ``on_fetch(bucket_index, n_buckets)`` fires on each REAL device
    fetch — tests and the data-plane microbench use it to observe
    pipelining."""

    def __init__(self, layout, device_flat, bucket_bytes: int,
                 on_fetch: Callable[[int, int], None] | None = None,
                 uploaded=None):
        self._device = device_flat
        # the step's uploaded input while spans are recorded (see
        # _wait_for_upload), dropped at the first wait
        self._uploaded = uploaded
        self.on_fetch = on_fetch
        # greedy plan over the fixed layout: consecutive tensors grouped
        # into ~bucket_bytes f32 slices of the flat output (loss scalar
        # rides bucket 0); a tensor larger than the budget rides alone —
        # same grouping rule as rpc/data_plane.split_tensors
        plan: list[tuple[int, int, list]] = []
        group: list = []
        start = 0
        for entry in layout:
            _name, off, size, _shape, _dtype = entry
            end = 1 + off + size
            if group and bucket_bytes > 0 and \
                    4 * (end - start) > bucket_bytes:
                plan.append((start, 1 + off, group))
                group, start = [], 1 + off
            group.append(entry)
        if group or not plan:
            end = (1 + group[-1][1] + group[-1][2]) if group else 1
            plan.append((start, end, group))
        self._plan = plan
        self._slices: list = [None] * len(plan)
        self._host: dict[int, np.ndarray] = {}
        self._lock = threading.Lock()

    @property
    def num_buckets(self) -> int:
        return len(self._plan)

    @property
    def loss(self) -> float:
        # the first real fetch of bucket 0 blocks until the step is done:
        # that wait is the step itself, not a D2H leg
        return float(self._fetch(0, "worker/device_wait")[0])

    def _dev_slice(self, i: int):
        s = self._slices[i]
        if s is None:
            a, b, _ = self._plan[i]
            with _DISPATCH_LOCK:
                s = self._slices[i] = self._device[a:b]
        return s

    def _fetch(self, i: int, leg: str = "worker/d2h") -> np.ndarray:
        with self._lock:
            buf = self._host.get(i)
            if buf is None:
                if self.on_fetch is not None:
                    self.on_fetch(i, len(self._plan))
                a, b, _ = self._plan[i]
                with obs_trace.span(leg, bucket=i, bytes=4 * (b - a)):
                    if self._uploaded is not None:
                        uploaded, self._uploaded = self._uploaded, None
                        _wait_for_upload(uploaded)
                    buf = self._host[i] = np.asarray(self._dev_slice(i))
        return buf

    def _prefetch(self, i: int) -> None:
        """Kick bucket i's device→host copy without blocking, so it runs
        under the previous bucket's encode/transport."""
        if i >= len(self._plan) or i in self._host:
            return
        start_copy = getattr(self._dev_slice(i), "copy_to_host_async", None)
        if start_copy is not None:
            with _DISPATCH_LOCK:
                start_copy()

    def __iter__(self) -> Iterator[tuple[str, np.ndarray]]:
        for i, (start, _end, entries) in enumerate(self._plan):
            self._prefetch(i + 1)
            buf = self._fetch(i)
            for name, off, size, shape, _dtype in entries:
                a = 1 + off - start
                yield name, buf[a:a + size].reshape(shape)


class Trainer:
    """Jitted gradient computation for one worker process.

    ``local_devices``: devices for intra-worker data parallelism (defaults
    to all visible devices).  The batch's leading axis is sharded across
    them; parameters are replicated.

    ``mesh_config`` + ``rule_fn``: intra-worker MODEL parallelism — the
    worker's local chips form a full mesh (data/fsdp/tensor/...) and the
    unpacked params are sharding-constrained by ``rule_fn(mesh)`` inside
    the jitted step, so XLA partitions the forward/backward across the
    worker's chips (Megatron TP, ZeRO fsdp) while the PS protocol still
    sees one packed host store per push/pull.  The packed flat buffers at
    the host<->device boundary are themselves element-sharded over ALL
    mesh axes (padded to divisibility), so no chip ever materializes a
    full replica of the params or grads — the point of a model-parallel
    worker.  The reference's workers are strictly single-GPU-per-rank
    (src/worker.cpp); this is the TPU-native upgrade: a worker whose
    model does not fit one chip still speaks plain PS.
    """

    def __init__(self, model, local_devices: list | None = None,
                 mesh_config=None, rule_fn=None):
        self.model = model
        devices = local_devices or jax.local_devices()
        self._rule = None
        if mesh_config is not None:
            from ..parallel.mesh import (AXIS_NAMES, batch_sharding,
                                         build_mesh)

            need = mesh_config.num_devices
            if len(devices) < need:
                raise ValueError(
                    f"worker mesh {mesh_config.axis_sizes} needs {need} "
                    f"local devices, have {len(devices)}")
            self._mesh = build_mesh(mesh_config, devices=devices[:need])
            if rule_fn is not None:
                self._rule = rule_fn(self._mesh)
            # flat param/grad buffers are element-sharded across every
            # chip: 1/N of the store per chip at the boundary
            self._flat_sharding = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec(AXIS_NAMES))
            self._n_shard = need
            self._batch_sharded = batch_sharding(self._mesh)
        else:
            self._mesh = jax.sharding.Mesh(np.array(devices), ("local",))
            self._flat_sharding = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
            self._n_shard = 1
            self._batch_sharded = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec("local"))

        # fixed packing layout: (name, offset, size, shape, dtype), by name
        init = model.init_params(0)
        self._layout = []
        offset = 0
        for name in sorted(init):
            shape = tuple(np.shape(init[name]))
            size = math.prod(shape) if shape else 1
            self._layout.append((name, offset, size, shape,
                                 jnp.asarray(init[name]).dtype))
            offset += size
        self._packed_size = offset
        del init

        # padded so the element-sharded flat buffers divide over the mesh
        self._padded_in = -(-self._packed_size // self._n_shard) * self._n_shard
        out_size = 1 + self._packed_size  # loss at offset 0
        self._padded_out = -(-out_size // self._n_shard) * self._n_shard
        # The two flat float32 buffers the step uploads from, by turns.
        # Kept as ``bytearray``s and never as arrays: whoever holds a view
        # keeps the buffer (``utils/buffers.exported``), so the trainer
        # makes its views per call and lets go of them.
        self._pack_bufs: list[bytearray | None] = [None, None]
        self._pack_turn = 0   # the buffer a store made elsewhere goes to

        layout = self._layout
        mesh = self._mesh
        param_rule = self._rule
        pad_out = self._padded_out - out_size

        def packed_step(flat_params, batch):
            params = {name: flat_params[off:off + size]
                      .reshape(shape).astype(dtype)
                      for name, off, size, shape, dtype in layout}
            if param_rule is not None:
                # model parallelism: constrain each unpacked param to its
                # rule sharding — XLA partitions the whole step around it
                params = {
                    name: jax.lax.with_sharding_constraint(
                        value, jax.sharding.NamedSharding(
                            mesh, param_rule(name, tuple(value.shape))))
                    for name, value in params.items()}
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
            flat = jnp.concatenate(
                [jnp.reshape(loss, (1,)).astype(jnp.float32)]
                + [grads[name].astype(jnp.float32).ravel()
                   for name, *_ in layout]
                + ([jnp.zeros((pad_out,), jnp.float32)] if pad_out else []))
            return flat

        self._step = jax.jit(packed_step,
                             out_shardings=self._flat_sharding)

    @property
    def num_local_devices(self) -> int:
        return self._mesh.devices.size

    def init_params(self, seed: int = 0) -> TensorStore:
        """Deterministic init — every worker derives the identical store for
        PS bootstrap (cf. the reference's fabricated dummy 10x10 'weight'
        when the pull comes back empty — src/worker.cpp:346-353)."""
        params = self.model.init_params(seed)
        return {k: np.asarray(v, np.float32) for k, v in params.items()}

    def _shard_batch(self, batch):
        def put(x):
            x = np.asarray(x)
            return jax.device_put(x, self._batch_sharded)
        return jax.tree.map(put, batch)

    def _writable(self, i: int) -> np.ndarray:
        """Upload buffer ``i`` as a flat float32 array that may be
        written: the kept buffer when no view of it is alive (a store
        somebody kept, a straggler's converter, a device array that
        aliases it or a transfer still reading it), a new one in its
        place otherwise, its padded tail zeroed once."""
        kept = self._pack_bufs[i]
        buf, flat = float32_over(kept, (self._padded_in,), _obs_fresh_bytes)
        if buf is not kept:
            flat[self._packed_size:] = 0
            self._pack_bufs[i] = buf
        return flat

    def lend_store(self) -> dict[str, np.ndarray]:
        """Where a pull may land the parameters the NEXT step uploads:
        ``{name: writable float32 view of the layout's slot}`` in the
        upload buffer whose turn is next (the other one may still be
        aliased by the running step's input on the CPU client).  A store
        made of these views is uploaded where it lies (:meth:`_pack`);
        whoever keeps one, or any slice of it, keeps that buffer, and the
        next loan of its turn allocates (``worker.pull.fresh_bytes``)."""
        flat = self._writable(self._pack_turn)
        return {name: flat[off:off + size].reshape(shape)
                for name, off, size, shape, _dtype in self._layout}

    def _not_in_place(self, params: Mapping[str, np.ndarray],
                      buf: bytearray | None) -> list:
        """The layout's slots that ``params`` does not already hold in
        ``buf``: all but those whose array IS the slot's own memory
        (same address, float32, contiguous, same size)."""
        slots = [slot for slot in self._layout if slot[2]]
        if buf is None:
            return slots
        base = np.frombuffer(buf, np.uint8).__array_interface__["data"][0]

        def in_place(name, off, size, _shape, _dtype) -> bool:
            a = params[name]
            return (isinstance(a, np.ndarray) and a.dtype == np.float32
                    and a.size == size and a.flags.c_contiguous
                    and a.__array_interface__["data"][0] == base + 4 * off)

        return [slot for slot in slots if not in_place(*slot)]

    def _pack(self, params: Mapping[str, np.ndarray]) -> np.ndarray:
        """``params`` as the flat float32 buffer the step takes.

        A store landed in :meth:`lend_store`'s views is uploaded where it
        lies: of the two buffers the one that needs the fewer bytes
        copied is taken (its turn decides a tie) and only the slots not
        in place are copied into it (``worker.pack.copied_bytes``).  A
        store that lies in neither is packed whole into the buffer whose
        turn it is, by the rule of :meth:`_writable`.

        Two buffers alternate because the CPU PJRT client may ZERO-COPY
        a device_put numpy array (the device buffer aliases it): the
        buffer written for the next step, by a pull during this one or
        by the copy here, is never the one this step uploaded."""
        turn = self._pack_turn
        copy = [self._not_in_place(params, buf) for buf in self._pack_bufs]
        cost = [sum(slot[2] for slot in slots) for slots in copy]
        if cost[turn ^ 1] < cost[turn]:
            turn ^= 1
        if cost[turn] < self._packed_size:
            # part of the store lies here already: params holds the views
            flat = np.frombuffer(self._pack_bufs[turn], np.float32)
        else:
            flat = self._writable(turn)
        self._pack_turn = turn ^ 1
        for name, off, size, _shape, _dtype in copy[turn]:
            flat[off:off + size] = np.asarray(
                params[name], np.float32).ravel()
        _obs_copied_bytes.add(4 * cost[turn])
        return flat

    def _dispatch_step(self, params: Mapping[str, np.ndarray], batch):
        """Pack + upload + launch the jitted step; returns the (async)
        flat device output without fetching it, and beside it the
        uploaded input (the step does not donate it) while spans are
        recorded, None otherwise: what :func:`_wait_for_upload` takes."""
        with obs_trace.span("worker/pack", bytes=4 * self._padded_in):
            packed = self._pack(params)
        with _DISPATCH_LOCK:
            with obs_trace.span("worker/h2d", bytes=4 * self._padded_in):
                flat = jax.device_put(packed, self._flat_sharding)
                batch = self._shard_batch(batch)
            with obs_trace.span("worker/dispatch"):
                out = self._step(flat, batch)
        return out, (flat if obs_trace.enabled() else None)

    def compute_gradients(self, params: Mapping[str, np.ndarray],
                          batch) -> tuple[TensorStore, float]:
        """params (host store) + batch -> (gradient store, loss).

        One H2D upload (packed params), one D2H fetch (loss + packed
        grads), regardless of tensor count."""
        out, uploaded = self._dispatch_step(params, batch)
        # wait for the step first, so that the one whole-output fetch
        # below times the copy alone
        with obs_trace.span("worker/device_wait"):
            if uploaded is not None:
                _wait_for_upload(uploaded)
                del uploaded    # or it holds its HBM through the fetch
            out.block_until_ready()
        with obs_trace.span("worker/d2h", bytes=4 * self._padded_out):
            packed = np.asarray(out)
        loss = float(packed[0])
        grads = {name: packed[1 + off:1 + off + size].reshape(shape)
                 for name, off, size, shape, _dtype in self._layout}
        return grads, loss

    def compute_gradient_buckets(self, params: Mapping[str, np.ndarray],
                                 batch, bucket_bytes: int | None = None,
                                 on_fetch=None) -> GradientBuckets:
        """Incremental-D2H variant of :meth:`compute_gradients`: same jitted
        step, but the packed gradient buffer stays on device and comes back
        host-side in ~``bucket_bytes`` slices fetched lazily as the
        returned :class:`GradientBuckets` is iterated — the producer side
        of the pipelined push (worker/worker.py).  Default bucket budget:
        rpc/data_plane.bucket_bytes()."""
        if bucket_bytes is None:
            from ..rpc.data_plane import bucket_bytes as _bb
            bucket_bytes = _bb()
        out, uploaded = self._dispatch_step(params, batch)
        return GradientBuckets(self._layout, out, bucket_bytes,
                               on_fetch=on_fetch, uploaded=uploaded)
