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
lies on the host as ONE flat f32 buffer each way per iteration, whatever
its tensors: the jitted step unpacks it, differentiates, and repacks the
gradients with the loss piggybacked at offset 0.  The gradients come back
as one flat device array, fetched in bucket-sized slices.  The parameters
go up in SECTIONS of that buffer, consecutive elements of at most the
bucket budget cut without regard to tensors: a store that a pull lands in
the trainer's loan (:meth:`Trainer.lend_store`) is uploaded section by
section as its bytes land, beside the rest of the pull; whatever did not
go up that way goes up when the step is dispatched, and a copy on the
device joins the sections into the one flat array the step takes.
"""

from __future__ import annotations

import bisect
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
# Bytes of input every dispatched step took, and those of them that were
# put on the device before the step was asked for (sections a loan
# uploaded as they landed).
_obs_upload_bytes = obs_stats.counter("worker.upload.bytes")
_obs_streamed_bytes = obs_stats.counter("worker.upload.streamed_bytes")


def _wait_for_upload(uploaded) -> None:
    """Inside ``worker/device_wait``, while spans are recorded: wait for
    the step's uploaded input first (the flat array every section is
    joined into), under a span of its own, so that the wait for the step
    does not hold the transfer (``jax.device_put`` returns before the
    bytes are on the device): what is LEFT of the transfer when the step
    is asked for.  With recording off there is nothing to wait for
    (:meth:`Trainer._dispatch_step` hands out None) and no call."""
    with obs_trace.span("worker/device_wait/upload"):
        uploaded.block_until_ready()


class _Loan(dict):
    """What :meth:`Trainer.lend_store` lends: ``{name: writable float32
    view of the layout's slot}`` in one upload buffer, and the upload of
    what lands there.

    :meth:`land` copies a tensor into its slot and puts on the device
    every section of the buffer that is whole by then, so the transfer
    runs beside the arrival and the landing of the next frames.  A slot
    larger than a section spans several and a section may hold several
    slots.  When the last section
    of a shard (the whole buffer, or one device's range of it on a worker
    mesh) is on its way, one copy on the device joins them into the
    shard the step takes and the sections are let go: long before the
    step is launched, so that nothing stands beside the step's own
    memory.  A shard is uploaded here whole or not at all: a name landed
    twice (a stream that fell back to the unary call) or a slot written
    behind the loan's back (:meth:`spoil`) leaves its shard to the
    dispatch.  Thread-safe: the shards of a sharded pull land from their
    own threads."""

    def __init__(self, trainer: "Trainer", flat: np.ndarray):
        layout, cuts = trainer._layout, trainer._cuts
        super().__init__((name, flat[off:off + size].reshape(shape))
                         for name, off, size, shape, _dtype in layout)
        self._trainer = trainer
        self._flat = flat
        self._where = {name: (off, size)
                       for name, off, size, _shape, _dtype in layout}
        self._starts = [a for a, _b in cuts]
        # elements of each section still to land (the padded tail is
        # zero from the start)
        self._left = [max(min(b, trainer._packed_size) - a, 0)
                      for a, b in cuts]
        self._landed: set[str] = set()
        # a shard's sections on the device so far (None: spoiled or
        # taken), how many of them something lands in, the shards joined
        self._parts: list[dict | None] = [{} for _ in trainer._shards]
        self._need = [sum(1 for s in shard if self._left[s])
                      for shard in trainer._shards]
        self._whole: dict[int, jax.Array] = {}
        self._lock = threading.Lock()

    def land(self, name: str, src: np.ndarray) -> np.ndarray:
        """Copy ``src`` (any dtype, the slot's size) into the slot of
        ``name``; returns the slot shaped as ``src``, READ-ONLY: its
        sections are on their way to the device, and bytes written after
        them would not reach the step."""
        off, size = self._where[name]
        with self._lock:
            again = name in self._landed
            self._landed.add(name)
        if again:
            self.spoil(off, size)
        # ONE pass over the tensor, whatever sections it spans: copied a
        # section at a time it lands at half the rate (a copy of 32 MiB
        # takes the cached stores that one of hundreds of MB does not),
        # so its sections go up behind it and not beside it
        held = self._flat[off:off + size].reshape(src.shape)
        np.copyto(held, src, casting="unsafe")
        held.flags.writeable = False
        a = off
        while a < off + size:
            s = bisect.bisect_right(self._starts, a) - 1
            b = min(off + size, self._trainer._cuts[s][1])
            self._note(s, b - a)
            a = b
        return held

    def _note(self, s: int, landed: int) -> None:
        """``landed`` more elements of section ``s`` are in the buffer:
        put the section once it is whole, join its shard once every
        section of it is put."""
        trainer = self._trainer
        k = trainer._shard_of[s]
        with self._lock:
            self._left[s] -= landed
            if self._left[s] or self._parts[k] is None:
                return
        section = trainer._put_landed(self._flat, s)
        with self._lock:
            parts = self._parts[k]
            if parts is None:
                return
            parts[s] = section
            if len(parts) < self._need[k]:
                return
        # sections that hold padding alone land nothing: put them now
        whole = trainer._join_landed(
            [parts[t] if t in parts else trainer._put_landed(self._flat, t)
             for t in trainer._shards[k]])
        with self._lock:
            if self._parts[k] is parts:
                self._whole[k] = whole
                parts.clear()

    def spoil(self, off: int, size: int) -> None:
        """Somebody else wrote ``size`` elements at ``off``: what went
        up of their shards is stale, and nothing more of them goes."""
        with self._lock:
            for s in range(bisect.bisect_right(self._starts, off) - 1,
                           bisect.bisect_left(self._starts, off + size)):
                k = self._trainer._shard_of[s]
                self._parts[k] = None
                self._whole.pop(k, None)

    def take(self) -> dict[int, jax.Array]:
        """The shards on the device (or on their way), by index; the
        loan uploads nothing after this."""
        with self._lock:
            whole, self._whole = self._whole, {}
            self._parts = [None] * len(self._parts)
        return whole


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
        """Bucket ``i`` as a device array of its own (``self._lock``
        held).  The flat output is let go with the last bucket cut from
        it, as each bucket is once it is on the host: by the end of a
        push nothing of the gradient stands on the device beside the
        parameters the pull brings."""
        s = self._slices[i]
        if s is None:
            a, b, _ = self._plan[i]
            with _DISPATCH_LOCK:
                s = self._slices[i] = self._device[a:b]
            if all(cut is not None or j in self._host
                   for j, cut in enumerate(self._slices)):
                self._device = None
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
                    self._slices[i] = None
        return buf

    def _prefetch(self, i: int) -> None:
        """Kick bucket i's device→host copy without blocking, so it runs
        under the previous bucket's encode/transport."""
        with self._lock:
            if i >= len(self._plan) or i in self._host:
                return
            start_copy = getattr(self._dev_slice(i), "copy_to_host_async",
                                 None)
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
        # The newest loan of each buffer, until a step takes its sections
        # (any dispatch lets go of both) or the buffer is lent again or
        # written whole.  A loan holds views of its buffer and sections on
        # the device: both go with it.
        self._loans: list[_Loan | None] = [None, None]
        # Shards and sections.  A shard is what one device holds of the
        # padded flat input: the whole of it where it is replicated, one
        # device's range of it on a worker mesh.  Each is cut into
        # sections, consecutive elements of at most the budget the
        # gradient buckets are planned by.  A section goes to the
        # device(s) of its shard, so joining a shard's sections is a copy
        # on that device and nothing crosses between devices.
        from ..rpc.data_plane import bucket_bytes
        per = bucket_bytes() // 4
        held_by: dict[tuple[int, int], list] = {}
        for device, (held,) in self._flat_sharding.devices_indices_map(
                (self._padded_in,)).items():
            held_by.setdefault(held.indices(self._padded_in)[:2],
                               []).append(device)
        # (first, last, where it is put) by shard
        self._ranges = [
            (start, stop, self._flat_sharding if len(held_by) == 1
             else jax.sharding.SingleDeviceSharding(devices[0]))
            for (start, stop), devices in sorted(held_by.items())]
        self._cuts: list[tuple[int, int]] = []   # [first, last) by section
        self._shards: list[list[int]] = []       # a shard's sections
        for start, stop, _home in self._ranges:
            step = per if per > 0 else max(stop - start, 1)
            starts = list(range(start, stop, step)) or [start]
            self._shards.append(list(range(
                len(self._cuts), len(self._cuts) + len(starts))))
            self._cuts += [(a, min(a + step, stop)) for a in starts]
        self._shard_of = [k for k, shard in enumerate(self._shards)
                          for _s in shard]
        self._join = jax.jit(lambda *sections: jnp.concatenate(sections))

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

    def _put_landed(self, flat: np.ndarray, s: int):
        """Section ``s`` of ``flat`` on its way to the device(s) of its
        shard (the call returns before the bytes have moved)."""
        a, b = self._cuts[s]
        home = self._ranges[self._shard_of[s]][2]
        with _DISPATCH_LOCK:
            with obs_trace.span("worker/h2d", bytes=4 * (b - a), section=s):
                return jax.device_put(flat[a:b], home)

    def _join_landed(self, sections: list):
        """A shard's sections as the one array the step takes of it."""
        if len(sections) == 1:
            return sections[0]
        with _DISPATCH_LOCK:
            with obs_trace.span("worker/h2d", bytes=0,
                                joined=len(sections)):
                return self._join(*sections)

    def lend_store(self) -> _Loan:
        """Where a pull may land the parameters the NEXT step uploads:
        ``{name: writable float32 view of the layout's slot}`` in the
        upload buffer whose turn is next (the other one may still be
        aliased by the running step's input on the CPU client), with
        :meth:`_Loan.land` to land a tensor through: what lands that way
        goes to the device section by section as it lands, and a store
        made of the views it returns is taken where it lies
        (:meth:`_pack`), from the device where it is whole there already
        (:meth:`_dispatch_step`).  Whoever keeps a view, or any slice of
        it, keeps that buffer, and the next loan of its turn allocates
        (``worker.pull.fresh_bytes``)."""
        turn = self._pack_turn
        self._loans[turn] = None      # with its views and its sections
        loan = self._loans[turn] = _Loan(self, self._writable(turn))
        return loan

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
        loan = self._loans[turn]
        if cost[turn] < self._packed_size:
            # part of the store lies here already: params holds the views
            flat = np.frombuffer(self._pack_bufs[turn], np.float32)
        else:
            # written whole: the loan's views would cost a new buffer,
            # and nothing it uploaded is what this step takes
            loan = self._loans[turn] = None
            flat = self._writable(turn)
        self._pack_turn = turn ^ 1
        for name, off, size, _shape, _dtype in copy[turn]:
            flat[off:off + size] = np.asarray(
                params[name], np.float32).ravel()
            if loan is not None:
                loan.spoil(off, size)
        _obs_copied_bytes.add(4 * cost[turn])
        return flat

    def _dispatch_step(self, params: Mapping[str, np.ndarray], batch):
        """Pack + upload + launch the jitted step; returns the (async)
        flat device output without fetching it, and beside it the
        uploaded input (the step does not donate it) while spans are
        recorded, None otherwise: what :func:`_wait_for_upload` takes.

        The input's shards are those the loan of that buffer put on the
        device as the store landed, where nothing has written the buffer
        since (``worker.upload.streamed_bytes``), and every other one is
        put here, whole.  Both loans end here, so that no section of a
        store this step does not take stays on the device beside it."""
        with obs_trace.span("worker/pack", bytes=4 * self._padded_in):
            packed = self._pack(params)
        loan = self._loans[self._pack_turn ^ 1]    # of the buffer taken
        self._loans = [None, None]
        early = loan.take() if loan is not None else {}
        streamed = 4 * sum(self._ranges[k][1] - self._ranges[k][0]
                           for k in early)
        _obs_upload_bytes.add(4 * self._padded_in)
        _obs_streamed_bytes.add(streamed)
        with _DISPATCH_LOCK:
            with obs_trace.span("worker/h2d",
                                bytes=4 * self._padded_in - streamed):
                shards = [early.pop(k) if k in early
                          else jax.device_put(packed[start:stop], home)
                          for k, (start, stop, home)
                          in enumerate(self._ranges)]
                batch = self._shard_batch(batch)
            with obs_trace.span("worker/dispatch"):
                flat = (shards[0] if len(shards) == 1 else
                        jax.make_array_from_single_device_arrays(
                            (self._padded_in,), self._flat_sharding, shards))
                out = self._step(flat, batch)
        return out, (flat if obs_trace.enabled() else None)

    def compute_gradients(self, params: Mapping[str, np.ndarray],
                          batch) -> tuple[TensorStore, float]:
        """params (host store) + batch -> (gradient store, loss).

        The packed params go up by sections, one D2H fetch brings loss
        and packed grads, regardless of tensor count."""
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
