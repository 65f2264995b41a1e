"""Worker runtime: discovery, registration, heartbeats, train loop.

Re-design of the reference `Worker` (reference: src/worker.cpp,
include/worker.h:25-33).  Protocol behavior preserved:

- discovery: ask the coordinator for the PS address, then register
  (reference: src/worker.cpp:108-122, 141-186)
- `query_with_retry`: up to 5 attempts, exponential backoff 100 ms * 2^n
  (reference: src/worker.cpp:129-139)
- heartbeat thread every 5 s reporting WorkerStatus
  (reference: src/worker.cpp:231-238)
- run_iteration: pull -> compute -> push -> poll sync status every 50 ms up
  to 200 polls, 3 outer retries (reference: src/worker.cpp:331-406).
  Against a framework PS the whole communication tail collapses into ONE
  fused ``PushPullStream`` round (push + barrier + pull — the server
  answers the instant aggregation completes instead of being polled), the
  gradients stream out in lazily-D2H-fetched buckets
  (trainer.GradientBuckets), the returned parameters are cached for the
  next iteration's "pull", and the next batch prefetches during
  communication.  All of it degrades to the reference-shaped serial
  protocol against a reference PS (per-connection UNIMPLEMENTED fallback,
  rpc/data_plane.py).
- `reconnect()` re-runs discovery+registration (reference: src/worker.cpp:124-127)
- checkpoint restore request at startup (reference: src/worker.cpp:289-314)

Departures:

- gradients come from a real jitted model step (Trainer), not the 0.01 stub;
- when the PS holds no parameters yet, the worker seeds it with a
  deterministic model init instead of fabricating a dummy 10x10 tensor
  (reference: src/worker.cpp:346-353);
- one persistent channel per peer instead of a fresh channel per call.
"""

from __future__ import annotations

import concurrent.futures
import logging
import os
import socket
import threading
import time
from typing import Callable, Iterator

import grpc
import numpy as np

from ..config import WorkerConfig
from ..core.tensor import TensorStore, to_wire
from ..obs import MetricsLogger, StepTimer, flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from ..obs.export import snapshot_blob
from ..rpc import messages as m
from ..rpc.data_plane import PSClient
from ..rpc.service import RpcClient
# the per-tier error-feedback stage (tiers/ef.py, ISSUE 9): the PS-leg
# residual below and the tier legs (worker→leaf, leaf→PS) are all
# instances of the same stage — one residual per compression point.
# error_feedback_enabled is re-exported here for back-compat (it lived
# in this module through PR 8).
from ..tiers.ef import ErrorFeedback, error_feedback_enabled  # noqa: F401

log = logging.getLogger("pst.worker")


class WorkerError(RuntimeError):
    pass


def _is_stale_shard_map(push) -> bool:
    """A live-reshard rejection that escaped the sharded client's own
    repartition replay (replication/messages.py STALE_SHARD_MAP) — NOT
    the bounded-staleness 'stale push' rejection of async mode."""
    from ..replication.messages import STALE_SHARD_MAP
    return STALE_SHARD_MAP in (push.message or "")


class Worker:
    def __init__(self, config: WorkerConfig, trainer,
                 batches: Iterator, start_heartbeat: bool = True):
        if config.wire_dtype not in m.WIRE_DTYPE_NAMES:
            raise ValueError(
                f"unknown wire_dtype {config.wire_dtype!r}; "
                f"options: {sorted(m.WIRE_DTYPE_NAMES)}")
        if not 0.0 < config.topk_density <= 1.0:
            # a percent-style typo (--topk-density=2) would otherwise
            # emit a k larger than the serialized pairs
            raise ValueError(f"topk_density must be in (0, 1], "
                             f"got {config.topk_density}")
        self.config = config
        self.trainer = trainer
        self.batches = batches
        self.status = m.WorkerStatus.IDLE
        self.iteration = -1  # last completed iteration
        self.last_loss = float("nan")
        metrics_path = os.environ.get("PSDT_METRICS_FILE") or None
        self.metrics = MetricsLogger(
            metrics_path and metrics_path.replace("%d", str(config.worker_id)))
        self.step_timer = StepTimer()
        # step-phase breakdown + retry accounting (obs registry; snapshots
        # ride heartbeats to the coordinator — obs/export.py).  "fused" is
        # the single push→barrier→pull round of the pipelined data plane.
        self._obs_phase = {name: obs_stats.histogram(f"worker.{name}_s")
                           for name in ("step", "data", "pull", "compute",
                                        "push", "fused", "barrier_wait")}
        self._obs_retries = obs_stats.counter("rpc.client.retries")
        # uncompressed f32 size of pushed gradients — the NUMERATOR of the
        # wire-compression ratio in the status rollup ...
        self._obs_push_payload = obs_stats.counter(
            "rpc.client.push.payload_bytes")
        # ... and the matching denominator: the bytes those tensors
        # actually encode to on the wire (int8/topk shrink it), counted
        # uniformly across the unary/stream/fused push paths
        self._obs_push_wire = obs_stats.counter(
            "rpc.client.push.wire_bytes")
        # bytes of served parameters that went to memory this worker had
        # to allocate (the trainer adds the upload buffers it had to take
        # in place of held ones): stands in steady state over the f32 wire
        self._obs_pull_fresh = obs_stats.counter("worker.pull.fresh_bytes")
        self._coordinator = RpcClient(config.coordinator_address,
                                      m.COORDINATOR_SERVICE, m.COORDINATOR_METHODS)
        self._ps: RpcClient | None = None
        self._ps_address: str | None = None
        self._total_workers = 0
        self._requested_wire_dtype = m.WIRE_DTYPE_NAMES[config.wire_dtype]
        # PS-leg error-feedback stage (see _ef_residual property below);
        # must exist before _reset_wire_negotiation resets it
        self._push_ef = ErrorFeedback()
        # hierarchical aggregation (tiers/group_client.py): built at
        # discovery when enabled and the topology supports it
        self._tier = None
        self._reset_wire_negotiation()
        self.last_bootstrap = False  # True iff the last iteration seeded the PS
        # Parameters delivered by the previous iteration's fused round —
        # they ARE what a pull at the next iteration would return, so the
        # next step skips its pull entirely.
        self._next_params: TensorStore | None = None
        # one-shot note when the fused rounds start riding the same-host
        # shared-memory transport (rpc/shm_transport.py) instead of TCP
        self._shm_noted = False
        # single-slot batch prefetch: next(self.batches) runs on this
        # thread while the worker is blocked in communication
        self._prefetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1,
            thread_name_prefix=f"worker-{config.worker_id}-prefetch")
        self._prefetched: concurrent.futures.Future | None = None
        self._stop = threading.Event()
        # Elastic membership (elastic/, ISSUE 13): announce join after
        # registration, poll own state at heartbeat cadence (a
        # coordinator-side `pst-ctl drain` flips it to DRAINING), and
        # announce leave at shutdown so the barrier narrows immediately
        # instead of waiting out a stale-heartbeat reap.  None until
        # discovery; a reference coordinator latches it unsupported.
        self._membership = None
        # graceful-preemption latch (SIGTERM handler / drain poll): the
        # run loop finishes the in-flight iteration, then stops
        self._drain = threading.Event()
        if flight.enabled():
            # label this process's flight ring (real multi-process runs;
            # in-process test topologies share one ring, last label wins)
            flight.set_role(f"worker:{config.worker_id}")
        self._heartbeat_thread: threading.Thread | None = None
        if start_heartbeat:
            self._heartbeat_thread = threading.Thread(
                target=self._heartbeat_loop, daemon=True,
                name=f"worker-{config.worker_id}-heartbeat")
            self._heartbeat_thread.start()

    # ------------------------------------------------------------ lifecycle
    def initialize(self) -> None:
        """Discover PS + register (reference: src/worker.cpp:108-122)."""
        self._discover_parameter_server()
        self._register()

    def reconnect(self) -> None:
        """reference: src/worker.cpp:124-127."""
        self.initialize()

    def request_drain(self) -> None:
        """Graceful-preemption request (SIGTERM handler, or the
        coordinator's DRAINING state seen by the heartbeat poll): finish
        the in-flight iteration, then stop.  Safe from any thread."""
        if not self._drain.is_set():
            self._drain.set()
            flight.record("elastic.drain", worker=self.config.worker_id,
                          note="worker")

    @property
    def drain_requested(self) -> bool:
        return self._drain.is_set()

    def shutdown(self) -> None:
        if self._stop.is_set():
            # idempotent: drain flows (graceful preemption) shut a
            # worker down as soon as it leaves, and the owning harness
            # routinely shuts everything down again on exit — a second
            # call must not touch the already-closed channels
            return
        self._stop.set()
        if self._heartbeat_thread is not None:
            self._heartbeat_thread.join(timeout=2.0)
        # one parting heartbeat: runs shorter than heartbeat_period_s
        # would otherwise never deliver a metric snapshot, and even long
        # runs would leave the coordinator's rollup missing the tail
        # since the last periodic beat (obs/export.py piggyback)
        self.send_heartbeat()
        if self._membership is not None:
            # graceful deregistration: the registry drops us NOW and the
            # elastic barrier narrows at the next width refresh (the
            # membership generation bump makes that immediate) instead
            # of a 30 s stale-heartbeat reap
            try:
                self._membership.leave()
            finally:
                self._membership.close()
                self._membership = None
        self._prefetch_pool.shutdown(wait=False)
        if self._tier is not None:
            self._tier.close()
            self._tier = None
        self._coordinator.close()
        if self._ps is not None:
            self._ps.close()

    # ------------------------------------------------------------ discovery
    def _discover_parameter_server(self) -> None:
        resp = self.query_with_retry(
            lambda: self._coordinator.call("GetParameterServerAddress",
                                           m.GetPSAddressRequest(), timeout=5.0))
        self._ps_address = f"{resp.address}:{resp.port}"
        if self._ps is not None:
            self._ps.close()
        # Replication extension (replication/failover.py): fetch the
        # epoch-numbered shard map.  A reference coordinator answers
        # UNIMPLEMENTED (shard_map.supported stays False) and the worker
        # keeps the static discovery topology — no failover, exactly the
        # pre-replication behavior.
        from ..replication.failover import ShardMapClient
        shard_map = ShardMapClient(self.config.coordinator_address,
                                   worker_id=self.config.worker_id)
        has_map = shard_map.refresh()
        primaries = shard_map.primaries() if has_map else []
        if has_map and primaries and (len(primaries) > 1
                                      or shard_map.has_backups()):
            # dynamic topology: the sharded client follows promotions and
            # reshards via the map (even at one shard, for hot failover)
            from .ps_shards import ShardedPSClient
            self._ps = ShardedPSClient(primaries, shard_map=shard_map)
            log.info("worker %d: %d PS shard(s) at %s (map epoch %d, "
                     "failover %s)", self.config.worker_id, len(primaries),
                     primaries, shard_map.epoch,
                     "armed" if shard_map.has_backups() else "unarmed")
        elif len(resp.shards) > 1:
            # sharded store (extension field 3): fan pushes/pulls out per
            # tensor owner across all PS shards (worker/ps_shards.py)
            from .ps_shards import ShardedPSClient
            shard_map.close()
            self._ps = ShardedPSClient(list(resp.shards))
            log.info("worker %d: %d PS shards at %s", self.config.worker_id,
                     len(resp.shards), list(resp.shards))
        else:
            # PSClient: chunk-stream data plane with automatic unary
            # fallback against a reference PS (rpc/data_plane.py)
            shard_map.close()
            self._ps = PSClient(self._ps_address)
            log.info("worker %d: PS at %s", self.config.worker_id,
                     self._ps_address)
        self._reset_wire_negotiation()  # a new PS must re-prove packed support
        self._next_params = None  # cached params were the OLD PS's
        self._setup_tier()

    def _setup_tier(self) -> None:
        """Build the hierarchical-aggregation runtime (tiers/, ISSUE 9)
        when enabled and the topology supports it: single-PS fused data
        plane only — the sharded client owns its own fan-out weighting,
        and the tier would sit between the partitioner and the shards."""
        from ..tiers.topology import tiers_enabled

        if self._tier is not None:
            self._tier.close()
            self._tier = None
        if not (tiers_enabled(getattr(self.config, "tiers", None))
                and self.config.fused_step
                and getattr(self._ps, "supports_tiers", False)):
            return
        from ..tiers.group_client import TierClient
        trainer = self.trainer
        self._tier = TierClient(
            self.config.coordinator_address, self.config.worker_id,
            self._ps_address,
            host_id=getattr(self.config, "tier_host_id", "") or None,
            init_params_fn=(
                (lambda: trainer.init_params(seed=0))
                if trainer is not None else None),
            topk_density=self.config.topk_density,
            # forward the config tri-state: --tiers must work without
            # PSDT_TIERS exported in the worker's own environment
            enabled=getattr(self.config, "tiers", None))

    # The PS-leg residual dict, kept as an attribute-shaped view over the
    # ErrorFeedback stage for back-compat (tests and older call sites
    # poke `worker._ef_residual` directly).
    @property
    def _ef_residual(self) -> dict[str, np.ndarray]:
        return self._push_ef.residual

    @_ef_residual.setter
    def _ef_residual(self, value: dict[str, np.ndarray]) -> None:
        self._push_ef.residual = dict(value)

    def _reset_wire_negotiation(self) -> None:
        """Packed pushes start only after the connected PS proves it honors
        the packed extension (first non-empty pull served packed).  A
        reference PS skips the extension fields entirely, so pushing packed
        at it would silently aggregate empty gradients; the replacement PS
        after a crash may not honor what the previous one did."""
        self._wire_dtype = self._requested_wire_dtype
        self._peer_packed_ok = self._wire_dtype == m.WIRE_F32
        # int8 pushes carry quantization error forward (error feedback);
        # residuals are per-PS-connection state
        self._ef_residual = {}

    def _pull_wire_dtype(self) -> int:
        """Encoding requested for served parameters.  The lossy encodings
        (int8, topk) are for gradient pushes only — error feedback corrects
        their bias push-over-push, but repeatedly compressing the
        *parameters* on every pull would compound irrecoverable error, so
        those workers pull bf16."""
        if self._wire_dtype in (m.WIRE_INT8, m.WIRE_TOPK):
            return m.WIRE_BF16
        return self._wire_dtype

    def _register(self) -> None:
        info = m.WorkerInfo(worker_id=self.config.worker_id,
                            address=self.config.address,
                            port=self.config.port,
                            hostname=socket.gethostname())
        resp = self.query_with_retry(
            lambda: self._coordinator.call("RegisterWorker", info, timeout=5.0))
        if not resp.success:
            raise WorkerError(f"registration rejected: {resp.message}")
        self._total_workers = resp.total_workers
        log.info("worker %d registered (%d total)", self.config.worker_id,
                 resp.total_workers)
        self._announce_join()

    def _announce_join(self) -> None:
        """Membership join announce (elastic/, ISSUE 13): JOINING ->
        ACTIVE at the coordinator.  Builds the client lazily; a
        reference coordinator answers UNIMPLEMENTED and the client
        latches unsupported — membership stays advisory."""
        if self._membership is None:
            from ..elastic.membership import MembershipClient
            self._membership = MembershipClient(
                self.config.coordinator_address, self.config.worker_id)
        self._membership.join()

    def _poll_drain(self) -> None:
        """Heartbeat-cadence membership poll: a coordinator-side
        ``pst-ctl drain`` marked us DRAINING — latch the graceful
        preemption so the run loop stops after the in-flight
        iteration."""
        if self._membership is None or self._membership.supported is False \
                or self._drain.is_set():
            return
        from ..elastic import messages as emsg
        state = self._membership.poll_state()
        if state == emsg.MEMBER_DRAINING:
            log.warning("worker %d: coordinator requested drain",
                        self.config.worker_id)
            self.request_drain()

    # -------------------------------------------------------------- retries
    def query_with_retry(self, fn: Callable, attempts: int | None = None):
        """Exponential backoff wrapper (reference: src/worker.cpp:129-139)."""
        attempts = attempts or self.config.retry_max_attempts
        delay = self.config.retry_base_delay_s
        last_exc: Exception | None = None
        for attempt in range(attempts):
            try:
                return fn()
            except grpc.RpcError as exc:
                last_exc = exc
                self._obs_retries.add()
                if attempt < attempts - 1:
                    time.sleep(delay)
                    delay *= 2
        raise WorkerError(f"RPC failed after {attempts} attempts: {last_exc}")

    # ------------------------------------------------------------ heartbeat
    def _heartbeat_loop(self) -> None:
        """reference: src/worker.cpp:231-238.  Extension: if the coordinator
        no longer knows this worker (evicted after a long jit compile or a
        coordinator restart), re-register so the elastic barrier counts us
        again — the reference never calls its own reconnect()."""
        while not self._stop.wait(self.config.heartbeat_period_s):
            ok = self.send_heartbeat()
            self._poll_drain()
            if ok is False and self._total_workers > 0:
                log.warning("worker %d: heartbeat rejected, re-registering",
                            self.config.worker_id)
                try:
                    self._register()
                except WorkerError as exc:
                    log.warning("worker %d: re-registration failed: %s",
                                self.config.worker_id, exc)

    def send_heartbeat(self) -> bool | None:
        """True = accepted, False = coordinator rejected (unknown worker),
        None = coordinator unreachable."""
        try:
            resp = self._coordinator.call(
                "Heartbeat",
                m.HeartbeatRequest(worker_id=self.config.worker_id,
                                   status=self.status,
                                   # metric snapshot piggyback (extension
                                   # field; reference coordinators skip it)
                                   obs_snapshot=snapshot_blob(
                                       worker_id=self.config.worker_id)),
                timeout=5.0)
            return resp.success
        except grpc.RpcError:
            return None

    # ------------------------------------------------------------ data plane
    def _chunk_converter(self, local: TensorStore):
        """The ``on_chunk`` consumer of one attempt at a pull: wire
        tensors of one chunk to arrays in ``local`` (the decode leg of
        the round).

        Where the trainer lends the buffer its next step uploads
        (``Trainer.lend_store``) a served tensor goes from its frame into
        its slot there in ONE pass (``_Loan.land``, which also starts
        the upload of every section of the buffer that is whole by
        then), and ``local`` holds the slot, read-only: no new memory,
        and the step then takes the store where it lies, most of it on
        the device already.  The frame's view (``Tensor.borrow_array``)
        is let go before the next chunk is asked for.  A name the layout
        does not have, another size, an empty tensor, or a trainer that
        lends nothing is
        ``Tensor.to_array`` as ever, counted in
        ``worker.pull.fresh_bytes``; so is the array a packed or float64
        wire had to be unpacked into on its way to the slot.  The loan is
        taken once per attempt: a straggler thread of a failed sharded
        pull keeps its converter and with it that buffer, and the retry's
        loan is another."""
        lend = getattr(self.trainer, "lend_store", None)
        dest = lend() if lend is not None else {}
        fresh = self._obs_pull_fresh

        def convert_chunk(tensors) -> None:
            with obs_trace.span("rpc/client/decode", tensors=len(tensors)):
                for t in tensors:
                    slot = dest.get(t.name)
                    src = None if slot is None else t.borrow_array()
                    if src is None or src.size != slot.size or not src.size:
                        local[t.name] = src = t.to_array()
                        fresh.add(src.nbytes)
                        continue
                    if src.flags.writeable:
                        fresh.add(src.nbytes)
                    local[t.name] = dest.land(t.name, src)
        return convert_chunk

    def pull_parameters(self, iteration: int) -> tuple[int, TensorStore]:
        """reference: src/worker.cpp:240-252."""
        t0 = time.perf_counter()
        with obs_trace.span("worker/pull", iteration=iteration):
            result = self._pull_parameters(iteration)
        self._obs_phase["pull"].observe(time.perf_counter() - t0)
        return result

    def _pull_parameters(self, iteration: int) -> tuple[int, TensorStore]:
        def attempt():
            # a FRESH store per attempt: after a sharded-pull failure,
            # the other shards' fan-out threads may still be streaming
            # chunks of the FAILED attempt — they write into the old
            # dict, never into this retry's
            local: TensorStore = {}

            # Version-aware pull (delta/, ISSUE 10): advertise the held
            # version and let the PS answer O(changed bytes).  The
            # client returns None whenever the plain protocol must run
            # (disabled, reference PS, permanent downgrade).
            delta_fn = getattr(self._ps, "delta_pull", None)
            if delta_fn is not None:
                result = delta_fn(
                    m.PullRequest(worker_id=self.config.worker_id,
                                  iteration=iteration,
                                  wire_dtype=self._pull_wire_dtype()),
                    timeout=30.0)
                if result is not None and result.store is not None:
                    return result.update, result.store
            resp = self._ps.pull_parameters(
                m.PullRequest(worker_id=self.config.worker_id,
                              iteration=iteration,
                              wire_dtype=self._pull_wire_dtype()),
                # converted per chunk AS IT ARRIVES, overlapping the
                # transport of later chunks (rpc/data_plane.py on_chunk)
                timeout=30.0, on_chunk=self._chunk_converter(local))
            return resp, local

        resp, store = self.query_with_retry(attempt)
        if resp is not None:
            # a delta-served round carries no wire tensors (resp is None)
            # and leaves the proven packed negotiation untouched
            self._note_pull_tensors(resp.parameters)
            iteration = resp.iteration if resp.iteration else iteration
        return iteration, store

    def _note_pull_tensors(self, parameters) -> None:
        """Feed one pull response's tensor metadata into the packed-wire
        negotiation.  Called on every path that receives served parameters
        (unary/streamed pull AND the fused push-pull round)."""
        if not self._peer_packed_ok and parameters:
            if any(t.packed_dtype != m.WIRE_F32 for t in parameters):
                self._peer_packed_ok = True
            else:
                # Server ignored the extension (reference PS): stay on the
                # reference-compatible f32 encoding rather than pushing
                # payloads the server cannot see.
                log.warning(
                    "worker %d: PS does not support wire_dtype=%s, "
                    "falling back to f32", self.config.worker_id,
                    self.config.wire_dtype)
                self._wire_dtype = m.WIRE_F32
                self._peer_packed_ok = True
        elif self._peer_packed_ok and self._wire_dtype != m.WIRE_F32:
            # Negotiation was proven against the PREVIOUS process at this
            # address.  A PS that crashed and restarted is reached again via
            # transparent gRPC channel reconnection — never re-entering
            # _discover_parameter_server — so stale proof must be dropped
            # whenever a pull stops looking packed: an empty pull (restarted
            # PS lost its store; our next push may seed it and must not be
            # quantized) or a non-empty pull served entirely unpacked (a
            # replacement PS that ignores the extension would silently see
            # empty gradients in our packed pushes).
            if not parameters or all(
                    t.packed_dtype == m.WIRE_F32 for t in parameters):
                log.warning(
                    "worker %d: pull no longer packed (PS restart?), "
                    "re-negotiating wire encoding", self.config.worker_id)
                self._reset_wire_negotiation()

    def push_gradients(self, iteration: int, grads: TensorStore) -> m.PushResponse:
        """reference: src/worker.cpp:254-272."""
        t0 = time.perf_counter()
        with obs_trace.span("worker/push", iteration=iteration):
            resp = self._push_gradients(iteration, grads)
        self._obs_phase["push"].observe(time.perf_counter() - t0)
        return resp

    def _push_gradients(self, iteration: int, grads: TensorStore) -> m.PushResponse:
        # Retry invariant the PS-side streaming aggregation depends on:
        # query_with_retry replays the SAME payload (same grads, same
        # error-feedback residual — committed only after acceptance), so
        # the server's per-(worker, tensor) dedup makes a retry of a push
        # that actually landed converge to exactly one contribution
        # (core/ps_core.py first-push-wins).
        self._obs_push_payload.add(
            sum(4 * int(np.asarray(g).size) for g in grads.values()))
        push_dtype = self._wire_dtype if self._peer_packed_ok else m.WIRE_F32
        new_residual = None
        if (push_dtype in (m.WIRE_INT8, m.WIRE_TOPK)
                and error_feedback_enabled()):
            tensors, new_residual = self._compress_with_feedback(
                grads, push_dtype)
        else:
            tensors = to_wire(grads, push_dtype,
                              topk_density=self.config.topk_density)
        # actual wire footprint of the payloads (packed encodings shrink
        # it) so the --metrics compression ratio is truthful
        self._obs_push_wire.add(sum(t.encoded_size() for t in tensors))
        update = m.GradientUpdate(worker_id=self.config.worker_id,
                                  iteration=iteration, gradients=tensors)
        resp = self.query_with_retry(
            lambda: self._ps.push_gradients(update, timeout=30.0))
        if new_residual is not None and resp.success:
            # commit the carried error only for pushes the PS accepted — a
            # rejected (stale) push's gradient was discarded whole, so its
            # quantization error must not leak into the next push
            self._ef_residual = new_residual
        return resp

    def _compress_with_feedback(
            self, grads: TensorStore, wire_dtype: int) -> tuple[list, dict]:
        """Lossy gradient compression with error feedback (1-bit-SGD /
        EF-SGD / Deep-Gradient-Compression style): each push sends
        compress(grad + residual) and carries the un-sent part — rounding
        error under int8, the whole non-top-k mass under topk — into the
        next push, so compression bias cancels over time instead of
        accumulating.  The residual is what the PS did NOT see: decoding
        the wire tensor gives exactly the server's view.  Implemented on
        the shared per-tier stage (tiers/ef.py) — this is the PS-leg
        instance; the caller commits the returned carry only after the
        PS accepts the push."""
        tensors = self._push_ef.compress(
            grads, wire_dtype, topk_density=self.config.topk_density)
        return tensors, self._push_ef.pending()

    # -------------------------------------------------------- fused data plane
    def _use_fused(self) -> bool:
        return (self.config.fused_step and self._ps is not None
                and hasattr(self._ps, "push_pull"))

    def _wire_tensors(self, grads, push_dtype: int | None = None,
                      ef: ErrorFeedback | None = None):
        """Lazy wire-tensor producer for the fused push.

        ``grads``: a mapping OR a lazy ``(name, array)`` iterable
        (trainer.GradientBuckets — each re-iteration replays from its
        host-side cache).  Returns ``(tensors_fn, ef_stage)``:
        ``tensors_fn()`` yields wire tensors one by one — compression +
        error-feedback adjustment happen per tensor AS the RPC sender
        consumes it, so D2H fetch ⊕ compress ⊕ encode ⊕ transport
        pipeline per bucket.  ``ef_stage`` (non-None under a lossy
        encoding with feedback on) holds the staged residual; the caller
        ``commit()``s it only after the receiver accepts the push.

        ``push_dtype``/``ef`` default to the PS-leg negotiation and the
        PS-leg stage; the tier rounds pass their own (tiers/, ISSUE 9 —
        one residual per compression point).

        Replays are payload-identical: a retry re-reads the same gradients
        (GradientBuckets' host-side cache) against the same committed
        residual, which is what lets the receiving aggregator dedup a
        retried push per (worker, tensor) instead of double-counting it
        (core/ps_core.py first-push-wins)."""
        if push_dtype is None:
            push_dtype = (self._wire_dtype if self._peer_packed_ok
                          else m.WIRE_F32)
        compress = push_dtype in (m.WIRE_INT8, m.WIRE_TOPK)
        stage = ef if ef is not None else self._push_ef
        use_ef = compress and stage.on()
        ef_stage: ErrorFeedback | None = stage if use_ef else None

        def tensors():
            if ef_stage is not None:
                ef_stage.begin()  # a retry replays from scratch
            payload = wire = 0
            pairs = grads.items() if hasattr(grads, "items") else grads
            for name, g in pairs:
                g = np.asarray(g, np.float32)
                payload += 4 * g.size
                if compress:
                    adjusted = (ef_stage.adjust(name, g) if ef_stage
                                else g)
                    with obs_trace.span("rpc/client/encode", tensor=name,
                                        bytes=4 * g.size):
                        t = m.Tensor.from_array(
                            name, adjusted, wire_dtype=push_dtype,
                            topk_density=self.config.topk_density)
                    if ef_stage is not None:
                        # what the receiver did NOT see carries into the
                        # next push
                        ef_stage.stage(name, adjusted, t)
                else:
                    with obs_trace.span("rpc/client/encode", tensor=name,
                                        bytes=4 * g.size):
                        t = m.Tensor.from_array(name, g,
                                                wire_dtype=push_dtype)
                wire += t.encoded_size()
                yield t
            self._obs_push_payload.add(payload)
            self._obs_push_wire.add(wire)

        return tensors, ef_stage

    def _tier_push_pull(self, tier, iteration: int, grads
                        ) -> tuple[m.PushResponse, TensorStore] | None:
        """One fused round via the group's leaf aggregator (tiers/,
        ISSUE 9): same wire protocol, the peer is the elected same-host
        leaf instead of the PS — this leg usually rides the shm rings.
        Returns None when the round did not deliver (the caller replays
        the SAME iteration on the flat path; the PS's member cover and
        per-(worker, tensor) dedup make that replay exact): a soft miss
        (leaf not armed yet / leaf barrier timeout) keeps the tier for
        the next round, a transport error (leaf death) or repeated
        misses downgrade it permanently."""
        tensors_fn, ef_stage = self._wire_tensors(
            grads, push_dtype=tier.push_dtype, ef=tier.push_ef)
        local: TensorStore = {}
        convert_chunk = self._chunk_converter(local)
        t0 = time.perf_counter()
        flight.record("fused.start", iteration=iteration,
                      worker=self.config.worker_id)
        push = params = None
        try:
            with obs_trace.span("worker/tier_fused", iteration=iteration):
                push, params = tier.client.push_pull(
                    self.config.worker_id, iteration, tensors_fn,
                    pull_wire_dtype=self._pull_wire_dtype(),
                    timeout=self.config.fused_timeout_s,
                    on_chunk=convert_chunk)
        except grpc.RpcError as exc:
            tier.downgrade(f"leaf transport error: {exc.__class__.__name__}")
            return None
        finally:
            flight.record("fused.end", iteration=iteration,
                          worker=self.config.worker_id,
                          a=int(1e6 * (time.perf_counter() - t0)),
                          b=1 if params is not None else 0)
        if push.success and params is not None:
            self._obs_phase["fused"].observe(time.perf_counter() - t0)
            tier.note_success()
            if ef_stage is not None:
                ef_stage.commit()
            # deliberately NOT fed into _note_pull_tensors: the leaf
            # proving packed support says nothing about the PS this
            # worker would push to after a downgrade
            return push, local
        if not push.success and tier.is_soft_refusal(push.message):
            tier.soft_failure((push.message or "leaf refusal")[:80])
        elif push.success:
            tier.soft_failure("leaf barrier timeout")
        else:
            tier.downgrade(f"leaf rejected push: {push.message}")
        return None

    def _fused_push_pull(self, iteration: int,
                         grads) -> tuple[m.PushResponse, TensorStore | None]:
        """One fused push→barrier→pull round.  Returns the push verdict
        plus the fresh post-aggregation parameter store, or ``None`` for
        the store when the fused round did not deliver one (reference
        server, server-side barrier timeout) — the caller then falls back
        to the serial barrier-poll + pull.

        With an active tier assignment the round rides the group's leaf
        aggregator first; any miss there falls through to the flat round
        below for the SAME iteration (``grads`` is replayable by
        contract, and the PS-side dedup absorbs overlap)."""
        tier = self._tier
        if tier is not None and tier.maybe_activate():
            result = self._tier_push_pull(tier, iteration, grads)
            if result is not None:
                return result
        tensors_fn, residual_box = self._wire_tensors(grads)

        def attempt():
            # Version-aware fused round first (delta/, ISSUE 10): one
            # PushPullDeltaStream round whose response is O(changed
            # bytes) against the client's cached pull.  None = run the
            # plain fused round (disabled, downgraded, shm-preferred);
            # a mid-round downgrade also returns None and the plain
            # replay below is exact (PS-side per-(worker,tensor) dedup).
            delta_fn = getattr(self._ps, "delta_push_pull", None)
            if delta_fn is not None:
                result = delta_fn(
                    self.config.worker_id, iteration, tensors_fn,
                    pull_wire_dtype=self._pull_wire_dtype(),
                    timeout=self.config.fused_timeout_s)
                if result is not None:
                    push = (result.push if result.push is not None
                            else m.PushResponse(success=False,
                                                message="empty fused "
                                                        "response"))
                    return push, result.update, result.store

            # fresh store per attempt, same rationale as _pull_parameters
            local: TensorStore = {}
            push, params = self._ps.push_pull(
                self.config.worker_id, iteration, tensors_fn,
                pull_wire_dtype=self._pull_wire_dtype(),
                timeout=self.config.fused_timeout_s,
                on_chunk=self._chunk_converter(local))
            return push, params, (local if params is not None else None)

        t0 = time.perf_counter()
        flight.record("fused.start", iteration=iteration,
                      worker=self.config.worker_id)
        try:
            with obs_trace.span("worker/fused", iteration=iteration):
                push, params, store = self.query_with_retry(attempt)
        except BaseException:
            flight.record("fused.end", iteration=iteration,
                          worker=self.config.worker_id,
                          a=int(1e6 * (time.perf_counter() - t0)), b=0)
            raise
        flight.record("fused.end", iteration=iteration,
                      worker=self.config.worker_id,
                      a=int(1e6 * (time.perf_counter() - t0)),
                      b=1 if params is not None else 0)
        self._obs_phase["fused"].observe(time.perf_counter() - t0)
        if not self._shm_noted and getattr(self._ps, "shm_active", False):
            # the PSClient negotiated the same-host shared-memory rings
            # (rpc/shm_transport.py); every later fused round bypasses TCP
            self._shm_noted = True
            log.info("worker %d: fused data plane riding shared memory",
                     self.config.worker_id)
        if residual_box is not None and push.success:
            residual_box.commit()
        if store is None:
            return push, None
        if params is not None:
            # a delta-served round carries no wire tensors (params is
            # None); the proven packed negotiation stands
            self._note_pull_tensors(params.parameters)
        return push, store

    # ---------------------------------------------------------- batch stream
    def _next_batch(self):
        """The prefetched batch when one is ready, else a synchronous
        ``next()`` on the loader."""
        if self._prefetched is not None:
            fut, self._prefetched = self._prefetched, None
            return fut.result()
        return next(self.batches)

    def _start_batch_prefetch(self) -> None:
        """Kick ``next(self.batches)`` on the prefetch thread so data
        loading runs under the step's communication phase.  Single-slot:
        the iterator is only ever advanced by one party at a time."""
        if self._prefetched is None and not self._stop.is_set():
            try:
                self._prefetched = self._prefetch_pool.submit(
                    next, self.batches)
            except RuntimeError:  # pool shut down mid-run
                self._prefetched = None

    def _refresh_topology_on_partial(self) -> bool:
        """A partial pull may mean a live reshard moved tensors to shards
        this client does not know yet (not a shard restart): refresh the
        shard map if the client has one.  True when a map-backed re-pull
        is worth attempting (the topology may have changed, or the
        publish is moments away); False = no dynamic map, go re-seed."""
        refresh = getattr(self._ps, "refresh_topology", None)
        if refresh is None:
            return False
        try:
            refresh()
        except Exception:  # noqa: BLE001 — fall through to the re-seed path
            log.warning("worker %d: topology refresh failed",
                        self.config.worker_id, exc_info=True)
            return False
        shard_map = getattr(self._ps, "_shard_map", None)
        return shard_map is not None and shard_map.supported

    def check_sync_ready(self, iteration: int) -> m.SyncStatusResponse:
        """reference: src/worker.cpp:274-287."""
        return self.query_with_retry(
            lambda: self._ps.call("CheckSyncStatus",
                                  m.SyncStatusRequest(iteration=iteration),
                                  timeout=5.0))

    _expected_names: frozenset[str] | None = None

    def _expected_param_names(self) -> frozenset[str]:
        """The model's full parameter-name set (cached) — used to detect a
        PARTIAL pull under the sharded-PS topology, where one restarted
        shard loses its partition while the others still serve theirs."""
        if self._expected_names is None:
            self._expected_names = frozenset(self.trainer.init_params(seed=0))
        return self._expected_names

    def _seed_bootstrap(self, iteration: int, missing) -> float:
        """PS store empty (or, under the sharded topology, one shard
        restarted empty — the merged pull is then PARTIAL): every worker
        pushes the same deterministic init for the missing names; the PS
        bootstrap rule (first aggregated payload *becomes* the parameters
        — reference src/parameter_server.cpp:78-81) then lands exactly
        the init on the empty shard(s).  Replaces the reference's dummy
        10x10 fallback (src/worker.cpp:346-353).  Rides the plain push
        path deliberately: the fused data plane refuses to seed an empty
        store (server/ps_service.py PushPullStream)."""
        init = self.trainer.init_params(seed=0)
        if missing:
            # a replacement shard must also re-prove packed support
            # before quantized pushes resume
            self._reset_wire_negotiation()
            init = {name: init[name] for name in missing}
            log.warning(
                "worker %d: pull missing %d tensors (shard "
                "restart?), re-seeding deterministic init",
                self.config.worker_id, len(missing))
        else:
            log.info("worker %d: PS empty, pushing deterministic init",
                     self.config.worker_id)
        flight.record("boot.seed", iteration=iteration,
                      worker=self.config.worker_id, a=len(init))
        push = self.push_gradients(iteration, init)
        if not push.success:
            raise WorkerError(f"bootstrap push rejected: {push.message}")
        if not push.aggregation_complete:
            self._await_barrier(iteration)
        self.iteration = iteration
        self.last_bootstrap = True
        return float("nan")

    # ------------------------------------------------------------ train loop
    def run_freerun_iteration(self, iteration: int) -> float:
        """One free-running step (freerun/, ISSUE 16): take whatever
        parameters the previous round delivered (or pull the published
        snapshot), compute, push — and never wait.  The free-run PS
        applies every push on arrival damped by ``beta^staleness`` and
        answers it ``aggregation_complete=True`` (a version-vector
        deduped RPC retry answers success too), so there is NO barrier
        to poll and deliberately no fallback to one: a worker here is
        bounded only by its own compute plus one RPC round.  The fused
        data plane still collapses push + pull into one round, but its
        legs are independent — the response parameters are simply the
        PS's current published version, not a post-barrier promise."""
        self.status = m.WorkerStatus.TRAINING
        self.step_timer.__enter__()
        self.last_bootstrap = False
        t_step = time.perf_counter()
        step_span = obs_trace.span("worker/step", iteration=iteration,
                                   worker=self.config.worker_id)
        step_span.__enter__()
        flight.record("step.start", iteration=iteration,
                      worker=self.config.worker_id)
        try:
            params, self._next_params = self._next_params, None
            if params is None:
                _, params = self.pull_parameters(iteration)
            missing = (self._expected_param_names() - set(params)
                       if params else set())
            if not params or missing:
                # rides the plain push; the free-run PS answers it
                # complete=True so no barrier poll runs inside
                return self._seed_bootstrap(iteration, missing)

            t0 = time.perf_counter()
            batch = self._next_batch()
            t1 = time.perf_counter()
            self._obs_phase["data"].observe(t1 - t0)
            fused = self._use_fused()
            incremental = fused and hasattr(self.trainer,
                                            "compute_gradient_buckets")
            with obs_trace.span("worker/compute", iteration=iteration):
                if incremental:
                    grads = self.trainer.compute_gradient_buckets(params,
                                                                  batch)
                    loss = grads.loss
                else:
                    grads, loss = self.trainer.compute_gradients(params,
                                                                 batch)
            self._obs_phase["compute"].observe(time.perf_counter() - t1)
            self.last_loss = loss
            self._start_batch_prefetch()

            if fused:
                push, fresh = self._fused_push_pull(iteration, grads)
                if fresh is not None:
                    self._next_params = fresh
            else:
                push = self.push_gradients(iteration, grads)
            if not push.success:
                raise WorkerError(f"push rejected: {push.message}")
            self.iteration = max(self.iteration, iteration)
            return loss
        finally:
            step_span.__exit__(None, None, None)
            flight.record("step.end", iteration=iteration,
                          worker=self.config.worker_id,
                          a=int(1e6 * (time.perf_counter() - t_step)))
            self._obs_phase["step"].observe(time.perf_counter() - t_step)
            self.status = m.WorkerStatus.IDLE
            self.step_timer.__exit__()
            self.metrics.log(step=self.iteration, loss=self.last_loss,
                             step_time_s=self.step_timer.summary().get("last_s"))

    def run_iteration(self, iteration: int) -> float:
        """One synchronous training step (reference: src/worker.cpp:331-406
        is pull -> compute -> push -> 50 ms barrier polls).  Returns the
        loss.  Against a framework PS the communication tail is ONE fused
        PushPullStream round whose response both closes the barrier and
        delivers the next iteration's parameters (cached, so the next
        step's pull is free); against a reference PS every leg degrades to
        the serial unary protocol.  Under ``config.freerun`` the step is
        the barrier-free loop above instead — routed here so every
        caller (run(), the CLI main, tests) picks the mode up from the
        config alone."""
        if getattr(self.config, "freerun", False):
            return self.run_freerun_iteration(iteration)
        self.status = m.WorkerStatus.TRAINING
        self.step_timer.__enter__()
        self.last_bootstrap = False
        t_step = time.perf_counter()
        # the step span roots the distributed trace: the pull/push/barrier
        # client spans nest under it, and their contexts ride the RPC
        # extension field so the PS-side handler spans share its trace id
        step_span = obs_trace.span("worker/step", iteration=iteration,
                                   worker=self.config.worker_id)
        step_span.__enter__()
        flight.record("step.start", iteration=iteration,
                      worker=self.config.worker_id)
        try:
            params, self._next_params = self._next_params, None
            if params is None:
                _, params = self.pull_parameters(iteration)
            missing = (self._expected_param_names() - set(params)
                       if params else set())
            for _ in range(3 if missing else 0):
                # the "missing" tensors may have moved in a live reshard
                # rather than been lost: refresh the shard map and
                # re-pull (a few times — the handoff publishes the new
                # map moments after the old owner stops serving) before
                # concluding a shard restarted empty and re-seeding
                if not self._refresh_topology_on_partial():
                    break
                _, params = self.pull_parameters(iteration)
                missing = (self._expected_param_names() - set(params)
                           if params else set())
                if not missing:
                    break
                time.sleep(0.3)
            if not params or missing:
                return self._seed_bootstrap(iteration, missing)

            effective_it = iteration
            fused = self._use_fused()
            incremental = fused and hasattr(self.trainer,
                                            "compute_gradient_buckets")
            fresh: TensorStore | None = None
            for attempt in range(3):
                t0 = time.perf_counter()
                batch = self._next_batch()
                t1 = time.perf_counter()
                self._obs_phase["data"].observe(t1 - t0)
                with obs_trace.span("worker/compute", iteration=effective_it):
                    if incremental:
                        # gradients stay on device; reading .loss blocks on
                        # the jitted step (+ bucket 0's D2H) while the
                        # remaining buckets fetch lazily INSIDE the fused
                        # RPC, overlapping encode/transport per bucket
                        grads = self.trainer.compute_gradient_buckets(
                            params, batch)
                        loss = grads.loss
                    else:
                        grads, loss = self.trainer.compute_gradients(params,
                                                                     batch)
                self._obs_phase["compute"].observe(time.perf_counter() - t1)
                self.last_loss = loss
                # the next batch loads while this thread blocks on the PS
                self._start_batch_prefetch()

                if fused:
                    push, fresh = self._fused_push_pull(effective_it, grads)
                else:
                    push = self.push_gradients(effective_it, grads)
                if push.success:
                    break
                if _is_stale_shard_map(push) and attempt < 2:
                    # a live reshard outran the client's map AND the
                    # client could not refresh it (coordinator
                    # unreachable / no map support): re-discover the
                    # topology from scratch and retry the iteration
                    log.warning(
                        "worker %d: shard map stale at iteration %d and "
                        "refresh failed; re-discovering topology",
                        self.config.worker_id, effective_it)
                    self._discover_parameter_server()
                    _, params = self.pull_parameters(effective_it)
                    continue
                if ("stale" in push.message
                        and not _is_stale_shard_map(push) and attempt < 2):
                    # bounded-staleness rejection (async mode): fast-forward
                    # to the PS's current iteration, re-pull fresh params,
                    # recompute, retry — no reference analogue (its protocol
                    # is strictly synchronous)
                    log.info("worker %d: stale at iteration %d, "
                             "fast-forwarding to %d", self.config.worker_id,
                             effective_it, push.iteration)
                    effective_it = max(push.iteration, effective_it + 1)
                    _, params = self.pull_parameters(effective_it)
                    continue
                if fused and "store empty" in push.message:
                    # the PS (or one shard) restarted empty under our cached
                    # params and refused to bootstrap from a fused gradient
                    # push.  Re-pull to see what is actually missing: empty
                    # or partial -> seed the deterministic init exactly like
                    # a start-of-step detection; complete -> another worker
                    # already re-seeded, retry with fresh params.
                    log.warning(
                        "worker %d: fused push refused (PS store empty — "
                        "restart?), re-pulling to re-seed",
                        self.config.worker_id)
                    self._reset_wire_negotiation()
                    _, params = self.pull_parameters(effective_it)
                    missing = (self._expected_param_names() - set(params)
                               if params else set())
                    if not params or missing:
                        return self._seed_bootstrap(effective_it, missing)
                    if attempt < 2:
                        continue
                raise WorkerError(f"push rejected: {push.message}")
            if fresh is not None:
                # the fused response IS the next iteration's pull
                self._next_params = fresh
            elif not push.aggregation_complete:
                self._await_barrier(effective_it)
            self.iteration = effective_it
            return loss
        finally:
            step_span.__exit__(None, None, None)
            flight.record("step.end", iteration=iteration,
                          worker=self.config.worker_id,
                          a=int(1e6 * (time.perf_counter() - t_step)))
            self._obs_phase["step"].observe(time.perf_counter() - t_step)
            self.status = m.WorkerStatus.IDLE
            self.step_timer.__exit__()
            self.metrics.log(step=self.iteration, loss=self.last_loss,
                             step_time_s=self.step_timer.summary().get("last_s"))

    def _await_barrier(self, iteration: int) -> None:
        """Poll CheckSyncStatus: 50 ms period, <=200 polls, 3 outer retries
        (reference: src/worker.cpp:372-389)."""
        t0 = time.perf_counter()
        with obs_trace.span("worker/barrier_wait", iteration=iteration):
            try:
                self._await_barrier_inner(iteration)
            finally:
                self._obs_phase["barrier_wait"].observe(
                    time.perf_counter() - t0)

    def _await_barrier_inner(self, iteration: int) -> None:
        # resp survives the poll loop: with sync_poll_max == 0 no poll ever
        # runs and the progress report below must not blow up unbound
        resp: m.SyncStatusResponse | None = None
        for outer in range(self.config.sync_outer_retries):
            for _ in range(self.config.sync_poll_max):
                resp = self.check_sync_ready(iteration)
                if resp.ready:
                    return
                time.sleep(self.config.sync_poll_period_s)
            log.warning("worker %d: barrier timeout at iteration %d "
                        "(%s), retry %d",
                        self.config.worker_id, iteration,
                        self._barrier_progress(resp), outer + 1)
            time.sleep(0.5)
        raise WorkerError(f"barrier never completed for iteration "
                          f"{iteration} ({self._barrier_progress(resp)})")

    @staticmethod
    def _barrier_progress(resp: m.SyncStatusResponse | None) -> str:
        if resp is None:
            return "no status polled"
        return f"{resp.workers_received}/{resp.total_workers} received"

    def run(self, iterations: int | None = None) -> None:
        """Full training run (reference: src/worker_main.cpp:40-43).
        A drain request (SIGTERM / ``pst-ctl drain``) stops the loop
        BETWEEN iterations: the in-flight iteration completes — its
        barrier contribution is never abandoned half-streamed — and the
        caller's shutdown() deregisters so the barrier narrows."""
        total = iterations if iterations is not None else self.config.iterations
        for i in range(total):
            if self._drain.is_set():
                log.warning("worker %d: draining — stopping after "
                            "iteration %d", self.config.worker_id,
                            self.iteration)
                break
            # async fast-forwards may skip numbers; never re-push a completed
            # iteration
            it = max(i, self.iteration + 1)
            loss = self.run_iteration(it)
            log.info("worker %d iteration %d loss %.4f",
                     self.config.worker_id, it, loss)

    # ------------------------------------------------------------ checkpoint
    def load_checkpoint_from_server(self, path: str) -> bool:
        """Ask the PS to load a checkpoint into itself
        (reference: src/worker.cpp:289-314 — the worker does not keep the
        returned parameter copy)."""
        self.status = m.WorkerStatus.CHECKPOINTING
        try:
            resp = self.query_with_retry(
                lambda: self._ps.call("LoadCheckpoint",
                                      m.LoadCheckpointRequest(path=path),
                                      timeout=60.0))
            if resp.success:
                # cached params predate the restore; force a real pull
                self._next_params = None
                log.info("worker %d: PS restored checkpoint %s (epoch %d)",
                         self.config.worker_id, path, resp.epoch)
            else:
                log.warning("worker %d: checkpoint restore failed: %s",
                            self.config.worker_id, resp.message)
            return resp.success
        finally:
            self.status = m.WorkerStatus.IDLE
