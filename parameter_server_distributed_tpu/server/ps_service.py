"""Parameter-server gRPC service.

Wraps `ParameterServerCore` in the 5-RPC service of the reference
(reference: src/parameter_server_service.cpp, proto/parameter_server.proto:5-11)
and runs the periodic checkpoint daemon
(reference: src/parameter_server_service.cpp:150-169) via CheckpointManager.

Two server-side hot-path optimizations live here (ISSUE 3):

- **Per-chunk gradient folding**: the streaming push handlers feed each
  decoded chunk through a :class:`~..core.ps_core.PushSink` as it arrives,
  so decode ⊕ accumulate overlap the transport of later chunks and the
  core never buffers a whole per-worker gradient store (streaming
  aggregation mode — core/ps_core.py).  A sink that folds inside the
  call is lent the frame's own read-only views
  (``decode_gradients(borrow=True)``): a float32 gradient goes from its
  frame into the accumulator in one pass, with no copy between.
- **Encode-once broadcast cache**: served parameter chunks are encoded to
  wire bytes once per (params version, wire dtype, chunk budget) and
  replayed to every subsequent puller of the same version
  (:class:`EncodedServeCache`), so the post-barrier fan-out to N workers
  runs ONE `to_wire` encode instead of N.  The version key makes
  invalidation automatic: apply/restore/initialize bump the core's store
  version and the next serve re-encodes.  The bodies are built into
  buffers the cache owns and reuses from version to version (the store's
  shapes do not change, so neither do the bodies' sizes): at the sizes a
  store is chunked into, a new buffer per body is new address space and a
  page fault every 4 KB of it.
- **Stripe-parallel miss encode** (ISSUE 5): the one real encode per
  version fans its per-chunk payload passes across the shared stripe
  executor (core/stripes.py), so a multi-chunk store encodes on multiple
  cores; the produced wire bytes are identical to the serial encode's.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict
from typing import Callable

import grpc

from ..analysis.lock_order import checked_lock
from ..checkpoint.manager import CheckpointManager
from ..config import ParameterServerConfig
from ..core.optimizer import make_optimizer
from ..core.ps_core import ParameterServerCore, PushSink
from ..core.tensor import from_wire, to_wire
from ..delta import messages as dmsg
from ..delta.chain import DeltaChain, DeltaPair, wire_dtype_compatible
from ..obs import flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from ..replication import messages as rmsg
from ..replication import sharded_update as sharded_mod
from ..replication.replicator import (ReplicaSink, Replicator,
                                      flatten_optimizer_state, state_chunks)
from ..replication.sharded_update import ShardedUpdater, ShardedUpdateSink
from ..rpc import messages as m
from ..rpc import shm_transport
from ..rpc.data_plane import (PreEncodedParameterUpdate, decode_gradients,
                              encode_parameter_record_groups, split_tensors,
                              stream_chunk_bytes)
from ..rpc.service import bind_service, make_server
from ..utils.buffers import exported

log = logging.getLogger("pst.ps")

# rpc/wire.py's counter of encoder output that went to new memory: a body
# buffer the serve cache has to allocate is such output
_obs_fresh_bytes = obs_stats.counter("rpc.wire.fresh_bytes")


class _ServeCacheEntry:
    __slots__ = ("event", "bodies", "failed", "version", "buffers")

    def __init__(self):
        self.event = threading.Event()
        self.bodies: list[memoryview] | None = None
        self.failed = False
        # store version the bodies were ACTUALLY encoded at (may differ
        # from the probe key's when the store advanced mid-build) — the
        # delta protocol stamps it on full serves so the receiver's base
        # version is exact, never the probe's guess
        self.version = -1
        # the buffers the bodies are built into, one per body: those of
        # the entry this one retired (EncodedServeCache.lookup) until
        # take() finds one short, missing or still read
        self.buffers: list[bytearray] = []

    def take(self, place: int, size: int) -> memoryview:
        """A writable view of exactly ``size`` bytes for body ``place``:
        over the buffer the retired version's body lay in when nothing
        reads it any more (``utils.buffers.exported``) and it is large
        enough, over a new one otherwise.  Each place has one taker."""
        buffers = self.buffers
        buffers.extend([None] * (place + 1 - len(buffers)))
        buf = buffers[place]
        if buf is None or len(buf) < size or exported(buf):
            _obs_fresh_bytes.add(size)
            buf = buffers[place] = bytearray(size)  # zeroed: touched
        return memoryview(buf)[:size]


class EncodedServeCache:
    """Encode-once broadcast cache: encoded parameter-chunk bytes keyed by
    (params version, wire dtype, chunk budget).

    Single-flight per key: the first serve of a version encodes (the
    cache miss); concurrent serves of the same key wait for that encode
    and replay its bytes instead of racing N duplicate `to_wire` passes —
    the post-barrier fan-out is exactly the situation where N pullers
    arrive at once.  Entries for superseded versions are dropped on
    insert, so the cache holds at most the current version's encodings
    (one per requested wire dtype).

    A dropped entry hands the BUFFERS its bodies lay in to the entry of
    the same kind (wire dtype, chunk budget) that retires it, and the new
    version's bodies are built into them (``_ServeCacheEntry.take``).  A
    puller still streaming the retired version keeps the views it holds
    and with them the buffer, and the new entry allocates in its place:
    the cache keeps one generation of bodies, and a second only while
    someone still reads the one before.  So that two wire dtypes pulled
    side by side both find their buffers, an insert drops the entries of
    ANOTHER kind only when they are more than one version behind."""

    def __init__(self):
        # leaf rank: held only around dict ops, never while acquiring a
        # core lock (analysis/lock_order.py)
        self._lock = checked_lock("EncodedServeCache._lock")
        self._entries: dict[tuple, _ServeCacheEntry] = {}

    def _retire(self, version: int, kind: tuple,
                heir: _ServeCacheEntry) -> None:
        """Drop what ``version`` of ``kind`` supersedes (lock held): the
        older entries of that kind, their buffers going to ``heir`` (a
        list of its own: a builder the store overtook may still be taking
        from the retired one), and the entries of other kinds more than
        one version behind."""
        for stale in [k for k in self._entries if k[0] < version]:
            if stale[1:] == kind:
                heir.buffers = list(self._entries.pop(stale).buffers)
            elif stale[0] < version - 1:
                del self._entries[stale]

    def lookup(self, key: tuple) -> tuple[_ServeCacheEntry, bool]:
        """Returns (entry, is_builder).  A builder MUST call :meth:`fill`
        or :meth:`fail`; everyone else waits on ``entry.event``.  Store
        versions are monotone, so only entries for OLDER versions are
        pruned — a probe that raced a newer serve must not evict the
        newer bytes."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                return entry, False
            entry = _ServeCacheEntry()
            self._retire(key[0], key[1:], entry)
            self._entries[key] = entry
            return entry, True

    def fill(self, key: tuple, entry: _ServeCacheEntry,
             bodies: list[bytes], version: int) -> None:
        entry.bodies = bodies
        entry.version = version
        if version != key[0]:
            # the store moved between the version probe and the atomic
            # (params, version) read: re-register under the version that
            # was actually encoded so later serves of it still hit — but
            # never resurrect a version the cache has already moved past
            with self._lock:
                if self._entries.get(key) is entry:
                    del self._entries[key]
                if not any(k[0] > version for k in self._entries):
                    for stale in [k for k in self._entries
                                  if k[0] < version]:
                        del self._entries[stale]
                    self._entries[(version,) + key[1:]] = entry
        entry.event.set()

    def fail(self, key: tuple, entry: _ServeCacheEntry) -> None:
        entry.failed = True
        with self._lock:
            if self._entries.get(key) is entry:
                del self._entries[key]
        entry.event.set()


class EncodedDeltaCache:
    """Delta tier of the encode-once cache (ISSUE 10): DeltaFrame wire
    bytes keyed by ``(from_version, to_version, chunk budget)`` — one
    encode per pair, replayed to every receiver crossing that version
    hop (the post-barrier fan-out AND every weight subscriber cross the
    same hops).  The chain's wire dtype is process-fixed, so it is not
    part of the key.  No explicit invalidation: store versions are never
    reused within a process (restore bumps past the max ever served), so
    a stale pair key can never be asked for again — the bounded LRU just
    ages entries out.  Unlike the full-serve cache there is no
    single-flight wait: building frames from an already-diffed pair is a
    byte repack, cheap enough that a racing duplicate build beats
    parking a handler thread."""

    CAPACITY = 32

    def __init__(self):
        # leaf (shared rank with EncodedServeCache._lock — never held
        # together): dict ops only, the repack runs outside it
        self._lock = checked_lock("EncodedDeltaCache._lock")
        self._frames: "OrderedDict[tuple, list[bytes]]" = OrderedDict()

    def get(self, pair: DeltaPair, wire_dtype: int,
            budget: int) -> list[bytes]:
        key = (pair.from_version, pair.to_version, budget)
        with self._lock:
            hit = self._frames.get(key)
            if hit is not None:
                self._frames.move_to_end(key)
                return hit
        bodies = [frame.encode()
                  for frame in _pair_frames(pair, wire_dtype, budget)]
        with self._lock:
            self._frames[key] = bodies
            while len(self._frames) > self.CAPACITY:
                self._frames.popitem(last=False)
        return bodies


def _pair_frames(pair: DeltaPair, wire_dtype: int, budget: int):
    """One delta pair -> its DeltaFrame messages: entries greedy-packed
    to roughly ``budget`` payload bytes per frame, the last frame
    stamped with the pair's post-apply store checksum and ``last=True``
    (the receiver applies a pair only once fully assembled —
    delta/client.py)."""
    def make(entries, last: bool) -> dmsg.DeltaFrame:
        return dmsg.DeltaFrame(
            from_version=pair.from_version, to_version=pair.to_version,
            delta=True, wire_dtype=wire_dtype, entries=entries,
            crc=pair.crc if last else 0, last=last)

    batch: list[dmsg.DeltaEntry] = []
    size = 0
    for name, idx_bytes, value_bytes, dense in pair.entries:
        nbytes = len(idx_bytes) + len(value_bytes)
        if batch and size + nbytes > budget:
            yield make(batch, last=False)
            batch, size = [], 0
        batch.append(dmsg.DeltaEntry(name=name, indices=idx_bytes,
                                     values=value_bytes, dense=dense))
        size += nbytes
    yield make(batch, last=True)


class ParameterServerService:
    """RPC handlers (reference: parameter_server_service_impl,
    src/parameter_server_service.cpp:15-175)."""

    def __init__(self, core: ParameterServerCore, ckpt: CheckpointManager):
        self.core = core
        self.ckpt = ckpt
        # same-host shared-memory transport (rpc/shm_transport.py): owns
        # the per-connection rings + serving threads; each shm round runs
        # through the SAME PushPullStream handler below, so semantics and
        # bytes are transport-independent.  Lazy: segments only exist
        # once a same-host client negotiates.  The handler is looked up
        # per round (not captured) so instance-level overrides — tests
        # shaping a reference PS — govern the shm path too.
        self.shm_server = shm_transport.ShmServer(
            lambda chunks, ctx: self.PushPullStream(chunks, ctx))
        # aggregation/serve timing net of RPC plumbing (the handler-level
        # latency histograms live in rpc/service.bind_service)
        # fused data plane: how long PushPullStream handlers park on the
        # barrier condition variable before serving
        # encode-once broadcast cache (see EncodedServeCache): hit = this
        # serve replayed cached wire bytes; miss = it ran the encode
        self._serve_cache = EncodedServeCache()
        self._obs_cache_hit = obs_stats.counter("ps.serve.cache_hit")
        self._obs_cache_miss = obs_stats.counter("ps.serve.cache_miss")
        # versioned delta serving (delta/, ISSUE 10): the chain diffs
        # consecutive store versions right after every apply (core delta
        # sink) and the frame cache replays each pair's encoded bytes to
        # the whole fan-out.  PSDT_DELTA_DEPTH=0 disables the subsystem —
        # the extension RPCs then always answer full frames.  The sink
        # is installed LAZILY on the first dtype-compatible delta
        # request (_arm_delta): until some receiver can actually take a
        # delta, the per-apply O(model) encode/diff would lengthen every
        # barrier close for nothing — an f32-pulling fleet against the
        # default bf16 chain, or a tiers/ leaf core whose same-host
        # members ride shm, never pays it.
        self.delta_chain: DeltaChain | None = None
        if dmsg.delta_enabled():
            self.delta_chain = DeltaChain()
        self._delta_armed = False
        self._delta_cache = EncodedDeltaCache()
        # live-subscription bound (SubscribeWeights parks one handler
        # thread per subscriber between versions; past the pool headroom
        # the barrier-closing fused push would queue behind them)
        self._active_subscribers = 0
        self._sub_lock = checked_lock(
            "ParameterServerService._sub_lock")
        self._obs_delta_hit = obs_stats.counter("ps.serve.delta_hit")
        self._obs_delta_miss = obs_stats.counter("ps.serve.delta_miss")
        self._obs_delta_bytes = obs_stats.counter("ps.serve.delta_bytes")
        # replication sink (replication/replicator.py): installs
        # primary->backup delta streams and tracks the replication
        # high-water mark.  Always present — ANY PS can serve as a
        # backup or a reshard target; the extension methods cost nothing
        # until a peer calls them.
        self.replica_sink = ReplicaSink(core)
        # cross-replica sharded-update sink (replication/
        # sharded_update.py, ISSUE 18): runs the fused arena stages over
        # this replica's owned stripe slices when the primary shards a
        # close across the replica set.  Always present for the same
        # reason as the replica sink.
        self.sharded_sink = ShardedUpdateSink(core, self.replica_sink)

    def _apply(self, worker_id: int, iteration: int, grads):
        """Decoded-gradients -> core aggregation, timed and traced (the
        "PS apply" leg of the distributed step trace — the enclosing
        handler span carries the worker's trace id)."""
        with obs_trace.span("ps/apply", worker=worker_id,
                            iteration=iteration):
            result = self.core.receive_gradients(worker_id, iteration, grads)
        return result

    @staticmethod
    def _decode(chunk: m.GradientUpdate, device: bool, sink) -> dict:
        """One push chunk's wire tensors to fold-ready arrays: the decode
        leg of the server's codec.  A sink that folds at once and keeps
        nothing (``PushSink.folds_at_once``) is lent the frame's own
        read-only views; every other sink gets arrays it owns."""
        with obs_trace.span("rpc/server/decode", worker=chunk.worker_id,
                            iteration=chunk.iteration):
            return decode_gradients(chunk.gradients, device,
                                    borrow=sink.folds_at_once)

    def _commit(self, sink: PushSink):
        """End-of-stream commit of a chunk-folded push, timed/traced like
        :meth:`_apply` (the fold legs were already accounted inside the
        stream loop — they overlap transport)."""
        with obs_trace.span("ps/apply", worker=sink.worker_id,
                            iteration=sink.iteration):
            result = sink.commit()
        return result

    @staticmethod
    def _push_result_response(result) -> m.PushResponse:
        return m.PushResponse(
            success=result.success,
            message=result.message,
            iteration=result.iteration,
            aggregation_complete=result.aggregation_complete,
            workers_received=result.workers_received,
            total_workers=result.total_workers,
        )

    # RPC: push gradients (reference: src/parameter_server_service.cpp:32-59)
    def ReceiveGradients(self, request: m.GradientUpdate, context) -> m.PushResponse:
        grads = from_wire(request.gradients)
        result = self._apply(request.worker_id, request.iteration, grads)
        return self._push_result_response(result)

    # RPC: pull parameters (reference: src/parameter_server_service.cpp:62-84)
    # Serves in the encoding the client requested (request.wire_dtype, a
    # framework extension; reference clients leave it 0 = repeated float).
    @staticmethod
    def _serve_wire_dtype(requested: int) -> int:
        """The lossy gradient-push encodings (int8, topk) must never be
        applied to SERVED parameters — error feedback corrects push bias
        over time, but re-compressing the parameters every pull compounds
        irrecoverable error (99% of weights zeroed, under topk).  The
        framework worker already asks for bf16 in that case
        (worker/worker.py _pull_wire_dtype); enforcing it server-side
        protects every other client too."""
        if requested in (m.WIRE_INT8, m.WIRE_TOPK):
            return m.WIRE_BF16
        return requested

    @staticmethod
    def _cache_build_wait_s() -> float:
        """How long a concurrent serve waits for an in-flight cache build
        before falling back to its own (uncached) encode.  Kept BELOW the
        worker's 30 s pull deadline (worker/worker.py _pull_parameters) —
        same principle as _fused_barrier_timeout_s: a wedged builder must
        degrade to a served (uncached) response, not to the client's
        DEADLINE_EXCEEDED."""
        return float(os.environ.get("PSDT_SERVE_CACHE_WAIT_S", "20"))

    def _encode_chunk_bodies(self, request_iteration: int, eff_dtype: int,
                             budget: int, entry: _ServeCacheEntry):
        """One real encode pass: (chunk bodies, store version) — the
        single shared recipe under the cache.  The per-chunk payload
        encodes (f32→bf16 casts, repeated-float packs) fan out across the
        shared stripe executor (rpc/data_plane.py
        encode_parameter_record_groups) — a version-miss encode of a
        multi-chunk store runs on multiple cores, and every consumer
        collects the whole body list anyway before touching the network
        (see _parameter_chunks for why the fill must not be
        client-paced).  The bodies are built into ``entry``'s buffers."""
        _, params, _, version = self.core.serve_view(request_iteration)
        with obs_trace.span("rpc/server/encode", version=version):
            tensors = to_wire(params, wire_dtype=eff_dtype)
            bodies = encode_parameter_record_groups(
                list(split_tensors(tensors, budget)), entry.take,
                stripes=self.core.stripes)
        del entry.buffers[len(bodies):]  # a smaller store's spare places
        return bodies, version

    def _serve_key(self, wire_dtype: int) -> tuple:
        eff = self._serve_wire_dtype(wire_dtype)
        budget = stream_chunk_bytes() or (32 << 20)
        return (self.core.serve_version(), eff, budget)

    def _wait_for_builder(self, entry: _ServeCacheEntry,
                          key: tuple) -> tuple[list[bytes], bool, int]:
        """Non-builder path: (bodies, cached, version).  Replays the
        in-flight builder's bytes (cached=True — the caller re-probes the
        version), or falls back to an uncached encode of the LIVE store
        if the builder failed/wedged (cached=False — already current, no
        re-probe) — serve correctness over cache purity."""
        if entry.event.wait(self._cache_build_wait_s()) and not entry.failed:
            self._obs_cache_hit.add()
            return entry.bodies, True, entry.version
        self._obs_cache_miss.add()
        bodies, version = self._encode_chunk_bodies(
            0, key[1], key[2], _ServeCacheEntry())
        return bodies, False, version

    def _encoded_parameter_chunks(self, request_iteration: int,
                                  wire_dtype: int) -> list[bytes]:
        return self._encoded_chunks_versioned(request_iteration,
                                              wire_dtype)[0]

    def _encoded_chunks_versioned(self, request_iteration: int,
                                  wire_dtype: int
                                  ) -> tuple[list[bytes], int]:
        """Whole-list encoded chunk bodies plus the store version they
        were encoded at, through the encode-once cache.  The version
        probe (`core.serve_version`) is a lock-and-read — a cache hit
        never copies the parameter store at all, let alone re-encodes it.
        A waiter that parked on a builder RE-PROBES the version on wake:
        the store may have advanced during the wait, and serving the old
        bytes then would stretch staleness from the probe window to the
        whole wait window (bounded retries; the final fallback serves
        what it has — indistinguishable from the serve having happened
        when it was first admitted).  The returned version labels the
        BYTES (entry.version), not the probe key — the delta protocol
        stamps it as the receiver's new base, which must be exact."""
        for _ in range(3):
            key = self._serve_key(wire_dtype)
            entry, builder = self._serve_cache.lookup(key)
            if builder:
                self._obs_cache_miss.add()
                try:
                    bodies, version = self._encode_chunk_bodies(
                        request_iteration, key[1], key[2], entry)
                except BaseException:
                    self._serve_cache.fail(key, entry)
                    raise
                self._serve_cache.fill(key, entry, bodies, version)
                return bodies, version
            bodies, cached, version = self._wait_for_builder(entry, key)
            if not cached or self.core.serve_version() == key[0]:
                return bodies, version
        return bodies, version

    def ServeParameters(self, request: m.PullRequest, context):
        with obs_trace.span("ps/serve", worker=request.worker_id,
                            iteration=request.iteration):
            # label read BEFORE the bodies resolve: a serve must never
            # stamp bytes with an iteration newer than they are (the old
            # code read both under one lock; bytes newer than the label
            # are the benign direction — a serve racing a push)
            iteration = self.core.current_iteration
            bodies = self._encoded_parameter_chunks(request.iteration,
                                                    request.wire_dtype)
            resp = PreEncodedParameterUpdate(iteration, True, bodies)
        return resp

    # RPC (framework extension, rpc/data_plane.py): client-streamed push.
    # Chunks decode + fold into the aggregation accumulator as they arrive,
    # overlapping transport; barrier/staleness semantics are exactly the
    # unary RPC's (the worker becomes a barrier contributor only at
    # end-of-stream commit).
    def PushGradientsStream(self, request_iterator, context) -> m.PushResponse:
        sink: PushSink | None = None
        device = False
        for chunk in request_iterator:
            if sink is None:
                sink = self.core.begin_push(chunk.worker_id, chunk.iteration)
                # read once per stream: device folds (ISSUE 11) decode
                # each chunk straight to device buffers
                device = self.core.device_fold
            if chunk.gradients:
                sink.fold(self._decode(chunk, device, sink))
        if sink is None:
            return m.PushResponse(success=False, message="empty push stream")
        return self._push_result_response(self._commit(sink))

    def _parameter_chunks(self, request_iteration: int, wire_dtype: int):
        """Serve the current store as a stream of ParameterUpdate chunks
        (shared by ServeParametersStream and the fused PushPullStream),
        replaying the encode-once cache's wire bytes.

        The builder (first serve of a version) encodes ALL chunk bodies
        on its first pull and fills the cache BEFORE streaming them: the
        fill must never be paced by the builder's client — each yield is
        subject to gRPC flow control, and a slow or stalled first puller
        must not hold the rest of the post-barrier fan-out hostage for
        the single-flight wait.  The miss serve trades its intra-serve
        encode ⊕ transport overlap (one serve per store version, CPU-
        bounded) for that decoupling; every other serve streams cached
        bytes chunk by chunk as before."""
        # label before bodies — see ServeParameters
        iteration = self.core.current_iteration
        bodies = self._encoded_parameter_chunks(request_iteration,
                                                wire_dtype)
        if not bodies:  # empty store still answers one (empty) chunk
            yield PreEncodedParameterUpdate(iteration, True, ())
            return
        for body in bodies:
            yield PreEncodedParameterUpdate(iteration, True, (body,))

    # RPC (framework extension): server-streamed pull.
    def ServeParametersStream(self, request: m.PullRequest, context):
        yield from self._parameter_chunks(request.iteration,
                                          request.wire_dtype)

    # Server-side cap on the fused barrier park.  Kept BELOW the worker's
    # fused call timeout so a stuck barrier surfaces as a clean
    # ready=False frame (client falls back to its poll loop) instead of a
    # DEADLINE_EXCEEDED stream abort.
    @staticmethod
    def _fused_barrier_timeout_s() -> float:
        return float(os.environ.get("PSDT_FUSED_BARRIER_TIMEOUT_S", "60"))

    # RPC (framework extension, rpc/data_plane.py): the fused synchronous
    # step.  Client-streamed gradient chunks fold into the aggregation
    # accumulator as they arrive and commit as ONE push at end-of-stream
    # (barrier/staleness semantics identical to the unary push); the
    # handler then parks on the aggregation condition variable and streams
    # the fresh parameters back the instant the barrier closes — no
    # CheckSyncStatus polling, no second round.
    def PushPullStream(self, request_iterator, context):
        # A fused push must never be the store's FIRST payload: the
        # bootstrap rule (first aggregated payload BECOMES the params
        # — reference src/parameter_server.cpp:78-81) is reserved for
        # the worker's deliberate init seed, which always rides the
        # plain push path.  A fused push of real gradients can only
        # reach an empty store when the PS restarted under a worker
        # holding cached params — refusing makes the worker re-pull,
        # notice the emptiness, and re-seed instead of silently
        # turning its gradients into parameters.  A gradient-FREE fused
        # push is a different animal: under the sharded topology a shard
        # owning no tensors of the model (possible after a reshard — or
        # a small model over many shards) still receives every worker's
        # empty barrier contribution, and refusing those would wedge the
        # whole barrier on a store that is legitimately empty forever.
        # ... and a store emptied by a reshard RETIRE (tombstones
        # present) must answer the stale-shard-map rejection — which the
        # normal fold/commit path produces — not the restart refusal, or
        # the pushing worker takes the re-seed recovery path instead of
        # repartitioning.
        empty_store = (not self.core.has_parameters
                       and not self.core.has_retired)
        sink: PushSink | None = None
        pull_wire_dtype = 0
        device = False
        for chunk in request_iterator:
            if empty_store and chunk.gradients:
                yield m.PushPullResponse(push=m.PushResponse(
                    success=False,
                    message="parameter store empty: fused push refused "
                            "(re-pull and seed init via the push path)",
                    iteration=self.core.current_iteration))
                return
            if sink is None:
                sink = self.core.begin_push(chunk.worker_id, chunk.iteration)
                pull_wire_dtype = chunk.pull_wire_dtype
                device = self.core.device_fold  # see PushGradientsStream
            if chunk.gradients:
                sink.fold(self._decode(chunk, device, sink))
        if sink is None:
            yield m.PushPullResponse(push=m.PushResponse(
                success=False, message="empty push stream"))
            return
        worker_id, iteration = sink.worker_id, sink.iteration
        result = self._commit(sink)
        push = self._push_result_response(result)
        # the push verdict goes out immediately: a stale rejection (async
        # mode) must reach the worker without waiting on any barrier
        yield m.PushPullResponse(push=push)
        if not result.success:
            return
        if not result.aggregation_complete:
            with obs_trace.span("ps/barrier_wait", worker=worker_id,
                                iteration=iteration):
                ready, received, total = self.core.wait_for_aggregation(
                    iteration, timeout=self._fused_barrier_timeout_s())
            if not ready:
                log.warning(
                    "PushPullStream: barrier timeout at iteration %d "
                    "(%d/%d received) — worker %d falls back to polling",
                    iteration, received, total, worker_id)
                yield m.PushPullResponse(params=m.ParameterUpdate(
                    iteration=self.core.current_iteration, ready=False))
                return
        with obs_trace.span("ps/serve", worker=worker_id,
                            iteration=iteration):
            for chunk in self._parameter_chunks(iteration, pull_wire_dtype):
                yield m.PushPullResponse(params=chunk)

    # ------------------------------------------------------------ delta serve
    # Versioned delta serving + live weight publication (delta/, ISSUE
    # 10).  The methods and their messages live OUTSIDE rpc/messages.py
    # so the reference wire manifest is untouched; a reference PS answers
    # UNIMPLEMENTED and callers downgrade permanently (the PR-2 fallback
    # discipline, zero failed steps).

    def _arm_delta(self) -> None:
        """Install the chain as the core's post-apply delta sink, once,
        on the FIRST dtype-compatible delta request (pull, fused round,
        or subscription).  Until some receiver can actually take a
        delta, every barrier close would pay the chain's O(model)
        encode/diff/crc for nothing — an f32-pulling fleet against the
        default bf16 chain, or a tiers/ leaf core whose same-host
        members ride shm, never arms.  Armed WITHOUT seeding from the
        live store: traffic is flowing by now, and an unserialized
        snapshot could tear against an in-flight apply's in-place
        update — the next serialized apply reseeds the retained image
        instead (one extra full serve, never a wrong base)."""
        if self._delta_armed or self.delta_chain is None:
            return
        # benign race: double-arming installs the same sink twice, and
        # neither install seeds, so no lock is needed here
        self._delta_armed = True
        self.core.set_delta_sink(self.delta_chain, seed=False)
        log.info("delta chain armed: first dtype-compatible delta "
                 "receiver seen; applies now build version pairs")

    def _delta_serve(self, held_version: int, wire_dtype: int,
                     request_iteration: int) -> tuple[list, int]:
        """Frames answering a receiver that holds ``held_version``:
        ``(frames, end_version)`` — a chain of encoded delta pairs when
        the receiver is within the depth budget and its pull encoding
        matches the chain's, a full serve otherwise (no base yet, depth
        exceeded, a reset — restore/install/retire — broke the chain, or
        a dtype mismatch).  Frames are thin wrappers over cache-owned
        bytes; materializing the list costs a few tuples, and the
        subscribe loop needs the end version up front."""
        held = int(held_version)
        eff = self._serve_wire_dtype(wire_dtype)
        budget = stream_chunk_bytes() or (32 << 20)
        chain = self.delta_chain
        current = self.core.serve_version()
        pairs = None
        reason = "disabled"
        if chain is not None:
            if not wire_dtype_compatible(eff, chain.wire_dtype):
                reason = "dtype"
            else:
                # a compatible receiver exists: make sure applies build
                self._arm_delta()
                if held <= 0:
                    reason = "no base"
                else:
                    pairs = chain.pairs_between(held, current)
                    if pairs is None:
                        # past the depth budget, or a reset broke the
                        # chain (restore/install/retire)
                        reason = "depth/reset"
        if pairs is not None:
            frames: list = []
            nbytes = 0
            for pair in pairs:
                for body in self._delta_cache.get(pair, chain.wire_dtype,
                                                  budget):
                    frames.append(dmsg.EncodedDeltaFrame(body))
                    nbytes += len(body)
            self._obs_delta_hit.add()
            self._obs_delta_bytes.add(nbytes)
            flight.record("serve.delta.hit", iteration=request_iteration,
                          a=nbytes, b=len(pairs))
            return frames, pairs[-1].to_version
        self._obs_delta_miss.add()
        flight.record("serve.delta.miss", iteration=request_iteration,
                      a=max(held, 0), b=current, note=reason)
        # full serve, version-stamped: the receiver's next held_version.
        # Label read BEFORE the bodies resolve (see ServeParameters).
        iteration = self.core.current_iteration
        bodies, version = self._encoded_chunks_versioned(request_iteration,
                                                         wire_dtype)
        if not bodies:  # empty store still answers one (empty) chunk
            return [dmsg.DeltaFrame(
                params=PreEncodedParameterUpdate(iteration, True, ()),
                to_version=version, last=True)], version
        return [dmsg.DeltaFrame(
                    params=PreEncodedParameterUpdate(iteration, True,
                                                     (body,)),
                    to_version=version, last=(i == len(bodies) - 1))
                for i, body in enumerate(bodies)], version

    # RPC (framework extension, delta/): version-aware unary pull — the
    # request advertises the held store version; the response is a delta
    # chain or a stamped full serve.
    def PullParametersDelta(self, request: dmsg.DeltaPullRequest, context):
        with obs_trace.span("ps/serve", worker=request.worker_id,
                            iteration=request.iteration):
            frames, _ = self._delta_serve(request.held_version,
                                          request.wire_dtype,
                                          request.iteration)
        yield from frames

    # RPC (framework extension, delta/): the version-aware fused round.
    # Same semantics as PushPullStream — fold chunks as they arrive,
    # commit as ONE push, park on the barrier, stream fresh parameters —
    # but the response rides DeltaFrames, so a receiver within the depth
    # budget gets O(changed bytes) instead of the full model.
    def PushPullDeltaStream(self, request_iterator, context):
        empty_store = (not self.core.has_parameters
                       and not self.core.has_retired)
        sink: PushSink | None = None
        pull_wire_dtype = 0
        held_version = 0
        device = False
        for dchunk in request_iterator:
            chunk = dchunk.update
            if chunk is None:
                continue
            if empty_store and chunk.gradients:
                # the PushPullStream bootstrap refusal, frame-shaped
                yield dmsg.DeltaFrame(push=m.PushResponse(
                    success=False,
                    message="parameter store empty: fused push refused "
                            "(re-pull and seed init via the push path)",
                    iteration=self.core.current_iteration))
                return
            if sink is None:
                sink = self.core.begin_push(chunk.worker_id,
                                            chunk.iteration)
                pull_wire_dtype = chunk.pull_wire_dtype
                held_version = int(dchunk.held_version)
                device = self.core.device_fold  # see PushGradientsStream
            if chunk.gradients:
                sink.fold(self._decode(chunk, device, sink))
        if sink is None:
            yield dmsg.DeltaFrame(push=m.PushResponse(
                success=False, message="empty push stream"))
            return
        worker_id, iteration = sink.worker_id, sink.iteration
        result = self._commit(sink)
        # the push verdict goes out immediately (see PushPullStream)
        yield dmsg.DeltaFrame(push=self._push_result_response(result))
        if not result.success:
            return
        if not result.aggregation_complete:
            with obs_trace.span("ps/barrier_wait", worker=worker_id,
                                iteration=iteration):
                ready, received, total = self.core.wait_for_aggregation(
                    iteration, timeout=self._fused_barrier_timeout_s())
            if not ready:
                log.warning(
                    "PushPullDeltaStream: barrier timeout at iteration %d "
                    "(%d/%d received) — worker %d falls back to polling",
                    iteration, received, total, worker_id)
                yield dmsg.DeltaFrame(params=m.ParameterUpdate(
                    iteration=self.core.current_iteration, ready=False))
                return
        with obs_trace.span("ps/serve", worker=worker_id,
                            iteration=iteration):
            frames, _ = self._delta_serve(held_version, pull_wire_dtype,
                                          iteration)
        yield from frames

    # How often a parked subscription handler re-probes liveness.  Short
    # enough that server shutdown and client cancellation are noticed
    # promptly; the chain's condition variable wakes it instantly on a
    # new version regardless.
    @staticmethod
    def _subscribe_poll_s() -> float:
        return float(os.environ.get("PSDT_SUBSCRIBE_POLL_S", "0.5"))

    # Live-subscription admission bound.  Each subscription parks one
    # handler thread between versions, and the gRPC pool is sized for
    # the fused data plane PLUS this many subscribers (see start());
    # past the bound a new subscriber would steal a thread the barrier-
    # closing fused push needs, so it is refused RESOURCE_EXHAUSTED —
    # the WeightFollower's bounded-backoff reconnect absorbs a refusal
    # like any transient transport error (retry, then degraded serving
    # last-good weights; never a crash).
    @staticmethod
    def _max_subscribers() -> int:
        return int(os.environ.get("PSDT_MAX_SUBSCRIBERS", "8"))

    # RPC (framework extension, delta/): live weight publication — the
    # decode fleet's train-to-production feed.  Streams one frame batch
    # per store version from the subscriber's held version forward (full
    # first when it holds nothing or fell behind the chain), until the
    # subscriber cancels or the server stops.  Each subscription parks
    # one handler thread between versions (bounded CV waits), like a
    # barrier-waiting fused worker does.
    def SubscribeWeights(self, request: dmsg.SubscribeRequest, context):
        with self._sub_lock:
            live = self._active_subscribers
            admitted = live < self._max_subscribers()
            if admitted:
                self._active_subscribers += 1
        if not admitted:
            log.warning(
                "SubscribeWeights refused: %d live subscriptions at the "
                "PSDT_MAX_SUBSCRIBERS=%d bound (subscriber %d backs off "
                "and retries)", live, self._max_subscribers(),
                request.subscriber_id)
            if context is not None:
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                              "subscriber limit reached "
                              f"({self._max_subscribers()}); retry later")
            return
        try:
            held = int(request.held_version)
            flight.record("publish.subscribe", a=max(held, 0),
                          b=request.subscriber_id)
            chain = self.delta_chain
            if chain is not None and wire_dtype_compatible(
                    self._serve_wire_dtype(request.wire_dtype),
                    chain.wire_dtype):
                # a live subscriber is a standing delta receiver: start
                # building pairs even before the first version advances
                self._arm_delta()
            while context is None or context.is_active():
                current = self.core.serve_version()
                if current > held and self.core.has_parameters:
                    lag = current - held
                    if held > 0 and lag > 1:
                        flight.record("publish.lag", a=lag,
                                      b=request.subscriber_id)
                    frames, end = self._delta_serve(held,
                                                    request.wire_dtype, 0)
                    yield from frames
                    if end > held:
                        held = end
                        continue
                    # a stale-cache race labeled the serve at (or before)
                    # the held version: nothing newer was actually
                    # delivered — fall through to the park, don't spin
                if chain is not None:
                    chain.wait_for_newer(held, self._subscribe_poll_s())
                else:
                    time.sleep(self._subscribe_poll_s())
        finally:
            with self._sub_lock:
                self._active_subscribers -= 1

    # RPC (framework extension, rpc/shm_transport.py): same-host shared-
    # memory transport negotiation for the fused data plane.  The method
    # and its messages live OUTSIDE rpc/messages.py so the reference wire
    # manifest is untouched; a reference PS answers UNIMPLEMENTED and the
    # client downgrades to TCP permanently (PR-2 fallback discipline).
    def NegotiateShm(self, request: shm_transport.ShmNegotiateRequest,
                     context) -> shm_transport.ShmNegotiateResponse:
        return self.shm_server.negotiate(request)

    # ----------------------------------------------------------- replication
    # RPCs (framework extension, replication/): the messages and method
    # names live OUTSIDE rpc/messages.py so the reference wire manifest is
    # untouched; a reference peer answers UNIMPLEMENTED and callers
    # downgrade permanently (replication/replicator.py, failover.py).

    # RPC: primary -> backup post-apply state ship / reshard stripe install
    def PushReplicaDelta(self, request_iterator, context) -> rmsg.ReplicaAck:
        return self.replica_sink.push_delta(request_iterator)

    # RPC: stream a consistent snapshot (full or name-filtered) — a late-
    # joining backup's initial sync, and a debugging/verification surface.
    # Optimizer slot state rides along __opt__/-prefixed (filtered to the
    # requested names' entries), so a backup seeded this way and promoted
    # before the first ship still optimizes from warm slots.
    def FetchReplicaState(self, request: rmsg.ReplicaStateRequest, context):
        epoch, iteration, version, params, opt = self.core.replica_snapshot()
        names = set(request.names)
        if names:
            params = {n: params[n] for n in names if n in params}
            opt = {slot: ({n: a for n, a in value.items() if n in names}
                          if isinstance(value, dict) else value)
                   for slot, value in opt.items()}
        payload = dict(params)
        if opt:
            payload.update(flatten_optimizer_state(opt))
        yield from state_chunks(epoch, iteration, version, payload)

    # RPC: the resharding version fence — atomically remove + tombstone
    # the moving tensors and stream their last-applied values (and their
    # optimizer slot entries, __opt__/-prefixed) back
    def RetireTensors(self, request: rmsg.RetireTensorsRequest, context):
        epoch, iteration, version, moved, moved_opt = \
            self.core.retire_tensors(list(request.names), request.map_epoch)
        log.info("retired %d tensors at map epoch %d (reshard handoff)",
                 len(moved), request.map_epoch)
        payload = dict(moved)
        if moved_opt:
            payload.update(flatten_optimizer_state(moved_opt))
        yield from state_chunks(epoch, iteration, version, payload)

    # RPC: cross-replica sharded close, apply leg (ISSUE 18) — the
    # primary streams the fold sums for this replica's owned stripe
    # slices; the fresh param/slot slices stream back
    def ShardedApplySlices(self, request_iterator, context):
        yield from self.sharded_sink.apply_slices(request_iterator, context)

    # RPC: cross-replica sharded close, install leg — the slices this
    # replica does NOT own arrive and the assembled store commits
    def InstallSlabSlices(self, request_iterator,
                          context) -> rmsg.ShardedSliceAck:
        return self.sharded_sink.install_slices(request_iterator, context)

    # RPC: replication high-water mark + tensor-name census (the reshard
    # controller's ownership listing — names only, no values)
    def ReplicaStatus(self, request: rmsg.ReplicaStatusRequest,
                      context) -> rmsg.ReplicaStatusResponse:
        return rmsg.ReplicaStatusResponse(
            iteration=self.core.current_iteration,
            params_version=self.core.params_version,
            primary_version=self.replica_sink.primary_version,
            primary_iteration=self.replica_sink.primary_iteration,
            names=sorted(self.core.get_parameters()),
            epoch=self.core.epoch)

    # RPC: barrier poll (reference: src/parameter_server_service.cpp:85-95)
    def CheckSyncStatus(self, request: m.SyncStatusRequest, context) -> m.SyncStatusResponse:
        iteration, ready, received, total = self.core.check_sync_status(request.iteration)
        return m.SyncStatusResponse(iteration=iteration, ready=ready,
                                    workers_received=received, total_workers=total)

    # RPC: on-demand save (reference: src/parameter_server_service.cpp:97-115)
    def SaveCheckpoint(self, request: m.SaveCheckpointRequest, context) -> m.SaveCheckpointResponse:
        try:
            saved = self.ckpt.save(epoch=request.epoch if request.epoch else None,
                                   path=request.path or None)
            return m.SaveCheckpointResponse(success=True, message="checkpoint saved",
                                            checkpoint_path=saved)
        except Exception as exc:  # noqa: BLE001 — report failure over RPC
            log.exception("SaveCheckpoint failed")
            return m.SaveCheckpointResponse(success=False, message=str(exc))

    # RPC: load into the PS; response ships the params back as the reference
    # does (src/parameter_server_service.cpp:126-137) even though its worker
    # discards them (src/worker.cpp:311-313).  Above the echo cap the
    # echo is omitted: a 1B store's packed repeated-float encoding (~4 GB)
    # would blow the 1 GB gRPC message cap AFTER the load already
    # succeeded server-side, turning a successful restore into a
    # client-visible error.  Workers (ours and the reference's) discard
    # the echo anyway.
    @staticmethod
    def _echo_max_bytes() -> int:
        # read per call (matching rpc/data_plane.stream_chunk_bytes) so
        # env overrides set after import still take effect
        return int(os.environ.get("PSDT_CKPT_ECHO_MAX_BYTES",
                                  str(256 << 20)))

    def LoadCheckpoint(self, request: m.LoadCheckpointRequest, context) -> m.LoadCheckpointResponse:
        try:
            epoch, _iteration = self.ckpt.load(request.path)
            _, params, _ = self.core.serve_parameters()
            cap = self._echo_max_bytes()
            # .size without np.asarray: device-resident stores (jax
            # Arrays) must not be copied to host just to be counted
            nbytes = sum(4 * int(v.size) for v in params.values())
            if nbytes > cap:
                log.info("LoadCheckpoint: store is %.2f GB f32 — omitting "
                         "the parameter echo (cap %d MB)", nbytes / 1e9,
                         cap >> 20)
                return m.LoadCheckpointResponse(
                    success=True,
                    message="checkpoint loaded (parameter echo omitted: "
                            "store exceeds the unary response cap; pull "
                            "via ServeParameters)",
                    epoch=epoch)
            return m.LoadCheckpointResponse(success=True, message="checkpoint loaded",
                                            epoch=epoch, parameters=to_wire(params))
        except Exception as exc:  # noqa: BLE001
            log.exception("LoadCheckpoint failed")
            return m.LoadCheckpointResponse(success=False, message=str(exc))


class ParameterServer:
    """Process-level assembly: core + checkpoint daemon + gRPC server
    (reference: run_server at src/parameter_server_service.cpp:177-191)."""

    def __init__(self, config: ParameterServerConfig,
                 live_workers_fn: Callable[[], int] | None = None,
                 contributions_fn: Callable | None = None):
        self.config = config
        optimizer = make_optimizer(config.optimizer, config.learning_rate,
                                   config.momentum, config.weight_decay)
        self.core = ParameterServerCore(
            total_workers=config.total_workers,
            optimizer=optimizer,
            staleness_bound=config.staleness_bound,
            live_workers_fn=live_workers_fn if config.elastic else None,
            live_workers_ttl_s=config.live_workers_ttl_s,
            gc_iterations=config.gc_iterations,
            aggregation=config.aggregation or None,
            # tier contribution weights (tiers/topology.py
            # TierContributionProvider): a leaf aggregator's ONE upstream
            # push counts as its whole group on the barrier
            contributions_fn=contributions_fn,
            # K-of-N quorum close (elastic/quorum.py, ISSUE 13); 0/-1
            # defer to the PSDT_QUORUM / PSDT_QUORUM_GRACE_MS env
            quorum=config.quorum or None,
            quorum_grace_ms=(config.quorum_grace_ms
                             if config.quorum_grace_ms >= 0 else None),
            # free-running barrier-free mode (freerun/, ISSUE 16);
            # False defers to the PSDT_FREERUN env
            freerun=config.freerun or None,
        )
        self.ckpt = CheckpointManager(
            self.core,
            directory=config.checkpoint_dir,
            checkpoint_interval=config.checkpoint_interval,
            check_period_s=config.autosave_period_s,
            keep=config.checkpoint_keep,
        )
        self.service = ParameterServerService(self.core, self.ckpt)
        # primary/backup replication (replication/replicator.py): ship
        # the post-apply state to config.backup_address after every
        # barrier close.  PSDT_REPLICATION picks the mode (async |
        # sync | off); constructed here, started with the server.
        self.replicator: Replicator | None = None
        mode = (config.replication
                or os.environ.get("PSDT_REPLICATION", "async")).lower()
        replication_on = mode not in ("off", "0", "false")
        if config.backup_address and replication_on:
            self.replicator = Replicator(self.core, config.backup_address,
                                         mode=mode)
        # Cross-replica sharded update (replication/sharded_update.py,
        # ISSUE 18): partition each arena close across the replica set.
        # Requires a sync-mode Replicator (the exchange IS the
        # replication for a close, so the backup must provably hold the
        # base before the barrier publishes) — any other mode leaves the
        # flag inert.  Config forces; "" defers to PSDT_SHARDED_UPDATE.
        self.sharded_updater: ShardedUpdater | None = None
        sharded_on = (config.sharded_update not in ("", "0", "false")
                      if config.sharded_update
                      else sharded_mod.enabled())
        if sharded_on and self.replicator is not None and mode == "sync":
            self.sharded_updater = ShardedUpdater(
                self.core, self.replicator,
                dtype=config.sharded_update_dtype or None)
            self.core.set_sharded_updater(self.sharded_updater)
        elif sharded_on:
            log.warning("PSDT_SHARDED_UPDATE set but replication is not "
                        "sync-mode with a backup; sharded update stays "
                        "disarmed")
        # Replication headroom (ISSUE 9 satellite): a backup that gets
        # PROMOTED starts serving barriers with no backup of its own —
        # silently, until now.  The unarmed gauge flags that window in
        # pst-status --metrics, and a configured --standby address
        # re-arms the promoted primary's Replicator automatically: the
        # standby replicator stays DORMANT until the first barrier close
        # proves this process is a serving primary (a pure backup never
        # closes barriers — it installs deltas), then starts shipping.
        self._obs_unarmed = obs_stats.gauge("ps.replica.unarmed")
        self._standby: Replicator | None = None
        if (self.replicator is None and replication_on
                and config.standby_address):
            self._standby = Replicator(self.core, config.standby_address,
                                       mode=mode)
        if self.replicator is None:
            self.core.set_replication_hook(self._on_primary_apply)
        self._server: grpc.Server | None = None

    @property
    def bound_port(self) -> int:
        return self._port

    def _on_primary_apply(self) -> None:
        """Replication hook of a PS with no armed Replicator: a barrier
        close means this process is serving as a PRIMARY.  If it had
        ever installed a replica delta it is a PROMOTED backup — re-arm
        toward the standby when one is configured (this close's state
        ships too), else surface the unreplicated window as the
        ps.replica.unarmed gauge.  MUST NOT raise (core contract)."""
        if self.service.replica_sink.primary_version < 0:
            return  # never was a replica: ordinary unreplicated primary
        standby, self._standby = self._standby, None
        if standby is not None:
            self.replicator = standby
            standby.start()  # swaps the core hook to the replicator's
            standby.on_apply()  # do not lose THIS close's ship
            self._obs_unarmed.set(0)
            flight.record("repl.ship.start", a=0, b=0,
                          note=f"re-armed -> {standby.backup_address}")
            log.warning("promoted primary re-armed replication toward "
                        "standby %s", standby.backup_address)
        elif not self._obs_unarmed.value:
            self._obs_unarmed.set(1)
            log.warning("promoted primary is serving WITHOUT a backup "
                        "(no --standby configured) — ps.replica.unarmed")

    def start(self) -> int:
        """Start serving; returns the bound port (0 in config = ephemeral)."""
        # The fused data plane parks one handler thread per barrier-waiting
        # worker (PushPullStream blocks in wait_for_aggregation), so the
        # pool must exceed the barrier width or the LAST worker's push —
        # the one that would close the barrier — queues behind the parked
        # handlers and every step stalls to the barrier timeout.  2x +
        # headroom leaves room for concurrent pulls/checkpoint RPCs and
        # moderate elastic growth past the configured width; on top of
        # that, one slot per admitted SubscribeWeights subscription (each
        # live subscription parks one thread between versions, and the
        # service refuses subscribers past PSDT_MAX_SUBSCRIBERS, so the
        # decode fleet can never starve the training plane).
        self._server = make_server(
            max_workers=max(8, 2 * self.config.total_workers + 8
                            + self.service._max_subscribers()))
        bind_service(self._server, m.PARAMETER_SERVER_SERVICE,
                     {**m.PARAMETER_SERVER_METHODS,
                      **m.PARAMETER_SERVER_STREAM_METHODS,
                      **shm_transport.SHM_METHODS,
                      **rmsg.REPLICATION_PS_METHODS,
                      **rmsg.SHARDED_UPDATE_PS_METHODS,
                      **dmsg.DELTA_PS_METHODS}, self.service)
        addr = f"{self.config.bind_address}:{self.config.port}"
        self._port = self._server.add_insecure_port(addr)
        if self._port == 0:
            raise RuntimeError(f"could not bind {addr}")
        self._server.start()
        if flight.enabled():
            # label this process's flight ring for pst-trace's listing
            # (a backup PS that never sees traffic still identifies)
            flight.set_role(f"ps:{self.config.bind_address}:{self._port}")
        self.ckpt.start()
        if self.replicator is not None:
            self.replicator.start()
            log.info("replicating to backup %s (%s mode)",
                     self.replicator.backup_address, self.replicator.mode)
        log.info("parameter server listening on %s (total_workers=%d, "
                 "checkpoint_interval=%d)", addr, self.config.total_workers,
                 self.config.checkpoint_interval)
        return self._port

    def wait(self) -> None:
        assert self._server is not None
        self._server.wait_for_termination()

    def stop(self, grace: float = 1.0) -> None:
        if self.sharded_updater is not None:
            self.core.set_sharded_updater(None)
            self.sharded_updater.stop()
        if self.replicator is not None:
            self.replicator.stop()
        if self._standby is not None:
            # dormant (never armed): just release its channel + hook
            self._standby.stop()
        self.ckpt.stop()
        # tear down shm connections first: their serving threads may be
        # parked on the barrier CV or a ring doorbell, and closing the
        # rings unsticks both before the gRPC drain
        self.service.shm_server.close()
        if self._server is not None:
            self._server.stop(grace).wait()
