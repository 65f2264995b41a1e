"""Streaming data plane for the parameter-server service.

The reference moves every push/pull as ONE unary protobuf message
(reference proto/parameter_server.proto:5-11).  At config-3 scale (GBs of
tensors per push) a monolithic message serializes encode -> transport ->
decode, peaks at several whole-store-sized buffers, and hits gRPC's
message-size ceiling.  This framework extension moves the same payloads as
a STREAM of chunk messages, each carrying a subset of the tensors:

- ``PushGradientsStream`` (client-streaming): gRPC pulls the request
  iterator from a sender thread, so chunk N+1's fused encode
  (wire.ArrayPayload) overlaps chunk N's transport, and the server's
  per-chunk decode + f32 conversion overlaps receiving later chunks.
- ``ServeParametersStream`` (server-streaming): the server encodes and
  ships tensors chunk by chunk; the client converts each chunk while the
  next is in flight.
- ``PushPullStream`` (bidirectional): the fused synchronous step.  The
  client streams its gradient chunks; the server applies them, parks on
  the aggregation barrier (condition variable — core/ps_core.py
  ``wait_for_aggregation``), and streams the fresh parameter chunks back
  on the same call.  One RPC round replaces push + M× CheckSyncStatus
  polls + pull, and because the request side accepts a LAZY tensor
  iterator, the worker's bucketed D2H fetch ⊕ compress ⊕ encode ⊕
  transport all pipeline per bucket (worker/trainer.py GradientBuckets).

Chunks reuse the wire-compatible ``GradientUpdate`` / ``ParameterUpdate``
schemas (a chunk is just a smaller message), so nothing new exists at the
encoding layer.  Reference peers are unaffected: these are extra method
names on the same gRPC service, and :class:`PSClient` permanently falls
back to the reference's unary RPCs for a connection the first time the
server answers UNIMPLEMENTED — so it interoperates with a reference PS
unchanged.

A single tensor larger than the chunk budget rides alone in one oversized
chunk (tensors are never split mid-payload); the budget is a grouping
target, not a hard message cap.
"""

from __future__ import annotations

import functools
import logging
import os
import time
from typing import Callable, Iterable, Iterator, Sequence

import grpc

from ..delta.client import (DeltaBaseMismatch, DeltaPullState,
                            DeltaRoundResult, apply_frames)
from ..delta.messages import (DELTA_PS_METHODS, DeltaPullRequest,
                              DeltaPushChunk, delta_enabled)
from ..obs import flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from . import messages as m
from . import shm_transport
# The wire payload codec (ISSUE 6): every packed tensor payload on this
# data plane encodes/decodes through this narrow interface — PythonCodec
# is the byte-identity oracle and fallback, NativeCodec the zero-copy C++
# fast path selected per process via PSDT_NATIVE (see codec.py).
from .codec import (Codec, NativeCodec, PythonCodec,  # noqa: F401 — public
                    active_codec)
from .service import RpcClient
from .service import status_code as _status_code
from .wire import WT_LEN, WT_VARINT, _len_delimited_size, _tag, _varint_size, \
    _Writer, encode_fresh, encode_varint

log = logging.getLogger("pst.data_plane")

# Default chunk budget for streamed pushes/pulls.  Tens of MB amortizes
# per-message overhead while keeping encode/transport/decode pipelined;
# PSDT_STREAM_CHUNK_BYTES overrides, 0 disables streaming entirely.
DEFAULT_CHUNK_BYTES = 32 << 20


def stream_chunk_bytes() -> int:
    return int(os.environ.get("PSDT_STREAM_CHUNK_BYTES",
                              str(DEFAULT_CHUNK_BYTES)))


def bucket_bytes() -> int:
    """Bucket budget for the worker's incremental gradient D2H fetch
    (worker/trainer.py GradientBuckets).  Defaults to the stream chunk
    budget so D2H buckets and wire chunks stay aligned; PSDT_BUCKET_BYTES
    overrides independently (0 falls back to whole-store fetch)."""
    raw = os.environ.get("PSDT_BUCKET_BYTES")
    if raw is not None:
        return int(raw)
    return stream_chunk_bytes()


# Bytes decode_gradients copied out of a received frame so that its
# caller owns what it gets (the float32 wire's tensors arrive as
# read-only views of the frame; beside rpc.wire.fresh_bytes).  A sink
# that folds at once borrows the views and this stays still.
_obs_decode_copied = obs_stats.counter("rpc.server.decode.copied_bytes")


def decode_gradients(tensors: Iterable[m.Tensor], device: bool = False,
                     borrow: bool = False) -> dict:
    """Decode one push chunk's wire Tensors into fold-ready arrays.

    ``device=False`` (the default, and the only behavior before
    ISSUE 11): host numpy, owned and writable as ``Tensor.to_array``
    gives it — byte-identical to the pre-existing fold input.
    ``device=True`` (the serving core asked for device folds —
    ``ParameterServerCore.device_fold``): each
    packed payload lands as a jax device buffer with the dequantize
    running ON DEVICE (core/device_apply.tensor_to_device — int8 wire
    bytes cross the host boundary at a quarter of the f32 volume, bf16
    at half), so the accumulator sums and the sharded optimizer apply
    never round-trip through host numpy.

    ``borrow=True`` (host decode only) is for a consumer that reads the
    arrays before it asks for the next chunk and keeps none of them
    (``PushSink.folds_at_once``): a float32-wire tensor is then the
    READ-ONLY view of the frame it arrived in (``Tensor.borrow_array``),
    not a copy of it into new memory.  Packed wires unpack into new
    arrays either way."""
    if device:
        from ..core import device_apply

        return {t.name: device_apply.tensor_to_device(t) for t in tensors}
    out = {}
    copied = 0
    for t in tensors:
        arr = t.borrow_array()
        if not (borrow or arr.flags.writeable):
            copied += arr.nbytes
            arr = arr.copy()
        out[t.name] = arr
    if copied:
        _obs_decode_copied.add(copied)
    return out


def _tensor_nbytes(t: m.Tensor) -> int:
    if t.packed:
        return len(t.packed)
    data = t.data
    return getattr(data, "nbytes", 4 * len(data))


def split_tensors(tensors: Iterable[m.Tensor],
                  chunk_bytes: int) -> Iterator[list[m.Tensor]]:
    """Greedy-pack tensors into order-preserving chunks of roughly
    ``chunk_bytes`` payload each.  Cheap: only metadata is touched (the
    payloads are lazy ArrayPayloads or buffer views)."""
    group: list[m.Tensor] = []
    size = 0
    for t in tensors:
        n = _tensor_nbytes(t)
        if group and size + n > chunk_bytes:
            yield group
            group, size = [], 0
        group.append(t)
        size += n
    if group:
        yield group


_PARAMETERS_FIELD = 2  # m.ParameterUpdate.parameters
_ITERATION_FIELD = 1   # m.ParameterUpdate.iteration
_READY_FIELD = 3       # m.ParameterUpdate.ready


def encode_parameter_record_groups(
        groups: Sequence[Sequence[m.Tensor]],
        take: Callable[[int, int], memoryview],
        stripes: int | None = None) -> list[memoryview]:
    """Encode several chunk groups' ``ParameterUpdate.parameters`` bodies,
    fanning the per-group :func:`encode_parameter_records` passes across
    the shared stripe executor (core/stripes.py) when more than one group
    and more than one stripe are configured.  ``stripes`` is the serving
    core's resolved stripe count (so a ``ParameterServerCore(stripes=1)``
    serial escape hatch is honored here too, not only via PSDT_STRIPES);
    None falls back to the env/core-count default.  Group order is
    preserved and each group's bytes are exactly what the serial encode
    produces — the wire format is untouched, only WHICH thread runs each
    group's payload casts/packs changes (the numpy casts release the GIL,
    so a multi-chunk store encodes on multiple cores).

    Flat-arena stores (core/arena.py ArenaStore, ISSUE 15) feed this
    fan-out ZERO-COPY by construction: their tensor values are numpy
    views slicing the per-stripe readback slab by packing-table offset,
    so the payload casts/packs here read the slab directly instead of
    re-gathering per-tensor device buffers — and because view identity
    never changes the f32 values, the encoded bytes are byte-identical
    to the per-tensor path's.

    ``take(i, size)`` hands group ``i``'s encoder its destination (see
    :func:`encode_parameter_records`)."""
    from ..core.stripes import run_striped, stripe_count

    jobs = [functools.partial(encode_parameter_records, group,
                              functools.partial(take, i))
            for i, group in enumerate(groups)]
    if len(groups) <= 1 or stripe_count(stripes) <= 1:
        return [job() for job in jobs]
    return run_striped(jobs)


def encode_parameter_records(
        tensors: Iterable[m.Tensor],
        take: Callable[[int], memoryview]) -> memoryview:
    """Encode a group of wire Tensors ONCE into the exact bytes of
    ``ParameterUpdate.parameters`` (field 2) records — tag, length, and
    tensor body per element.  The server's encode-once broadcast cache
    (server/ps_service.py) stores these and replays them to every puller
    of the same (params version, wire dtype) via
    :class:`PreEncodedParameterUpdate`, so the per-tensor payload encode
    (f32→bf16 cast, repeated-float pack) runs once per version instead of
    once per pulling worker.

    The bytes go where the caller says: ``take(size)`` returns a writable
    view of exactly ``size`` bytes (the serve cache hands out the buffers
    of the version it retires, already touched), and a read-only view of
    it comes back."""
    items = [(t, t.encoded_size()) for t in tensors]
    out = take(sum(_len_delimited_size(_PARAMETERS_FIELD, size)
                   for _, size in items))
    writer = _Writer(out)
    for tensor, size in items:
        writer.write(_tag(_PARAMETERS_FIELD, WT_LEN))
        writer.write(encode_varint(size))
        tensor.encode_into(writer)
    assert writer.pos == len(out), (writer.pos, len(out))
    return out.toreadonly()


class PreEncodedParameterUpdate:
    """A ``ParameterUpdate`` whose ``parameters`` field is pre-encoded wire
    bytes (one or more :func:`encode_parameter_records` blobs).  Encodes
    byte-identically to ``m.ParameterUpdate(...)`` with the same content —
    field order 1, 2, 3 with proto3 default elision — so reference-shaped
    clients decode it indistinguishably.  Quacks like a codec Message
    (``encode`` / ``encoded_size`` / ``encode_into``), which is all the
    gRPC serializer and the ``PushPullResponse.params`` embedding need."""

    __slots__ = ("iteration", "ready", "bodies")

    def __init__(self, iteration: int, ready: bool,
                 bodies: Sequence[bytes]):
        self.iteration = int(iteration)
        self.ready = bool(ready)
        self.bodies = bodies

    def encoded_size(self) -> int:
        size = sum(len(b) for b in self.bodies)
        if self.iteration:
            size += (_varint_size(_ITERATION_FIELD << 3)
                     + _varint_size(self.iteration))
        if self.ready:
            size += _varint_size(_READY_FIELD << 3) + 1
        return size

    def encode_into(self, writer: "_Writer") -> None:
        if self.iteration:
            writer.write(_tag(_ITERATION_FIELD, WT_VARINT))
            writer.write(encode_varint(self.iteration))
        for body in self.bodies:
            writer.write(memoryview(body))
        if self.ready:
            writer.write(_tag(_READY_FIELD, WT_VARINT))
            writer.write(b"\x01")

    def encode(self) -> bytes:
        return encode_fresh(self.encoded_size(), self.encode_into)


class PSClient(RpcClient):
    """Parameter-server client with the streaming data plane.

    ``push_gradients`` / ``pull_parameters`` use the chunk-stream RPCs and
    transparently fall back (once, remembered per connection) to the
    reference unary RPCs when the server does not implement them.  All
    other methods are plain :meth:`RpcClient.call`.
    """

    # single-PS fused topology: the hierarchical-aggregation tier
    # (tiers/group_client.py) can interpose a same-host leaf aggregator
    # in front of this connection; the sharded fan-out client says False
    supports_tiers = True

    def __init__(self, target: str,
                 service: str = m.PARAMETER_SERVER_SERVICE,
                 methods=None, chunk_bytes: int | None = None):
        methods = dict(methods or m.PARAMETER_SERVER_METHODS)
        methods.update(m.PARAMETER_SERVER_STREAM_METHODS)
        methods.update(shm_transport.SHM_METHODS)
        methods.update(DELTA_PS_METHODS)
        super().__init__(target, service, methods)
        self.chunk_bytes = (stream_chunk_bytes() if chunk_bytes is None
                            else chunk_bytes)
        # None = untried; False = server answered UNIMPLEMENTED (reference
        # PS) — unary forever on this connection
        self._stream_ok: bool | None = None
        # same tri-state for the fused push→barrier→pull method
        self._fused_ok: bool | None = None
        # same-host shared-memory transport (rpc/shm_transport.py): None =
        # negotiation untried; False = permanently downgraded to TCP
        # (UNIMPLEMENTED / refused / attach failure / transport error) —
        # the PR-2 per-connection fallback discipline
        self._shm_conn: shm_transport.ShmClientConnection | None = None
        self._shm_ok: bool | None = None
        self._obs_shm_fallback = obs_stats.counter("rpc.shm.fallback")
        # versioned delta serving (delta/, ISSUE 10): the cached pull
        # this connection patches in place, and the same tri-state
        # downgrade latch as the other extensions — None = untried,
        # False = permanently full-serve (UNIMPLEMENTED / checksum
        # mismatch / version-bookkeeping failure)
        self._delta_state = DeltaPullState()
        self._delta_ok: bool | None = None

    def _streaming(self) -> bool:
        return self.chunk_bytes > 0 and self._stream_ok is not False

    def _fused(self) -> bool:
        return self.chunk_bytes > 0 and self._fused_ok is not False

    @property
    def shm_active(self) -> bool:
        """True once a same-host shared-memory connection is serving the
        fused rounds (worker logging/diagnostics)."""
        return self._shm_conn is not None and self._shm_ok is True

    def close(self) -> None:
        self._drop_shm(permanent=False)
        super().close()

    # ------------------------------------------------------- shm transport
    def _drop_shm(self, permanent: bool = True) -> None:
        conn, self._shm_conn = self._shm_conn, None
        if permanent:
            self._shm_ok = False
        if conn is not None:
            conn.close()

    def _shm_connection(self, timeout):
        """The negotiated shared-memory connection, negotiating on first
        use.  Returns None whenever the fused round should ride TCP —
        permanently after a refusal/UNIMPLEMENTED/attach failure, or just
        for this round when the negotiation RPC itself failed transiently."""
        if not shm_transport.enabled() or self._shm_ok is False:
            return None
        if self._shm_conn is not None:
            return self._shm_conn
        try:
            resp = self.call(
                "NegotiateShm",
                shm_transport.ShmNegotiateRequest(
                    host_id=shm_transport.host_id(),
                    ring_bytes=shm_transport.ring_bytes()),
                timeout=timeout if timeout else 10.0)
        except grpc.RpcError as exc:
            if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                # reference PS: no such method, TCP forever
                self._shm_ok = False
                self._obs_shm_fallback.add()
                flight.record("shm.downgrade", note="UNIMPLEMENTED")
            return None
        if not resp.accepted:
            log.info("shm transport refused by %s: %s", self._target,
                     resp.message)
            self._shm_ok = False
            self._obs_shm_fallback.add()
            flight.record("shm.downgrade", note="refused")
            return None
        try:
            self._shm_conn = shm_transport.ShmClientConnection(
                resp.c2s_name, resp.s2c_name, int(resp.ring_bytes),
                doorbell_addr=resp.doorbell)
        except (OSError, ValueError, ImportError) as exc:
            # segments not reachable from this process (container /dev/shm
            # isolation, permissions): same-host claim was wrong — TCP
            log.warning("shm segment attach failed (%s); using TCP", exc)
            self._shm_ok = False
            self._obs_shm_fallback.add()
            flight.record("shm.downgrade", note="attach failed")
            return None
        self._shm_ok = True
        log.info("shm transport active to %s (ring %d MB x2)",
                 self._target, int(resp.ring_bytes) >> 20)
        flight.record("shm.attach", b=int(resp.ring_bytes))
        return self._shm_conn

    # ------------------------------------------------------------------ push
    def push_gradients(self, update: m.GradientUpdate,
                       timeout: float | None = None) -> m.PushResponse:
        if not self._streaming():
            return self.call("ReceiveGradients", update, timeout=timeout)

        def chunks() -> Iterator[m.GradientUpdate]:
            # worker_id/iteration ride on every chunk (a handful of bytes);
            # the server reads them off the first.  An empty push still
            # sends ONE empty chunk: under the sharded topology a shard
            # owning none of the pushed tensors must still see the push as
            # a barrier contribution (worker/ps_shards.py).
            sent = False
            for group in split_tensors(update.gradients, self.chunk_bytes):
                sent = True
                yield m.GradientUpdate(worker_id=update.worker_id,
                                       iteration=update.iteration,
                                       gradients=group)
            if not sent:
                yield m.GradientUpdate(worker_id=update.worker_id,
                                       iteration=update.iteration,
                                       gradients=[])

        try:
            resp = self.call("PushGradientsStream", chunks(), timeout=timeout)
            self._stream_ok = True
            return resp
        except grpc.RpcError as exc:
            if _status_code(exc) != grpc.StatusCode.UNIMPLEMENTED:
                raise
            self._stream_ok = False
            return self.call("ReceiveGradients", update, timeout=timeout)

    # ------------------------------------------------------------------ delta
    def _delta(self) -> bool:
        """Whether the version-aware delta protocol should be attempted
        on this connection.  ``delta_enabled`` is read per round so tests
        and operators can flip PSDT_DELTA_DEPTH without rebuilding the
        client; the downgrade latch (UNIMPLEMENTED / checksum mismatch)
        is permanent per connection, like every other extension."""
        return (self.chunk_bytes > 0 and self._delta_ok is not False
                and delta_enabled())

    @property
    def held_version(self) -> int:
        """Store version of the cached pull deltas patch (-1 = none)."""
        return self._delta_state.version

    def _delta_downgrade(self, reason: str) -> None:
        """Permanent per-connection downgrade to the full-serve protocol
        (the PR-2 discipline).  The base may be partially patched after a
        failed apply, so it is dropped unconditionally."""
        self._delta_ok = False
        self._delta_state.invalidate()
        flight.record("serve.delta.downgrade", note=reason[:48])
        log.warning("delta serving permanently downgraded for %s: %s",
                    self._target, reason)

    def _delta_result(self, frames) -> DeltaRoundResult | None:
        """Fold a DeltaFrame stream, translating failures into the
        downgrade discipline: None = the caller must replay via the
        plain protocol (the PS-side per-(worker,tensor) dedup makes the
        replay of an already-landed push exact)."""
        try:
            result = apply_frames(frames, self._delta_state)
        except DeltaBaseMismatch as exc:
            self._delta_downgrade(f"base mismatch: {exc}")
            return None
        self._delta_ok = True
        return result

    def delta_pull(self, request: m.PullRequest,
                   timeout: float | None = None
                   ) -> DeltaRoundResult | None:
        """Version-aware unary pull (``PullParametersDelta``): advertises
        the held version, applies a served delta chain in place against
        the cached pull, and returns the round result (``result.store``
        is the fresh full store either way).  None = use the plain pull
        path (delta disabled or this connection downgraded)."""
        if not self._delta():
            return None
        req = DeltaPullRequest(worker_id=request.worker_id,
                               iteration=request.iteration,
                               wire_dtype=request.wire_dtype,
                               held_version=max(self.held_version, 0))
        try:
            frames = self.call("PullParametersDelta", req, timeout=timeout)
            return self._delta_result(frames)
        except grpc.RpcError as exc:
            if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                self._delta_downgrade("UNIMPLEMENTED (reference PS)")
                return None
            raise

    def delta_push_pull(self, worker_id: int, iteration: int, tensors_fn,
                        pull_wire_dtype: int = 0,
                        timeout: float | None = None
                        ) -> DeltaRoundResult | None:
        """The version-aware fused round (``PushPullDeltaStream``): the
        ordinary fused chunk stream wrapped with the held version, the
        response a delta chain applied in place (or a stamped full
        serve).  None = run the plain fused round instead — delta
        disabled/downgraded, or the connection prefers the same-host
        shared-memory rings (the shm transport speaks PushPullStream;
        on loopback, zero-copy beats delta byte savings and the wire is
        not the bottleneck anyway)."""
        if not self._delta():
            return None
        if shm_transport.enabled() and self._shm_ok is not False:
            return None
        held = max(self.held_version, 0)

        def chunks() -> Iterator[DeltaPushChunk]:
            # held_version and pull_wire_dtype ride the first chunk only
            # (the server reads header fields off it); an empty push
            # still sends one empty chunk (see push_gradients)
            first = True
            for group in split_tensors(tensors_fn(), self.chunk_bytes):
                yield DeltaPushChunk(
                    update=m.GradientUpdate(
                        worker_id=worker_id, iteration=iteration,
                        gradients=group,
                        pull_wire_dtype=pull_wire_dtype if first else 0),
                    held_version=held if first else 0)
                first = False
            if first:
                yield DeltaPushChunk(
                    update=m.GradientUpdate(worker_id=worker_id,
                                            iteration=iteration,
                                            gradients=[],
                                            pull_wire_dtype=pull_wire_dtype),
                    held_version=held)

        try:
            frames = self.call("PushPullDeltaStream", chunks(),
                               timeout=timeout)
            result = self._delta_result(frames)
        except grpc.RpcError as exc:
            if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                self._delta_downgrade("UNIMPLEMENTED (reference PS)")
                return None
            raise
        if result is not None:
            # the server just proved it speaks the fused protocol family
            self._fused_ok = True
        return result

    # ------------------------------------------------------------------ fused
    def push_pull(self, worker_id: int, iteration: int, tensors,
                  pull_wire_dtype: int = 0, timeout: float | None = None,
                  on_chunk=None) -> tuple[m.PushResponse,
                                          m.ParameterUpdate | None]:
        """Fused synchronous step over ``PushPullStream``: stream the
        gradient chunks, let the server barrier-wait, receive the fresh
        parameter chunks — one data-plane round.

        ``tensors``: an iterable of wire Tensors, or a ZERO-ARG CALLABLE
        returning a fresh iterator (required when the tensors materialize
        lazily, e.g. bucketed D2H fetch — the unary fallback re-reads
        them, and a half-consumed generator cannot be replayed).
        ``on_chunk``: same contract as :meth:`pull_parameters`.

        Returns ``(push_response, parameter_update | None)``.  The second
        element is ``None`` whenever fresh parameters were NOT delivered
        on this round — fused method unimplemented (reference server),
        push rejected, or server-side barrier timeout — and the caller
        must fall back to its own barrier-wait + pull.  The fallback is
        remembered per connection, exactly like the chunk-stream RPCs."""
        tensors_fn = tensors if callable(tensors) else lambda: iter(tensors)
        if not self._fused():
            return self._push_only(worker_id, iteration, tensors_fn,
                                   timeout), None

        def chunks() -> Iterator[m.GradientUpdate]:
            # pull_wire_dtype rides the first chunk only (the server reads
            # header fields off it); an empty push still sends one empty
            # chunk — the sharded-topology barrier invariant (see
            # push_gradients)
            first = True
            for group in split_tensors(tensors_fn(), self.chunk_bytes):
                yield m.GradientUpdate(
                    worker_id=worker_id, iteration=iteration,
                    gradients=group,
                    pull_wire_dtype=pull_wire_dtype if first else 0)
                first = False
            if first:
                yield m.GradientUpdate(worker_id=worker_id,
                                       iteration=iteration, gradients=[],
                                       pull_wire_dtype=pull_wire_dtype)

        # Same-host fast path: the SAME chunk messages, encoded straight
        # into the shared-memory ring (the ring is the encoder's
        # destination: no frame-sized buffer on this side) instead of
        # into a `bytes` for the gRPC channel.  Any shm failure
        # downgrades this connection to TCP permanently and the round is
        # replayed below (tensors_fn is replayable by contract).
        conn = self._shm_connection(timeout)
        if conn is not None:
            # a shm round IS a fused PushPullStream round, just not over
            # gRPC: count it under the same call/latency instruments so
            # rounds-per-step accounting stays transport-independent
            # (payload bytes land in rpc.shm.bytes instead), give it the
            # same client span, and stamp the trace context on every
            # chunk — the ring transport bypasses RpcClient.call, which
            # is where the field-999 plumbing normally happens
            calls, latency, _ = self._instruments["PushPullStream"]
            calls.add()
            t0 = time.perf_counter()
            flight.record("rpc.cli.start", note="PushPull/shm")
            ok = False
            try:
                with obs_trace.span("rpc/client/PushPullStream",
                                    target=self._target, transport="shm"):
                    ctx = obs_trace.wire_context()

                    def stamped() -> Iterator[m.GradientUpdate]:
                        for chunk in chunks():
                            if ctx:
                                chunk.trace_context = ctx
                            yield chunk

                    def decoded(frames) -> Iterator[m.PushPullResponse]:
                        for f in frames:
                            with obs_trace.span("rpc/client/decode",
                                                bytes=len(f)):
                                frame = m.PushPullResponse.decode(f)
                            yield frame

                    # each response frame is decoded and handed to
                    # on_chunk as it leaves the ring, inside the
                    # connection's round lock
                    result = conn.round_trip(
                        stamped(), timeout,
                        lambda frames: self._assemble_fused(
                            decoded(frames), on_chunk))
                # the server just proved it speaks the fused protocol
                self._fused_ok = True
                ok = True
                return result
            except shm_transport.ShmTransportError as exc:
                log.warning("shm fused round failed (%s); permanently "
                            "downgrading %s to TCP", exc, self._target)
                flight.record("shm.downgrade", note="round failed")
                self._obs_shm_fallback.add()
                self._drop_shm()
            finally:
                latency.observe(time.perf_counter() - t0)
                flight.record("rpc.cli.end",
                              a=int(1e6 * (time.perf_counter() - t0)),
                              b=1 if ok else 0, note="PushPull/shm")

        try:
            result = self._assemble_fused(
                self.call("PushPullStream", chunks(), timeout=timeout),
                on_chunk)
            self._fused_ok = True
            return result
        except grpc.RpcError as exc:
            if _status_code(exc) != grpc.StatusCode.UNIMPLEMENTED:
                raise
            self._fused_ok = False
            return self._push_only(worker_id, iteration, tensors_fn,
                                   timeout), None

    @staticmethod
    def _assemble_fused(frames, on_chunk) -> tuple[m.PushResponse,
                                                   m.ParameterUpdate | None]:
        """Fold a ``PushPullResponse`` frame stream (gRPC call or decoded
        shm frames — identical bytes, identical semantics) into the
        ``(push, params | None)`` result."""
        push: m.PushResponse | None = None
        merged: list[m.Tensor] = []
        params_iteration, ready, got_params = 0, False, False
        for frame in frames:
            if frame.push is not None and push is None:
                push = frame.push
            if frame.params is not None:
                got_params = True
                chunk = frame.params
                params_iteration, ready = chunk.iteration, chunk.ready
                if on_chunk is not None:
                    on_chunk(chunk.parameters)
                    merged.extend(
                        m.Tensor(name=t.name,
                                 packed_dtype=t.packed_dtype)
                        for t in chunk.parameters)
                else:
                    merged.extend(chunk.parameters)
        if push is None:
            return m.PushResponse(success=False,
                                  message="empty fused response"), None
        if not (got_params and ready):
            return push, None
        return push, m.ParameterUpdate(iteration=params_iteration,
                                       parameters=merged, ready=True)

    def _push_only(self, worker_id: int, iteration: int, tensors_fn,
                   timeout) -> m.PushResponse:
        """Degraded fused call: push leg only (chunk-streamed when the
        server supports it, unary otherwise); the caller supplies the
        barrier-wait and pull."""
        update = m.GradientUpdate(worker_id=worker_id, iteration=iteration,
                                  gradients=list(tensors_fn()))
        return self.push_gradients(update, timeout=timeout)

    # ------------------------------------------------------------------ pull
    def pull_parameters(self, request: m.PullRequest,
                        timeout: float | None = None,
                        on_chunk=None) -> m.ParameterUpdate:
        """Returns one merged ParameterUpdate (chunks are concatenated in
        server order, so the result is indistinguishable from the unary
        response).

        ``on_chunk(tensors)``: optional per-chunk consumer called as each
        chunk ARRIVES — the worker converts tensors to f32 arrays there,
        overlapping conversion with the transport of later chunks.  The
        consumed tensors still appear in the returned message (the
        consumer must not mutate them); on the unary fallback it is
        called once with the whole list, so callers behave identically
        either way."""
        def unary_pull() -> m.ParameterUpdate:
            resp = self.call("ServeParameters", request, timeout=timeout)
            if on_chunk is not None:
                on_chunk(resp.parameters)
            return resp

        if not self._streaming():
            return unary_pull()
        try:
            chunks = self.call("ServeParametersStream", request,
                               timeout=timeout)
            merged: list[m.Tensor] = []
            iteration, ready = 0, False
            got_any = False
            for chunk in chunks:
                got_any = True
                iteration, ready = chunk.iteration, chunk.ready
                if on_chunk is not None:
                    on_chunk(chunk.parameters)
                    # the consumer took the payloads; retain only the
                    # metadata callers read off the response (name +
                    # packed_dtype for wire negotiation) — holding the
                    # full wire copy alongside the converted store would
                    # double peak pull memory at GB scale
                    merged.extend(
                        m.Tensor(name=t.name, packed_dtype=t.packed_dtype)
                        for t in chunk.parameters)
                else:
                    merged.extend(chunk.parameters)
            self._stream_ok = True
            if not got_any:  # zero-chunk stream: treat as an empty store
                return unary_pull()
            return m.ParameterUpdate(iteration=iteration, parameters=merged,
                                     ready=ready)
        except grpc.RpcError as exc:
            if _status_code(exc) != grpc.StatusCode.UNIMPLEMENTED:
                raise
            self._stream_ok = False
            return unary_pull()
