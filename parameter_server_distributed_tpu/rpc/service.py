"""gRPC plumbing: generic service binding + typed clients.

The reference generates C++ service/stub classes with grpc_cpp_plugin
(reference: CMakeLists.txt:87-113).  Here the equivalent binding is done at
runtime through gRPC's generic-handler API with the wire codec from
`wire.py`, so no gencode is needed while remaining wire-compatible with the
reference's services (method paths `/parameter_server.ParameterServer/<M>`
and `/coordinator.Coordinator/<M>`).

One deliberate departure: the reference opens a **fresh channel per call**
on the worker hot path (reference: src/worker.cpp:241, 255, 275, 219) —
connection setup per RPC.  Clients here hold one persistent channel.

Both ends of every RPC are instrumented through the observability
subsystem (obs/): per-method call counts, latency histograms, and
request/response byte counters are always on (a few dict ops per call —
bounded overhead), and when tracing is enabled the client opens a span
whose context rides the request's extension field so the server handler's
span joins the caller's trace (obs/trace.py).  Latency for a
``unary_stream`` client call covers dispatch only (the response iterator
outlives the call); byte counters still see every chunk because they live
in the (de)serializers.
"""

from __future__ import annotations

import concurrent.futures
import time
from typing import Any, Callable, Iterator, Mapping

import grpc

from ..obs import flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from .wire import Message


def _spec(entry) -> tuple[type[Message], type[Message], str]:
    """Normalize a method-table entry: (req, resp) -> unary-unary, or
    (req, resp, style) with style in unary | stream_unary | unary_stream
    | stream_stream."""
    if len(entry) == 2:
        req_cls, resp_cls = entry
        return req_cls, resp_cls, "unary"
    req_cls, resp_cls, style = entry
    return req_cls, resp_cls, style


def _counting_deserializer(decode: Callable, counter) -> Callable:
    def deserialize(buf):
        counter.add(len(buf))
        return decode(buf)
    return deserialize


def _counting_serializer(counter) -> Callable:
    def serialize(msg: Message) -> bytes:
        data = msg.encode()
        counter.add(len(data))
        return data
    return serialize


def _instrument_handler(behavior: Callable, method: str, style: str):
    """Wrap a service method with call/latency accounting and a server
    span that adopts the caller's trace context (when the request message
    carries the extension field and tracing is on)."""
    calls = obs_stats.counter(f"rpc.server.{method}.calls")
    latency = obs_stats.histogram(f"rpc.server.{method}.latency_s")
    span_name = f"rpc/server/{method}"

    def flight_end(t0: float) -> None:
        # both-ends flight evidence: the handler's end stamp with its
        # wall time — a crash mid-handler leaves the start stamp open,
        # which is exactly the "in flight at death" witness
        flight.record("rpc.srv.end", a=int(1e6 * (time.perf_counter() - t0)),
                      note=method)

    if style == "stream_unary":
        def stream_unary(request_iterator, context):
            calls.add()
            t0 = time.perf_counter()
            flight.record("rpc.srv.start", note=method)
            # the remote context arrives on the FIRST chunk, after the
            # handler has started — SpanHolder defers adoption
            holder = obs_trace.SpanHolder(span_name)

            def chunks():
                for req in request_iterator:
                    holder.adopt(getattr(req, "trace_context", b""),
                                 getattr(req, "iteration", None))
                    yield req

            try:
                return behavior(chunks(), context)
            finally:
                holder.finish()
                latency.observe(time.perf_counter() - t0)
                flight_end(t0)
        return stream_unary

    if style == "stream_stream":
        def stream_stream(request_iterator, context):
            calls.add()
            t0 = time.perf_counter()
            flight.record("rpc.srv.start", note=method)
            # like stream_unary, the remote context arrives on the first
            # request chunk, after the handler has started
            holder = obs_trace.SpanHolder(span_name)

            def chunks():
                for req in request_iterator:
                    holder.adopt(getattr(req, "trace_context", b""),
                                 getattr(req, "iteration", None))
                    yield req

            def stream():
                try:
                    yield from behavior(chunks(), context)
                finally:
                    holder.finish()
                    latency.observe(time.perf_counter() - t0)
                    flight_end(t0)
            return stream()
        return stream_stream

    if style == "unary_stream":
        def unary_stream(request, context):
            calls.add()
            t0 = time.perf_counter()
            flight.record("rpc.srv.start", note=method)
            ctx = getattr(request, "trace_context", b"")

            def stream():
                try:
                    with obs_trace.server_span(span_name, ctx):
                        yield from behavior(request, context)
                finally:
                    latency.observe(time.perf_counter() - t0)
                    flight_end(t0)
            return stream()
        return unary_stream

    def unary(request, context):
        calls.add()
        t0 = time.perf_counter()
        flight.record("rpc.srv.start", note=method)
        try:
            with obs_trace.server_span(
                    span_name, getattr(request, "trace_context", b"")):
                return behavior(request, context)
        finally:
            latency.observe(time.perf_counter() - t0)
            flight_end(t0)
    return unary


def bind_service(server: grpc.Server, service_name: str,
                 methods: Mapping[str, tuple],
                 impl: Any) -> None:
    """Register ``impl`` on ``server``: for each method M, ``impl.M(request,
    context)`` must exist and return the response message (for
    ``stream_unary`` the first argument is a request iterator; for
    ``unary_stream`` the method returns an iterator of responses)."""
    handlers = {}
    for method, entry in methods.items():
        req_cls, resp_cls, style = _spec(entry)
        make_handler = {
            "unary": grpc.unary_unary_rpc_method_handler,
            "stream_unary": grpc.stream_unary_rpc_method_handler,
            "unary_stream": grpc.unary_stream_rpc_method_handler,
            "stream_stream": grpc.stream_stream_rpc_method_handler,
        }[style]
        handlers[method] = make_handler(
            _instrument_handler(getattr(impl, method), method, style),
            request_deserializer=_counting_deserializer(
                req_cls.decode,
                obs_stats.counter(f"rpc.server.{method}.request_bytes")),
            response_serializer=_counting_serializer(
                obs_stats.counter(f"rpc.server.{method}.response_bytes")),
        )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(service_name, handlers),))


# Shared channel/server options.  The HTTP/2 tuning matters for the bulk
# data plane: the default 16KB frame size caps loopback/LAN throughput at a
# fraction of line rate for tensor-sized messages (measured ~2x on streamed
# chunks with 16MB frames); the larger write buffer keeps the transport fed
# while the next chunk encodes.
CHANNEL_OPTIONS = [
    ("grpc.max_send_message_length", 1 << 30),
    ("grpc.max_receive_message_length", 1 << 30),
    ("grpc.http2.max_frame_size", 16 << 20),
    ("grpc.http2.write_buffer_size", 64 << 20),
]


def status_code(exc: grpc.RpcError):
    """Status code of an RpcError, or None for errors that carry none
    (e.g. fault-injection stubs raising bare grpc.RpcError)."""
    code = getattr(exc, "code", None)
    return code() if callable(code) else None


def make_server(max_workers: int = 8) -> grpc.Server:
    return grpc.server(
        concurrent.futures.ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="rpc-handler"),
        options=CHANNEL_OPTIONS)


def _inject_stream(request_iterator, ctx: bytes) -> Iterator[Message]:
    """Stamp the trace context on every chunk of a client-streamed request
    (gRPC pulls the iterator from its own sender thread, so the context is
    captured eagerly on the calling thread)."""
    for req in request_iterator:
        if hasattr(req, "trace_context"):
            req.trace_context = ctx
        yield req


class RpcClient:
    """Typed unary-unary client over one persistent insecure channel
    (the reference uses insecure channels throughout —
    src/worker.cpp:143, parameter_server_service.cpp:181)."""

    def __init__(self, target: str, service_name: str,
                 methods: Mapping[str, tuple]):
        self._target = target
        self._channel = grpc.insecure_channel(target,
                                              options=CHANNEL_OPTIONS)
        self._calls: dict[str, Callable] = {}
        # per-method instruments, resolved once (registry lookups are
        # locked dict ops; the hot path should only touch the instruments)
        self._instruments: dict[str, tuple] = {}
        for method, entry in methods.items():
            req_cls, resp_cls, style = _spec(entry)
            make_call = {
                "unary": self._channel.unary_unary,
                "stream_unary": self._channel.stream_unary,
                "unary_stream": self._channel.unary_stream,
                "stream_stream": self._channel.stream_stream,
            }[style]
            self._calls[method] = make_call(
                f"/{service_name}/{method}",
                request_serializer=_counting_serializer(
                    obs_stats.counter(f"rpc.client.{method}.request_bytes")),
                response_deserializer=_counting_deserializer(
                    resp_cls.decode,
                    obs_stats.counter(
                        f"rpc.client.{method}.response_bytes")),
            )
            self._instruments[method] = (
                obs_stats.counter(f"rpc.client.{method}.calls"),
                obs_stats.histogram(f"rpc.client.{method}.latency_s"),
                style)

    def call(self, method: str, request: Message, timeout: float | None = None):
        """Unary call.  For a ``stream_unary`` or ``stream_stream`` method
        pass an ITERATOR of request messages (gRPC pulls it from a sender
        thread, so per-chunk encode overlaps transport); ``unary_stream``
        and ``stream_stream`` return an iterator of response messages that
        decode as chunks arrive."""
        calls, latency, style = self._instruments[method]
        calls.add()
        t0 = time.perf_counter()
        flight.record("rpc.cli.start", note=method)
        ok = False
        try:
            if not obs_trace.enabled():
                resp = self._calls[method](request, timeout=timeout)
                ok = True
                return resp
            with obs_trace.span(f"rpc/client/{method}", target=self._target):
                ctx = obs_trace.wire_context()
                if style in ("stream_unary", "stream_stream"):
                    request = _inject_stream(request, ctx)
                elif ctx and hasattr(request, "trace_context"):
                    request.trace_context = ctx
                resp = self._calls[method](request, timeout=timeout)
                ok = True
                return resp
        finally:
            latency.observe(time.perf_counter() - t0)
            flight.record("rpc.cli.end",
                          a=int(1e6 * (time.perf_counter() - t0)),
                          b=1 if ok else 0, note=method)

    def close(self) -> None:
        self._channel.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
