"""Front-door stream router for the decode fleet (``pst-route``).

The router speaks the SAME ``psdt_fleet.Decode`` service it routes to —
a client cannot tell a router from a single decode server, which is the
downgrade matrix: no router => point ``pst-serve`` clients at the one
server, byte-unchanged.

Admission: each incoming ``SubmitStream`` picks the best ACTIVE backend
by **free-slot / queue-depth score plus cached-prefix overlap** (free
slots plus ``PSDT_ROUTE_OVERLAP_WEIGHT`` per leading prompt block
already in the backend's radix prefix cache — fingerprints ride the
``UpdateFleet`` heartbeats, models/prefix_tree.py — then shortest
queue tie-break, server id as the stable final tie-break) from the
coordinator's fleet table (TTL-polled over ``UpdateFleet``; the router
additionally debits a claim per stream it routed since the last poll,
so a burst between polls spreads instead of dogpiling the
momentarily-best server).  Backends without a fingerprint (cache off,
pre-radix builds) score zero overlap, so the order degrades to exactly
the PR 14 free-slot score.  The stream is then **pinned**: every chunk of
its lifetime relays from that one backend — a mid-stream weight rollout
on the backend swaps the version under the stream (PR 10 semantics, the
tokens keep flowing), and the router never re-routes a live
continuation, which is what makes rolling updates zero-drop.

DRAINING backends take no new streams but keep their pinned ones; a
backend that dies mid-stream surfaces as that stream's error chunk
(the decode context is gone — re-routing a continuation would silently
restart the generation)."""

from __future__ import annotations

import logging
import threading
import time

import grpc

import os

from ..analysis.lock_order import checked_lock
from ..models.prefix_tree import block_hashes, overlap_blocks, unpack_fp
from ..obs import flight
from ..obs import stats as obs_stats
from ..rpc import messages as m
from ..rpc.service import RpcClient, make_server
from ..rpc.service import status_code as _status_code
from . import messages as fmsg

log = logging.getLogger("pst.fleet.router")


def overlap_weight() -> float:
    """Free-slot-equivalents one reusable prefix block is worth in the
    routing score (``PSDT_ROUTE_OVERLAP_WEIGHT``): cache affinity may
    outbid up to ``weight * blocks`` free slots, never an infinite
    amount — a backend with a hot prefix but a long queue still loses
    to an idle one eventually.  0 disables prefix-aware routing."""
    return float(os.environ.get("PSDT_ROUTE_OVERLAP_WEIGHT", "1.0"))


def score_backends(entries, claims: dict[int, int] | None = None,
                   prompt_hashes=None, weight: float = 1.0) -> list:
    """ACTIVE backends ordered best-first: free slots (minus the
    router's own un-heartbeaten claims) PLUS cached-prefix overlap —
    each leading block of the prompt already in a backend's radix cache
    (``prompt_hashes`` vs the entry's heartbeated ``prefix_fp``) counts
    as ``weight`` free slots — then shortest queue, then server id.
    Pure — the unit-testable policy.  Without prompt hashes, or against
    entries with no fingerprint (cache off, pre-radix builds), every
    overlap is zero and the order is exactly the PR 14 free-slot/
    queue-depth score (the downgrade matrix)."""
    claims = claims or {}
    live = [e for e in entries if int(e.state) == fmsg.MEMBER_ACTIVE]

    def affinity(e) -> float:
        fp = bytes(getattr(e, "prefix_fp", b""))
        if not prompt_hashes or not fp or not weight:
            return 0.0
        return weight * overlap_blocks(prompt_hashes, unpack_fp(fp))

    return sorted(
        live,
        key=lambda e: (-(int(e.free_slots)
                         - claims.get(int(e.server_id), 0)
                         + affinity(e)),
                       int(e.queue_depth), int(e.server_id)))


class FleetRouter:
    """See module docstring."""

    def __init__(self, coordinator: str, *, port: int = 0,
                 bind_address: str = "127.0.0.1",
                 poll_s: float = 0.5):
        self._coordinator = coordinator
        self._bind = f"{bind_address}:{int(port)}"
        self._poll_s = float(poll_s)
        # Guards the backend table, per-backend claims, the backend
        # client cache, and the poll-in-flight flag (leaf —
        # analysis/lock_order.py rank 75).
        self._lock = checked_lock("FleetRouter._lock")
        # Poll single-flight is a FLAG under _lock, not a lock held
        # across the RPC: while one thread refreshes, every other
        # admission routes on the last-known table + claims instead of
        # queueing behind a coordinator round-trip (a slow coordinator
        # would otherwise add its full RPC timeout to fleet-wide TTFT).
        self._polling = False
        self._entries: list = []
        self._table_at = 0.0
        self._epoch = 0
        self._claims: dict[int, int] = {}
        self._clients: dict[str, RpcClient] = {}
        self._next_stream = 0
        self.streams_routed = 0
        # prefix blocks of the last routed prompt already cached on the
        # chosen backend (0 = no reusable prefix / fingerprints absent)
        self._obs_overlap = obs_stats.gauge("fleet.route_overlap")
        self._coord = RpcClient(coordinator, m.COORDINATOR_SERVICE,
                                fmsg.FLEET_COORD_METHODS)
        self._grpc = None
        self.port = 0

    # ----------------------------------------------------------- lifecycle
    def start(self) -> int:
        from ..rpc.service import bind_service
        self._grpc = make_server(max_workers=32)
        bind_service(self._grpc, fmsg.DECODE_SERVICE, fmsg.DECODE_METHODS,
                     self)
        self.port = self._grpc.add_insecure_port(self._bind)
        if self.port == 0:
            raise RuntimeError(f"could not bind {self._bind}")
        self._grpc.start()
        return self.port

    def stop(self, grace: float = 1.0) -> None:
        if self._grpc is not None:
            self._grpc.stop(grace).wait()
        with self._lock:
            clients, self._clients = dict(self._clients), {}
        for client in clients.values():
            client.close()
        self._coord.close()

    def wait(self) -> None:
        assert self._grpc is not None
        self._grpc.wait_for_termination()

    # ------------------------------------------------------------- routing
    def _refresh_table(self, force: bool = False) -> None:
        """TTL refresh of the fleet table.  Non-blocking for everyone
        but the one thread that actually polls: a stale-but-usable
        table plus claims beats queueing admissions behind a
        coordinator RPC.  ``force`` polls even when fresh (the
        empty-table retry and the Control STATUS probe) but still
        yields to a poll already in flight."""
        with self._lock:
            fresh = (time.monotonic() - self._table_at < self._poll_s)
            if (fresh and not force) or self._polling:
                return
            self._polling = True
        try:
            resp = self._coord.call(
                "UpdateFleet",
                fmsg.FleetRequest(server_id=-1,
                                  action=fmsg.FLEET_QUERY),
                timeout=2.0)
        except grpc.RpcError as exc:
            if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                log.warning("coordinator does not speak UpdateFleet; "
                            "router has no fleet to route to")
            return  # transient: keep the last table
        finally:
            with self._lock:
                self._polling = False
        with self._lock:
            self._entries = list(resp.entries)
            self._epoch = int(resp.epoch)
            self._table_at = time.monotonic()
            self._claims.clear()  # the table now reflects past claims

    def _pick_backend(self, prompt_tokens=None):
        """Best backend entry or None.  Debits a claim so concurrent
        admissions between polls spread across the fleet.  An empty
        view retries briefly (force-polling, yielding to a poll already
        in flight) before rejecting — a cold router's second concurrent
        admission must not bounce just because the first one's table
        poll has not landed yet.  ``prompt_tokens`` turns on
        prefix-aware placement: the prompt's block hashes are scored
        against each backend's heartbeated radix fingerprint, so
        streams sharing a system prompt pin to the backend already
        holding it."""
        hashes = block_hashes(prompt_tokens) if prompt_tokens else None
        weight = overlap_weight()
        self._refresh_table()
        deadline = time.monotonic() + 2.0
        while True:
            with self._lock:
                ranked = score_backends(self._entries, self._claims,
                                        hashes, weight)
                if ranked:
                    best = ranked[0]
                    sid = int(best.server_id)
                    self._claims[sid] = self._claims.get(sid, 0) + 1
                    if hashes:
                        self._obs_overlap.set(overlap_blocks(
                            hashes, unpack_fp(bytes(
                                getattr(best, "prefix_fp", b"")))))
                    return best
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.05)
            self._refresh_table(force=True)

    def _backend_client(self, address: str) -> RpcClient:
        with self._lock:
            client = self._clients.get(address)
            if client is None:
                client = RpcClient(address, fmsg.DECODE_SERVICE,
                                   fmsg.DECODE_METHODS)
                self._clients[address] = client
            return client

    # ---------------------------------------------------------------- gRPC
    def SubmitStream(self, request: fmsg.DecodeRequest, context):
        backend = self._pick_backend([int(t) for t in request.tokens])
        if backend is None:
            yield fmsg.DecodeChunk(error="no decode servers available",
                                   done=True)
            return
        with self._lock:
            self._next_stream += 1
            stream_id = self._next_stream
        sid = int(backend.server_id)
        flight.record("fleet.route", a=stream_id, b=sid,
                      note=backend.address[:48])
        self.streams_routed += 1
        client = self._backend_client(backend.address)
        try:
            # pinned for the stream's lifetime: every chunk relays from
            # this one backend, mid-rollout swaps included
            for chunk in client.call("SubmitStream", request,
                                     timeout=None):
                yield chunk
                if chunk.done:
                    return
        except grpc.RpcError as exc:
            # the backend died mid-stream: its decode context is gone,
            # so the honest answer is an error, not a silent restart
            yield fmsg.DecodeChunk(
                error=f"backend {sid} lost mid-stream "
                      f"({_status_code(exc)})", done=True)

    def Control(self, request: fmsg.DecodeControlRequest,
                context) -> fmsg.DecodeControlResponse:
        """The router's own status: backends visible, streams routed.
        Management actions target servers, not the router."""
        if int(request.action) != fmsg.CTRL_STATUS:
            return fmsg.DecodeControlResponse(
                success=False,
                message="router: only STATUS is supported here; address "
                        "Control to a decode server")
        self._refresh_table()
        with self._lock:
            active = [e for e in self._entries
                      if int(e.state) == fmsg.MEMBER_ACTIVE]
            return fmsg.DecodeControlResponse(
                success=True,
                message=f"router: {len(active)} active backends "
                        f"(fleet epoch {self._epoch})",
                server_id=-1,
                slots=sum(int(e.slots) for e in active),
                free_slots=sum(int(e.free_slots) for e in active),
                queue_depth=sum(int(e.queue_depth) for e in active),
                streams_served=self.streams_routed)
