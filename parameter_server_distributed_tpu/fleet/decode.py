"""Fleet-facing decode server: the gRPC face of models/serving.DecodeServer.

One :class:`FleetDecodeServer` wraps one continuous-batching
:class:`~..models.serving.DecodeServer` behind the ``psdt_fleet.Decode``
gRPC service and runs three loops:

- the **decode loop** (the ONLY thread that touches the DecodeServer):
  admits queued requests into free slots between ``step()`` rounds,
  streams each newly decoded token to its request's output queue, and
  applies weight swaps/commands at round boundaries — continuous
  batching under an open-loop arrival process, no drain-the-batch
  barrier anywhere;
- the **membership loop** (when a coordinator address is given):
  ``UpdateFleet`` register + heartbeat-cadence load reports (free
  slots, queue depth, serving version), which double as the drain
  signal — a coordinator-side drain (scale-in, ``pst-ctl``) is seen on
  the next beat, the server stops admitting, finishes its in-flight
  streams, and leaves.  A reference coordinator answers UNIMPLEMENTED
  => permanent standalone downgrade (the PR-2/PR-13 discipline);
- the optional **weight feed**: a :class:`~..delta.subscriber
  .WeightFollower` (PR 10) polled between rounds fills the bounded
  version store.  Standalone servers auto-advance to each version as it
  lands (exactly ``pst-serve --follow``); fleet-registered servers hold
  versions and swap when the controller says so (the rolling update),
  unless ``auto_advance`` is forced.

Version skew is first-class: every streamed chunk is stamped with the
params version that decoded it, ``Control(ROLLBACK, v)`` swaps back to a
held version AND pins there — publications newer than the pin are held
but never served until ``Control(UNPIN)`` — so a rolled-back fleet can
never leak a newer-version continuation (tested).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import OrderedDict
from typing import Callable

import grpc

from ..analysis.lock_order import checked_lock
from ..elastic import messages as emsg
from ..obs import flight
from ..rpc import messages as m
from ..rpc.service import RpcClient, make_server
from ..rpc.service import status_code as _status_code
from . import messages as fmsg

log = logging.getLogger("pst.fleet.decode")

# Serializes jax dispatch across colocated decode servers (tests, bench,
# single-host fleets run several FleetDecodeServers in one process;
# concurrent dispatch deadlocks the CPU client — the same hazard
# worker/trainer.py's _DISPATCH_LOCK guards).  Uncontended when each
# server runs in its own process, which is the production shape.
_DISPATCH_LOCK = checked_lock("decode._DISPATCH_LOCK")


class _Stream:
    """One admitted (or queued) request: its parsed fields and the
    queue its chunks flow out on (None = end of stream).  ``cancelled``
    is set by the handler when the client is gone (disconnect, stall
    timeout): a cancelled stream is never admitted from the queue, and
    an in-flight one has its slot freed at the next round — an
    abandoned request must not burn max_new decode rounds into a queue
    nobody reads."""

    __slots__ = ("tokens", "max_new", "temperature", "stop", "out",
                 "request_id", "cancelled")

    def __init__(self, tokens, max_new, temperature, stop):
        self.tokens = tokens
        self.max_new = max_new
        self.temperature = temperature
        self.stop = stop
        self.out: "queue.Queue[fmsg.DecodeChunk | None]" = queue.Queue()
        self.request_id = -1
        self.cancelled = False


class _CommandBox:
    """Outcome channel for one decode-loop command: the Control handler
    waits on ``done`` and reads ``ok``/``why``."""

    __slots__ = ("done", "ok", "why")

    def __init__(self):
        self.done = threading.Event()
        self.ok = False
        self.why = ""


def box_ok(box: _CommandBox | None) -> None:
    if box is not None:
        box.ok = True
        box.done.set()


def box_fail(box: _CommandBox | None, why: str) -> None:
    if box is not None:
        box.why = why
        box.done.set()


class FleetDecodeServer:
    """See module docstring.  ``transform`` is applied to every published
    store before it swaps in (the int8 weight-quantization binding from
    cli/serve_main.py — boot weights and every fleet swap must quantize
    identically or not at all)."""

    def __init__(self, server, *, server_id: int = 0, port: int = 0,
                 bind_address: str = "127.0.0.1",
                 coordinator: str | None = None,
                 follower=None, auto_advance: bool | None = None,
                 transform: Callable[[dict], dict] | None = None,
                 versions_kept: int = 4, heartbeat_s: float = 0.5):
        self.server = server
        self.server_id = int(server_id)
        self._bind = f"{bind_address}:{int(port)}"
        self._coordinator = coordinator
        self._follower = follower
        self._transform = transform
        # standalone servers track the feed live (pst-serve --follow
        # semantics); fleet-registered ones hold versions for the
        # controller's rolling update
        self.auto_advance = (coordinator is None if auto_advance is None
                             else bool(auto_advance))
        self._versions_kept = max(1, int(versions_kept))
        self._heartbeat_s = float(heartbeat_s)
        # Test seam, no knob: tests/test_fleet.py sets this attribute to
        # a synthetic per-round service time, so that streams on a tiny
        # CPU model stay in flight long enough for a rollout or a drain
        # to land mid-stream.  0 = off, the only value outside tests.
        self._round_delay_s = 0.0
        # Guards the version store, pin, command queue hand-off flags,
        # and stream bookkeeping shared between gRPC handler threads and
        # the decode loop (leaf — analysis/lock_order.py rank 74).
        self._lock = checked_lock("FleetDecodeServer._lock")
        self._versions: "OrderedDict[int, dict]" = OrderedDict()
        self._pinned = -1
        self._admit: "queue.Queue[_Stream]" = queue.Queue()
        self._live: dict[int, _Stream] = {}     # request_id -> stream
        self._commands: "queue.Queue[tuple]" = queue.Queue()
        self._wake = threading.Event()
        self._draining = False
        self._stopped = threading.Event()
        self._left = threading.Event()   # deregistered (drain complete)
        self._registered = False
        self.streams_served = 0
        self._grpc: grpc.Server | None = None
        self.port = 0
        self._decode_thread = threading.Thread(
            target=self._decode_loop, daemon=True,
            name=f"fleet-decode-{server_id}")
        self._member_thread: threading.Thread | None = None
        self._client: RpcClient | None = None

    # ----------------------------------------------------------- lifecycle
    def start(self) -> int:
        """Bind the gRPC service, start the decode loop, and (with a
        coordinator) register + heartbeat.  Returns the bound port."""
        from ..rpc.service import bind_service
        self._grpc = make_server()
        bind_service(self._grpc, fmsg.DECODE_SERVICE, fmsg.DECODE_METHODS,
                     self)
        self.port = self._grpc.add_insecure_port(self._bind)
        if self.port == 0:
            raise RuntimeError(f"could not bind {self._bind}")
        self._grpc.start()
        self.address = f"{self._bind.rsplit(':', 1)[0]}:{self.port}"
        self._decode_thread.start()
        if self._coordinator:
            self._client = RpcClient(self._coordinator,
                                     m.COORDINATOR_SERVICE,
                                     fmsg.FLEET_COORD_METHODS)
            self._member_thread = threading.Thread(
                target=self._membership_loop, daemon=True,
                name=f"fleet-member-{self.server_id}")
            self._member_thread.start()
        return self.port

    def stop(self, grace: float = 1.0) -> None:
        self._stopped.set()
        self._wake.set()
        if self._grpc is not None:
            self._grpc.stop(grace).wait()
        self._decode_thread.join(timeout=5.0)
        if self._member_thread is not None:
            self._member_thread.join(timeout=5.0)
        if self._client is not None:
            self._client.close()
            self._client = None
        if self._follower is not None:
            self._follower.stop()

    def drain(self) -> None:
        """Stop admitting new streams; in-flight ones finish, then the
        server leaves the fleet (wait_drained() unblocks).  The SIGTERM
        and Control(DRAIN) path."""
        self._draining = True
        self._wake.set()

    def wait_drained(self, timeout: float | None = None) -> bool:
        """Block until a drain completed (in-flight streams finished and
        the server left the fleet) — the scale-in stop barrier."""
        return self._left.wait(timeout)

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------- helpers
    def queue_depth(self) -> int:
        return self._admit.qsize()

    def free_slots(self) -> int:
        """Router-facing capacity: slots not yet claimed by an in-flight
        OR queued request (a queued admission claims its slot at the
        next round boundary — advertising it free would double-book)."""
        return max(0, self.server.slots - self.server.active
                   - self._admit.qsize())

    def weight_version(self) -> int:
        return int(getattr(self.server, "params_version", 0))

    def prefix_fingerprint(self) -> bytes:
        fn = getattr(self.server, "prefix_fingerprint", None)
        return fn() if fn is not None else b""

    def publish_version(self, store: dict, version: int) -> None:
        """Hold a weight version in the bounded store (newest-kept LRU);
        auto-advancing servers also queue the swap.  A version at or
        below the rollback pin is held but never auto-served."""
        with self._lock:
            self._versions[int(version)] = store
            while len(self._versions) > self._versions_kept:
                # LRU, but NEVER the rollback pin: a pinned fleet keeps
                # receiving newer publications, and evicting the pinned
                # version would strand later rollback retries and
                # scale-out joins at "version not held"
                for held in self._versions:
                    if held != self._pinned:
                        del self._versions[held]
                        break
                else:
                    break
            advance = (self.auto_advance and
                       (self._pinned < 0 or version <= self._pinned))
        if advance:
            self._commands.put(("swap", int(version), None))
            self._wake.set()

    # --------------------------------------------------------- gRPC: submit
    def SubmitStream(self, request: fmsg.DecodeRequest, context):
        """Admit one stream: queue it for the decode loop, then relay its
        chunks.  Rejections (draining, bad request) are an error chunk,
        never a transport failure — the router relays them verbatim."""
        if self._draining or self._stopped.is_set():
            yield fmsg.DecodeChunk(error="server draining", done=True)
            return
        tokens = [int(t) for t in request.tokens]
        if not tokens:
            yield fmsg.DecodeChunk(error="empty prompt", done=True)
            return
        stream = _Stream(
            tokens, int(request.max_new) or 64,
            None if request.temperature < 0 else float(request.temperature),
            [int(t) for t in request.stop])
        self._admit.put(stream)
        self._wake.set()
        try:
            while True:
                try:
                    chunk = stream.out.get(timeout=30.0)
                except queue.Empty:
                    # a wedged decode loop must not hold the client
                    # forever
                    yield fmsg.DecodeChunk(error="decode stalled",
                                           done=True)
                    return
                if chunk is None:
                    return
                yield chunk
        finally:
            # handler exit for ANY reason the stream did not finish —
            # client disconnect (gRPC closes the generator), the stall
            # timeout above — marks the stream abandoned so the decode
            # loop drops it instead of decoding into a dead queue
            stream.cancelled = True
            self._wake.set()

    # -------------------------------------------------------- gRPC: control
    def Control(self, request: fmsg.DecodeControlRequest,
                context) -> fmsg.DecodeControlResponse:
        action = int(request.action)
        ok, message = True, "ok"
        if action == fmsg.CTRL_SWAP or action == fmsg.CTRL_ROLLBACK:
            version = int(request.version)
            with self._lock:
                if version == -1 and self._versions:
                    version = next(reversed(self._versions))
                held = version in self._versions
                newer_than_pin = (self._pinned >= 0
                                  and version > self._pinned)
                if held and action == fmsg.CTRL_ROLLBACK:
                    # pin FIRST, under the same lock hold that validated
                    # the version: no auto-advance can interleave
                    self._pinned = version
                    newer_than_pin = False
            if not held:
                ok, message = False, f"version {version} not held"
            elif newer_than_pin:
                ok = False
                message = (f"version {version} newer than rollback pin "
                           f"{self._pinned} (Control UNPIN first)")
            else:
                ok, why = self._run_command(("swap", version))
                message = (f"serving version {version}" if ok
                           else f"swap to {version} failed: {why}")
                if ok and action == fmsg.CTRL_ROLLBACK:
                    flight.record("fleet.rollout", a=version,
                                  b=self.server_id, note="rollback-pin")
        elif action == fmsg.CTRL_UNPIN:
            with self._lock:
                self._pinned = -1
            message = "unpinned"
        elif action == fmsg.CTRL_DRAIN:
            self.drain()
            message = "draining"
        elif action != fmsg.CTRL_STATUS:
            ok, message = False, f"unknown control action {action}"
        with self._lock:
            held = list(self._versions)
            pinned = self._pinned
        state = (emsg.MEMBER_DRAINING if self._draining
                 else emsg.MEMBER_ACTIVE)
        return fmsg.DecodeControlResponse(
            success=ok, message=message, server_id=self.server_id,
            state=state, slots=self.server.slots,
            free_slots=self.free_slots(), queue_depth=self.queue_depth(),
            weight_version=self.weight_version(), pinned_version=pinned,
            versions_held=held, streams_served=self.streams_served,
            prefill_tokens=int(getattr(self.server,
                                       "_prefill_tokens", 0)),
            prompt_tokens=int(getattr(self.server, "_prompt_tokens", 0)))

    def _run_command(self, command: tuple,
                     timeout: float = 30.0) -> tuple[bool, str]:
        """Queue a command for the decode loop, wait for it to apply
        (swaps must land at a round boundary — the loop is the only
        thread that may touch the DecodeServer), and return its real
        OUTCOME: "processed" is not "succeeded", and a Control caller
        reporting success for a swap that raised would silently break
        the rollback guarantee."""
        box = _CommandBox()
        self._commands.put((command[0], command[1], box))
        self._wake.set()
        if not box.done.wait(timeout):
            return False, "decode loop busy"
        return box.ok, box.why

    # ----------------------------------------------------------- decode loop
    def _apply_commands(self) -> None:
        """Round-boundary command point: weight swaps requested by
        Control/auto-advance apply here.  The DecodeServer runs a round
        ahead of its step(): the round in flight was dispatched under
        the weights that leave, so it is landed and its tokens are
        delivered, stamped with the old version, BEFORE the swap and its
        acknowledgement — no chunk of an older or newer version follows
        a swap that reported success.  The outcome (applied / already
        current / failed and why) flows back to the Control waiter
        through its box."""
        while True:
            try:
                kind, version, box = self._commands.get_nowait()
            except queue.Empty:
                return
            if kind == "swap":
                with self._lock:
                    store = self._versions.get(version)
                if store is None:
                    # evicted between the Control-side held-check and
                    # here (bounded store under continued publication)
                    box_fail(box, f"version {version} no longer held")
                elif version == self.weight_version():
                    box_ok(box)  # already serving it
                else:
                    try:
                        fresh = (self._transform(store) if self._transform
                                 else store)
                        with _DISPATCH_LOCK:
                            landed = self.server.land()
                        self._deliver(landed)
                        self.server.swap_params(fresh, version=version)
                        flight.record("fleet.swap", a=version,
                                      b=self.server_id)
                        box_ok(box)
                    except Exception as exc:  # noqa: BLE001 — serving
                        # boundary: a bad publication keeps the last-good
                        # weights (PR 10 discipline), never kills decode
                        log.warning("swap to version %d failed (%s); "
                                    "keeping last-good", version, exc)
                        box_fail(box, str(exc))
            elif box is not None:
                box_fail(box, f"unknown command {kind!r}")

    def _poll_feed(self) -> None:
        if self._follower is None:
            return
        fresh = self._follower.poll()
        if fresh is not None:
            self.publish_version(*fresh)

    def _admit_locked_rounds(self) -> None:
        """Admit queued streams into free slots — between rounds, per
        round, no batch barrier.  A submit() rejection becomes that
        stream's error chunk."""
        while self.server.has_free_slot:
            try:
                stream = self._admit.get_nowait()
            except queue.Empty:
                break
            if stream.cancelled:
                continue  # client already gone: never admit it
            try:
                with _DISPATCH_LOCK:
                    rid = self.server.submit(
                        stream.tokens, stream.max_new,
                        temperature=stream.temperature, stop=stream.stop)
            except Exception as exc:  # noqa: BLE001 — per-request error
                # boundary, exactly cli/serve_main.py admit(): malformed
                # requests must never kill in-flight streams
                stream.out.put(fmsg.DecodeChunk(error=str(exc), done=True))
                stream.out.put(None)
                continue
            stream.request_id = rid
            version = self.weight_version()
            if rid in self.server.finished():
                # max_new=1 / instant EOS: completed inside submit()
                for token in self.server.result(rid):
                    stream.out.put(fmsg.DecodeChunk(
                        request_id=rid, token=int(token),
                        weight_version=version))
                stream.out.put(fmsg.DecodeChunk(request_id=rid, done=True,
                                                weight_version=version))
                stream.out.put(None)
                self.streams_served += 1
                continue
            # the prefill already produced the first token
            stream.out.put(fmsg.DecodeChunk(
                request_id=rid, token=int(self.server.peek(rid)[0]),
                weight_version=version))
            self._live[rid] = stream

    def _reap_cancelled(self) -> None:
        """Free the slots of in-flight streams whose client vanished
        (the handler's finally marked them) — an abandoned request must
        not decode its remaining budget into a dead queue."""
        for rid, stream in list(self._live.items()):
            if stream.cancelled:
                del self._live[rid]
                self.server.cancel(rid)

    def _decode_loop(self) -> None:
        while not self._stopped.is_set():
            self._poll_feed()
            self._apply_commands()
            self._reap_cancelled()
            self._admit_locked_rounds()
            if self.server.idle:
                if self._draining and self._admit.qsize() == 0:
                    self._finish_drain()
                    return
                self._wake.wait(timeout=self._heartbeat_s)
                self._wake.clear()
                continue
            with _DISPATCH_LOCK:
                emitted = self.server.step()
            if self._round_delay_s:
                time.sleep(self._round_delay_s)
            self._deliver(emitted)

    def _deliver(self, emitted) -> None:
        """Stream a round's tokens, stamped with the version that decoded
        them (the server's, which changes only with nothing in flight),
        and close the streams of the requests that finished with it."""
        version = self.weight_version()
        for rid, token in emitted:
            stream = self._live.get(rid)
            if stream is not None:
                stream.out.put(fmsg.DecodeChunk(
                    request_id=rid, token=int(token),
                    weight_version=version))
        for rid in set(self.server.finished()) & set(self._live):
            stream = self._live.pop(rid)
            self.server.result(rid)  # tokens already streamed
            stream.out.put(fmsg.DecodeChunk(request_id=rid, done=True,
                                            weight_version=version))
            stream.out.put(None)
            self.streams_served += 1

    def _finish_drain(self) -> None:
        """Drain completed: every in-flight stream finished.  Leave the
        fleet (the registry narrows NOW) and unblock wait_drained()."""
        if self._client is not None and self._registered:
            try:
                self._client.call("UpdateFleet", fmsg.FleetRequest(
                    server_id=self.server_id, action=fmsg.FLEET_LEAVE),
                    timeout=5.0)
            except grpc.RpcError:
                pass  # coordinator gone: nothing left to tell
            self._registered = False
        log.info("decode server %d drained (%d streams served)",
                 self.server_id, self.streams_served)
        self._left.set()

    # ------------------------------------------------------ membership loop
    def _membership_loop(self) -> None:
        try:
            self._client.call("UpdateFleet", fmsg.FleetRequest(
                server_id=self.server_id, action=fmsg.FLEET_REGISTER,
                address=self.address, slots=self.server.slots),
                timeout=5.0)
            self._registered = True
        except grpc.RpcError as exc:
            if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                log.info("coordinator does not speak UpdateFleet; "
                         "serving standalone")
                self.auto_advance = True  # no controller will ever swap us
                return
        while not self._stopped.is_set() and not self._left.is_set():
            try:
                resp = self._client.call("UpdateFleet", fmsg.FleetRequest(
                    server_id=self.server_id, action=fmsg.FLEET_HEARTBEAT,
                    free_slots=self.free_slots(),
                    queue_depth=self.queue_depth(),
                    weight_version=self.weight_version(),
                    active_streams=len(self._live),
                    # radix prefix-cache fingerprint (ISSUE 20): an
                    # immutable snapshot the decode thread swaps in, so
                    # this cross-thread read needs no lock; empty when
                    # the cache is off (router overlap term degrades
                    # to zero)
                    prefix_fp=self.prefix_fingerprint()), timeout=5.0)
                if not resp.success:
                    # fell out of the table (reap after a stall):
                    # re-register — the row is the router's only view
                    self._client.call("UpdateFleet", fmsg.FleetRequest(
                        server_id=self.server_id,
                        action=fmsg.FLEET_REGISTER, address=self.address,
                        slots=self.server.slots), timeout=5.0)
                    self._registered = True
                elif (int(resp.self_state) == emsg.MEMBER_DRAINING
                        and not self._draining):
                    log.warning("decode server %d: coordinator drain",
                                self.server_id)
                    self._draining = True
                    self._wake.set()
            except grpc.RpcError:
                pass  # transient: keep serving, next beat retries
            if self._stopped.wait(self._heartbeat_s):
                return
