"""Shared-memory same-host transport for the fused data plane (ISSUE 6).

When a worker and its PS run on the same machine, the fused
``PushPullStream`` round still crosses the loopback TCP stack: every
chunk is HTTP/2-framed, copied into the kernel, copied back out, and
ACKed.  This module replaces that leg with two single-producer/
single-consumer byte rings in ``multiprocessing.shared_memory`` segments
— the SAME wire bytes (encoded ``GradientUpdate`` request frames one
way, ``PushPullResponse`` frames the other), so the codec, the message
schemas, and every aggregation semantic are untouched; only the
transport under them changes.

Negotiation (``NegotiateShm``) is an extension RPC on the parameter-
server service.  Its messages live HERE, not in ``rpc/messages.py``:
the wire-compat manifest pins the reference contract and must not
change — a reference peer simply never calls this method and answers
UNIMPLEMENTED, which the client treats exactly like the PR-2 stream
fallbacks: a PERMANENT per-connection downgrade to TCP.  The handshake
only succeeds when both ends report the same ``host_id`` (hostname +
kernel boot id — two containers that share a boot id but not /dev/shm
fail at segment attach and downgrade the same way) and the server can
actually create segments (/dev/shm unavailable => refused => TCP).

Ring protocol ("small doorbell"): each direction is a byte ring with two
u64 cursors in the segment header — ``tail`` (bytes ever written, owned
by the producer) and ``head`` (bytes ever read, owned by the consumer) —
plus a u32 ``closed`` latch either side may set.  A frame is a u32
length prefix followed by payload bytes, wrapped modulo the ring
capacity; frames larger than the ring stream through it in SPANS:
whatever is free (writing) or available (reading), up to a quarter of
the ring, so the ring is a pipeline four spans deep and the consumer's
copy-out overlaps the producer's copy-in.  A span moves in ONE native
call (``native.copy_fn``: no GIL, the wrap handled inside, a span of
2 MB or more cut over a few threads the library keeps and written past
the cache), and the cursor is stored and the doorbell rung once a span;
an end that finds less than 2 MB with more of the payload to come waits
for that much rather than move a sliver.  The DOORBELL is a 1-byte nudge
on a per-connection
AF_UNIX socket (abstract namespace — no filesystem litter): after
advancing a cursor the mover rings it, and a waiter parks in
``select`` — a real kernel wakeup, which matters twice: polling sleeps
have ~1 ms granularity on HZ-bound kernels, and in-process (tests,
colocated bench) a spinning waiter convoys the peer's copies under the
GIL.  Cursor updates are single aligned 8-byte stores — atomic on every
platform CPython runs on — and each cursor has exactly one writer; the
socket carries no data, only wakeups, so a lost/skipped doorbell is a
latency blip, never a correctness problem (waits recheck the cursors).

Produce side: a message bound for a ring is encoded INTO the ring
(``ShmRing.write_message``): the ring writes the frame's length from the
message's ``encoded_size()`` and hands ``encode_into`` a writer whose
destination is the ring's own span loop, so a tensor's bytes go from
their source array to the ring in one copy and no frame-sized buffer
exists on this side (at the sizes a store is chunked into, a new buffer
per frame is new address space, and its page faults, not the copy, set
the encoder's pace).  A payload that needs a real pack (bf16, int8,
top-k) is packed through a small scratch the ring end owns.  gRPC keeps
``Message.encode()`` and its new ``bytes``: its serializer accepts
nothing else.

Consume side: a frame leaves a ring ONCE, into a receive buffer the ring
end owns and has already touched (``_FramePool``: two per ring end, grown
to the largest frame seen), and the caller gets a read-only view of it.
Both ends consume a frame before they ask for the next: the server's
handler folds chunk k before frame k+1 is read, and the client decodes
and converts each response frame as it arrives, inside the connection's
round lock (``ShmClientConnection.round_trip``), so its decode runs
under the server's encode of the next frame and it never holds the whole
encoded response.  A buffer is reused only when the interpreter says no
view of it is alive; whoever keeps a view keeps the buffer.

Env knobs: ``PSDT_SHM`` (default on; 0 disables both ends),
``PSDT_SHM_RING_BYTES`` (per-direction ring capacity, default 32 MB —
frames larger than the ring stream through it).
Observability: ``rpc.shm.bytes`` counts payload bytes moved through
rings by this process, ``rpc.shm.wide_bytes`` the part of them that moved
in spans cut over more than one thread (all but prefixes, headers, end
markers and frames under 2 MB, where the machine has the cores);
``rpc.shm.frames`` the data frames read out of
one and ``rpc.shm.frame_allocs`` the receive buffers allocated or grown
for them (0 once every frame size has been seen); ``rpc.shm.fallback``
counts downgrades to TCP (refused negotiation, attach failure, or a
mid-flight transport error).
"""

from __future__ import annotations

import contextlib
import ctypes
import logging
import os
import socket
import struct
import threading
import time
import uuid
from typing import Callable, Iterator, TypeVar

import numpy as np

from .. import native
from ..analysis.lock_order import checked_lock
from ..core.stripes import usable_cores
from ..obs import flight
from ..obs import stats as obs_stats
from ..obs import trace as obs_trace
from ..utils.buffers import exported
from .wire import Field, Message

log = logging.getLogger("pst.shm")

T = TypeVar("T")

ENV_FLAG = "PSDT_SHM"
ENV_RING_BYTES = "PSDT_SHM_RING_BYTES"
# Frames larger than the ring stream through it in spans, so the ring
# only needs to be big enough to decouple the two sides — and every ring
# page is touched at negotiation (see _pretouch), so smaller also means
# a shorter warm-up.
DEFAULT_RING_BYTES = 32 << 20

# Segment header layout (64-byte cache line):
#   0  u64 tail   — bytes ever written (producer-owned cursor)
#   8  u64 head   — bytes ever read   (consumer-owned cursor)
#   16 u32 closed — either side latches 1 to tear the connection down
_HEADER = 64
_OFF_TAIL = 0
_OFF_HEAD = 8
_OFF_CLOSED = 16

_obs_bytes = obs_stats.counter("rpc.shm.bytes")
_obs_wide = obs_stats.counter("rpc.shm.wide_bytes")
_obs_frames = obs_stats.counter("rpc.shm.frames")
_obs_frame_allocs = obs_stats.counter("rpc.shm.frame_allocs")
_obs_fallback = obs_stats.counter("rpc.shm.fallback")


# How a ring end cuts a payload (ShmRing._transfer, _move).  A span of _WIDE
# bytes or more is cut over threads, none of which gets less than _PIECE,
# and written with stores that go past the cache (its reader is another
# core: a line left modified in this one's cache comes to it core to
# core, slower than from memory); a shorter one (a length prefix, a
# header, an end marker, a small tensor) is one memcpy on the caller's
# thread.  No more threads than still pay on a host's memory
# (scripts/ring_pace.py, PERF.md section 6) and no more than a quarter of
# the cores: both ends of a ring, the fold and the D2H run beside each
# other.
_PIECE = 1 << 20
_WIDE = 2 << 20
_MAX_WIDTH = max(1, min(3, usable_cores() // 4))
_STREAM = 2  # native.copy_fn's flag: stores that go past the cache


def enabled() -> bool:
    return os.environ.get(ENV_FLAG, "1") not in ("0", "false", "off")


def ring_bytes() -> int:
    return int(os.environ.get(ENV_RING_BYTES, str(DEFAULT_RING_BYTES)))


def host_id() -> str:
    """Same-host identity: hostname + kernel boot id.  The boot id guards
    against same-named hosts across a fleet; /dev/shm isolation between
    containers sharing a boot id is caught later, at segment attach."""
    boot = ""
    try:
        with open("/proc/sys/kernel/random/boot_id",
                  encoding="ascii") as fh:
            boot = fh.read().strip()
    except OSError:
        boot = "no-boot-id"
    return f"{socket.gethostname()}/{boot}"


class ShmTransportError(RuntimeError):
    """Any shared-memory transport failure.  The catcher downgrades the
    connection to TCP permanently (rpc/data_plane.py PSClient)."""


# --------------------------------------------------------------------------
# Negotiation messages — deliberately NOT in rpc/messages.py: the analyzer's
# wire manifest pins the reference contract, and this extension must leave
# it untouched.  A reference server answers the method with UNIMPLEMENTED.
# --------------------------------------------------------------------------

class ShmNegotiateRequest(Message):
    FIELDS = (
        Field(1, "host_id", "string"),
        Field(2, "worker_id", "int32"),
        Field(3, "ring_bytes", "int64"),
    )


class ShmNegotiateResponse(Message):
    """``accepted`` False carries the refusal reason in ``message`` (host
    mismatch, shm unavailable, disabled) — the client downgrades to TCP
    for the connection's lifetime either way.  ``doorbell`` is the
    abstract AF_UNIX address of the connection's doorbell socket."""
    FIELDS = (
        Field(1, "accepted", "bool"),
        Field(2, "message", "string"),
        Field(3, "c2s_name", "string"),
        Field(4, "s2c_name", "string"),
        Field(5, "ring_bytes", "int64"),
        Field(6, "host_id", "string"),
        Field(7, "doorbell", "string"),
    )


# Extension method table, bound alongside the reference + stream methods on
# the same gRPC service (server/ps_service.py).
SHM_METHODS = {
    "NegotiateShm": (ShmNegotiateRequest, ShmNegotiateResponse),
}


# Serializes the attach-side resource-tracker suppression below (the
# monkeypatch window must not race a concurrent attach).
_attach_lock = threading.Lock()


class _Doorbell:
    """1-byte wakeups over the connection's AF_UNIX socket.  Purely an
    optimization channel: the authoritative state is the ring cursors,
    so sends are fire-and-forget (a full socket buffer means the peer
    already has wakeups pending) and a waiter treats any readable byte —
    or a timeout — as "recheck the cursors"."""

    __slots__ = ("_sock",)

    def __init__(self, sock: socket.socket):
        sock.setblocking(False)
        self._sock = sock

    def ring(self) -> None:
        try:
            self._sock.send(b"\x01")
        except (BlockingIOError, OSError):  # buffer full / torn down
            pass

    def wait(self, timeout: float) -> None:
        import select
        try:
            readable, _, _ = select.select([self._sock], [], [], timeout)
            if readable:
                data = self._sock.recv(4096)
                if not data:
                    raise ShmTransportError("doorbell socket closed by peer")
        except BlockingIOError:  # drained by a concurrent recheck
            pass
        except OSError as exc:
            raise ShmTransportError(f"doorbell socket failed: {exc}") \
                from exc

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:  # already closed
            pass


def _doorbell_listener() -> tuple[socket.socket, str]:
    """Listening doorbell socket + its wire-encodable address ("@name"
    for the Linux abstract namespace, a filesystem path elsewhere)."""
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    name = f"psdt-db-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    try:
        sock.bind("\0" + name)
        addr = "@" + name
    except OSError:
        import tempfile
        path = os.path.join(tempfile.gettempdir(), name)
        sock.bind(path)
        addr = path
    sock.listen(1)
    return sock, addr


def _doorbell_connect(addr: str, timeout: float = 10.0) -> socket.socket:
    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.settimeout(timeout)
    sock.connect("\0" + addr[1:] if addr.startswith("@") else addr)
    return sock


def _address(buf: bytearray) -> int:
    # the export dies with the temporary
    return ctypes.addressof(ctypes.c_char.from_buffer(buf))


class _FramePool:
    """The receive buffers of one ring end.  A frame that leaves the ring
    lands in a buffer this pool owns and has already touched, not in
    fresh pages: at the sizes a parameter store is chunked into (far
    above malloc's mmap threshold) a per-frame buffer is new address
    space every time, and first-touch page faults, not the copy, set a
    ring read's pace.

    Two slots, because the consumer of frame k still holds its views
    while frame k+1 is read (the loop variable of whoever iterates the
    frames).  A slot is reused only when ``utils.buffers.exported`` says
    no view of it is left; a consumer that keeps one keeps that buffer,
    and the ring replaces the slot with a fresh buffer of the frame's own
    size (``rpc.shm.frame_allocs``).  Free slots grow to the largest frame
    seen (and a little over), at every take and at the end of a frame
    group, so that after one whole exchange nothing is allocated again
    whichever slot a frame falls on."""

    __slots__ = ("_slots", "_largest")

    def __init__(self):
        self._slots: list[bytearray] = []
        self._largest = 1

    @staticmethod
    def _fresh(size: int) -> bytearray:
        _obs_frame_allocs.add()
        return bytearray(size)  # zero-filled: every page touched here

    def settle(self) -> bytearray | None:
        """Grow every slot no consumer holds to the largest frame seen;
        returns one of them, or None when all are held."""
        free = None
        for i, buf in enumerate(self._slots):
            if exported(buf):
                continue
            if len(buf) < self._largest:
                buf = self._slots[i] = self._fresh(self._largest)
            if free is None:
                free = buf
        return free

    def take(self, n: int) -> bytearray:
        """A buffer of at least ``n`` >= 1 bytes that nothing refers to."""
        if n > self._largest:
            # with headroom: the same tensors arrive under headers that
            # differ by a few bytes from round to round (the iteration's
            # varint, a trace context), which must not cost two buffers
            self._largest = n + (n >> 6) + 4096
        buf = self.settle()
        if buf is None:
            buf = self._fresh(n)
            del self._slots[:-1]  # the longest-held slot is its holder's
            self._slots.append(buf)
        return buf


class ShmRing:
    """One direction of a connection: SPSC byte ring over a shared-memory
    segment.  Exactly one producer process/thread calls the ``write*``
    methods and one consumer the ``read*`` methods; the cursors make the
    hand-off safe without any cross-process lock.  ``doorbell`` (shared
    by both of a connection's rings at each endpoint) turns waits into
    kernel sleeps; without one — unit tests — waits degrade to timed
    polling.  The consumer's ``read_frame`` hands out views of pooled
    receive buffers (``_FramePool``), never a per-frame allocation."""

    def __init__(self, shm, capacity: int,
                 doorbell: _Doorbell | None = None):
        self._shm = shm
        self.capacity = capacity
        self._buf = shm.buf
        self.doorbell = doorbell
        # Spans move through the native GIL-FREE call when the lib is
        # available (native.copy_fn): a colocated producer/consumer
        # pair then overlaps its copies, where memoryview assignment
        # (the no-compiler fallback) convoys them under the GIL one
        # switch-interval at a time.  The raw base address stays valid
        # for the mmap's lifetime; teardown orders close() (latch, makes
        # waiters raise) before the unmap, and the server side refuses
        # to unmap under a still-running connection thread.
        self._copy = native.copy_fn()
        if self._copy is not None:
            carr = (ctypes.c_ubyte * len(shm.buf)).from_buffer(shm.buf)
            self._base = ctypes.addressof(carr) + _HEADER
            del carr  # export released; the address outlives it
        else:
            self._base = 0
        # the blocked-on-the-peer leg of the frame being moved (obs/trace
        # ``timed.carve``); one thread drives a ring's end, so a plain
        # attribute does
        self._blocked = None
        # consume side: where frames land (see _FramePool), and the four
        # bytes of a length prefix
        self._pool = _FramePool()
        self._prefix = bytearray(4)
        # produce side: where a payload that needs a real pack is packed
        # (see _RingWriter)
        self._scratch: bytearray | None = None

    # ------------------------------------------------------------- cursors
    def _tail(self) -> int:
        return struct.unpack_from("<Q", self._buf, _OFF_TAIL)[0]

    def _head(self) -> int:
        return struct.unpack_from("<Q", self._buf, _OFF_HEAD)[0]

    # A cursor is stored as ONE 8-byte move (a slice assignment), never
    # with ``struct.pack_into``, which zero-fills its destination before it
    # packs: a peer in another process could read that zero (in-process
    # the GIL hides it) and take it for a cursor behind its own.
    def _set_tail(self, v: int) -> None:
        self._buf[_OFF_TAIL:_OFF_TAIL + 8] = struct.pack("<Q", v)

    def _set_head(self, v: int) -> None:
        self._buf[_OFF_HEAD:_OFF_HEAD + 8] = struct.pack("<Q", v)

    @property
    def closed(self) -> bool:
        try:
            return struct.unpack_from("<I", self._buf, _OFF_CLOSED)[0] != 0
        except (ValueError, TypeError):  # memoryview released (teardown)
            return True

    def close(self) -> None:
        try:
            struct.pack_into("<I", self._buf, _OFF_CLOSED, 1)
        except (ValueError, TypeError):  # segment already unmapped: the
            pass  # release latch beat this closer — nothing left to latch

    def invalidate(self) -> None:
        """Drop the native raw-address fast path BEFORE the segment
        unmaps (ISSUE 8 shm-flake fix): a copy racing the unmap then
        takes the memoryview path, whose released-buffer ``ValueError``
        is caught and surfaced as :class:`ShmTransportError` — a clean
        downgrade instead of a SIGSEGV at a stale ``_base``."""
        self._base = 0  # zeroed FIRST: a racing span re-reads (base,
        self._copy = None  # copy) and falls back once either is gone

    # ------------------------------------------------------------ doorbell
    def _wait(self, ready: Callable[[], int], deadline: float,
              what: str) -> int:
        """Park until ``ready()`` returns non-zero (bytes available /
        free).  One immediate probe, then escalating micro-sleeps — the
        "doorbell" is the peer's cursor store becoming visible.  NO hot
        spinning: under the GIL a spinning waiter convoys the peer's copy
        loop (each hand-off costs a full switch interval), so yielding
        immediately is strictly faster in-process and costs at most one
        ~20 us sleep cross-process."""
        n = ready()
        if n:
            return n
        with self._blocked or contextlib.nullcontext():
            return self._park(ready, deadline, what)

    def _park(self, ready: Callable[[], int], deadline: float,
              what: str) -> int:
        while True:
            n = ready()
            if n:
                return n
            if self.closed:
                raise ShmTransportError(f"shm ring closed while {what}")
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise ShmTransportError(f"shm ring timeout while {what}")
            if self.doorbell is not None:
                # kernel sleep until the peer rings (capped so a closed
                # latch set without a ring is still noticed promptly)
                self.doorbell.wait(min(remaining, 0.05))
            else:
                time.sleep(min(remaining, 200e-6))

    # ---------------------------------------------------------------- spans
    def _transfer(self, into_ring: bool, mem, addr: int, total: int,
                  deadline: float) -> None:
        """Move ``total`` bytes between ``mem`` (whose address is ``addr``;
        0 without the native library) and the ring, span by span: whatever
        is free (writing) or available (reading), up to a quarter of the
        ring, so that the peer works on one span while this end moves the
        next.  The cursor is stored, and the doorbell rung, once a span,
        after its bytes are in.  A payload's tail of less than ``_WIDE``
        rides with its last span, and an end that finds less than
        ``_WIDE`` (or all that is left) waits for that much (under
        ``rpc/shm/wait``, like every wait) rather than move a sliver.
        Neither end ever waits for more than half the ring, so the two
        cannot wait for each other."""
        cap = self.capacity
        if into_ring:
            mine, publish, what = self._tail(), self._set_tail, "writing"
        else:
            mine, publish, what = self._head(), self._set_head, "reading"
        quarter = max(1, cap // 4)
        rest = quarter + min(_WIDE, quarter)
        moved = 0
        while moved < total:
            left = total - moved
            want = left if left <= rest else quarter
            least = want if want == left else min(want, _WIDE)

            def ready() -> int:
                found = (cap - (mine - self._head()) if into_ring
                         else self._tail() - mine)
                return found if found >= least else 0

            n = min(want, self._wait(ready, deadline, what))
            self._move(into_ring, mine % cap, mem, addr, moved, n)
            mine += n
            moved += n
            publish(mine)
            if self.doorbell is not None:
                self.doorbell.ring()

    def _move(self, into_ring: bool, pos: int, mem, addr: int, off: int,
              n: int) -> None:
        """One span between the ring at ``pos`` (it may wrap) and the
        caller's memory: ``mem[off:off + n]``, whose address is ``addr``
        (0 without the native library)."""
        # re-read the native fast path per span: invalidate() may have
        # dropped it mid-frame (teardown racing this end), and the
        # memoryview fallback fails CLEANLY on a released segment
        base, copy = self._base, self._copy
        if addr and copy is not None and base:
            wide = n >= _WIDE
            width = min(_MAX_WIDTH, n // _PIECE) if wide else 1
            flags = into_ring | (_STREAM if wide else 0)
            copy(base, self.capacity, pos, addr + off, n, flags, width)
            if width > 1:
                _obs_wide.add(n)
            return
        first = min(n, self.capacity - pos)
        for at, a, b in ((_HEADER + pos, off, off + first),
                         (_HEADER, off + first, off + n)):
            if into_ring:
                self._buf[at:at + b - a] = mem[a:b]
            else:
                mem[a:b] = self._buf[at:at + b - a]

    # ------------------------------------------------------------- produce
    def _write_bytes(self, data, deadline: float) -> None:
        view = memoryview(data)
        # the local ndarray keeps the source buffer alive for the call
        src = np.frombuffer(view, np.uint8) if self._copy is not None \
            else None
        self._transfer(True, view, src.ctypes.data if src is not None else 0,
                       view.nbytes, deadline)

    @contextlib.contextmanager
    def _frame(self, **args):
        """One frame through the ring as two legs: ``rpc/shm/copy`` (moving
        the bytes) and, carved out of it, ``rpc/shm/wait`` (every wait of
        the frame summed: ring full when writing, empty when reading)."""
        with obs_trace.timed("rpc/shm/copy", **args) as frame:
            self._blocked = frame.carve("rpc/shm/wait")
            try:
                yield frame
            finally:
                self._blocked = None

    # End-of-stream sentinel in the length slot.  Deliberately NOT length
    # zero: a fully-default GradientUpdate legally encodes to b"" under
    # proto3 default elision (the sharded-topology empty barrier
    # contribution at worker 0 / iteration 0), so zero-length DATA frames
    # must round-trip.
    _END = 0xFFFFFFFF

    def _put(self, data, deadline: float) -> None:
        try:
            self._write_bytes(data, deadline)
        except ValueError as exc:  # memoryview released under us
            raise ShmTransportError(
                f"shm segment released: {exc}") from exc

    def write_message(self, message, deadline: float,
                      encode_leg: str) -> None:
        """One length-prefixed frame whose payload is ``message``
        (anything with ``encoded_size()`` and ``encode_into(writer)``;
        a zero-length payload is legal), encoded straight into the ring:
        the bytes of ``message.encode()`` without the frame-sized
        ``bytes`` in between.  Frames larger than the ring stream through
        it — the consumer drains while the producer refills.

        What is left of encoding is the span ``encode_leg``: the sizes,
        before the frame's ``rpc/shm/copy`` opens, and a real pack into
        the scratch, for which the copy leg is closed and opened again
        (a frame that packs nothing is one ``rpc/shm/copy``).

        If ``encode_into`` raises, or writes another count than
        ``encoded_size()`` promised, the frame is torn (its length went
        out first): the caller latches the rings closed."""
        with obs_trace.span(encode_leg):
            size = message.encoded_size()
        with contextlib.ExitStack() as moving:
            moving.enter_context(self._frame(bytes=size))
            writer = _RingWriter(self, deadline, moving, encode_leg)
            writer.write(struct.pack("<I", size))
            message.encode_into(writer)
        if writer.pos != 4 + size:
            raise RuntimeError(
                f"{type(message).__name__} encoded {writer.pos - 4} "
                f"bytes into the ring, encoded_size() said {size}")
        _obs_bytes.add(4 + size)

    def write_end(self, deadline: float) -> None:
        """End-of-stream marker for one request/response group."""
        self._put(struct.pack("<I", self._END), deadline)
        _obs_bytes.add(4)

    # ------------------------------------------------------------- consume
    def _read_into(self, out: bytearray, n: int, deadline: float) -> None:
        """Fill ``out[:n]`` from the ring.  ``out`` is this ring's own (the
        prefix scratch or a pool buffer nothing else refers to), so its
        address holds for the call."""
        self._transfer(False, out,
                       _address(out) if self._copy is not None else 0, n,
                       deadline)

    def read_frame(self, deadline: float) -> memoryview | None:
        """The next frame's payload, or None at an end-of-stream marker.

        The payload is a READ-ONLY view of a buffer this ring end owns and
        reuses: it was copied out of the ring once, into pages already
        touched.  Consume it (decode, fold, convert) and let go of it;
        whoever keeps a view, of any depth, keeps that buffer, and the
        ring takes another (see :class:`_FramePool`).  Read-only, so that
        ``Tensor.to_array`` copies out of it exactly once, a fold that
        borrows it can only read it, and in-place aggregation can never
        write into a buffer about to be refilled."""
        with self._frame() as frame:
            try:
                self._read_into(self._prefix, 4, deadline)
                (length,) = struct.unpack("<I", self._prefix)
                if length == self._END:
                    self._pool.settle()
                    _obs_bytes.add(4)
                    return None
                if length:
                    out = self._pool.take(length)
                    self._read_into(out, length, deadline)
                    payload = memoryview(out).toreadonly()[:length]
                else:
                    payload = memoryview(b"")
            except ValueError as exc:  # memoryview released under us
                raise ShmTransportError(
                    f"shm segment released: {exc}") from exc
            frame.args["bytes"] = length
        _obs_bytes.add(4 + length)
        _obs_frames.add()
        return payload


class _RingWriter:
    """What a message's ``encode_into`` sees when its destination is a
    ring: ``wire._Writer``'s two methods over ``ShmRing._write_bytes``
    (spans, wrap, doorbell, the copy and wait legs as they are).

    A payload whose wire form already lies in memory is written from
    there (``ArrayPayload.wire_view``); one that needs a real pack goes
    through the ring end's scratch piece by piece, each piece packed
    under the encode leg with the frame's copy leg (``moving``) closed."""

    __slots__ = ("_ring", "_deadline", "_moving", "_encode_leg", "pos")

    # a cast's pieces keep the consumer's copy-out running beside the
    # next piece's pack, and each is a span wide enough to cut
    _SCRATCH = 4 << 20

    def __init__(self, ring: ShmRing, deadline: float,
                 moving: contextlib.ExitStack, encode_leg: str):
        self._ring = ring
        self._deadline = deadline
        self._moving = moving
        self._encode_leg = encode_leg
        self.pos = 0

    def write(self, data) -> None:
        self.pos += len(data)
        self._ring._put(data, self._deadline)

    def write_array(self, payload) -> None:
        view = payload.wire_view()
        if view is not None:
            self.write(view)
            return
        ring = self._ring
        if ring._scratch is None:
            ring._scratch = bytearray(self._SCRATCH)  # zeroed: touched
        pieces = payload.pack_pieces(ring._scratch)
        end = self.pos + payload.nbytes
        while self.pos < end:
            self._moving.close()
            with obs_trace.span(self._encode_leg):
                piece = next(pieces)
            self._moving.enter_context(ring._frame())
            # the piece moves on before the next one overwrites the scratch
            self.write(piece)


def _pretouch(shm) -> None:
    """Fault every page of the mapping in now (one store per 4 KB page):
    first-touch page faults during the first ring lap otherwise dominate
    the first few fused rounds."""
    view = np.frombuffer(shm.buf, np.uint8)
    view[_HEADER::4096] |= 0  # read-modify-write: faults without clobbering


def _create_segment(name: str, size: int):
    from multiprocessing import shared_memory
    with _attach_lock:
        # under the same lock as the attach-side tracker suppression: a
        # concurrent attach must not swallow this create's registration
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
    # zero the header so cursors/closed start clean (POSIX shm is
    # zero-filled, but be explicit — the protocol depends on it)
    shm.buf[:_HEADER] = bytes(_HEADER)
    _pretouch(shm)
    return shm


def _attach_segment(name: str):
    """Attach to a server-owned segment WITHOUT registering it with this
    process's resource tracker: the server is the owner and unlinks it; a
    client-side registration would double-unlink at exit (and, in the
    same-process test topology, fight the server's own registration).
    Python 3.13 grew ``track=False`` for exactly this; earlier versions
    need the documented workaround of suppressing ``register`` around the
    attach (bpo-38119)."""
    from multiprocessing import shared_memory
    try:
        shm = shared_memory.SharedMemory(name=name, create=False,
                                         track=False)
        _pretouch(shm)
        return shm
    except TypeError:  # Python < 3.13: no track kwarg
        pass
    from multiprocessing import resource_tracker
    with _attach_lock:
        orig = resource_tracker.register
        resource_tracker.register = lambda *a, **kw: None
        try:
            shm = shared_memory.SharedMemory(name=name, create=False)
        finally:
            resource_tracker.register = orig
    _pretouch(shm)
    return shm


class ShmClientConnection:
    """Worker-side endpoint of one negotiated connection: writes request
    frames to the c2s ring, reads response frames from the s2c ring.
    ``_lock`` serializes whole fused rounds — the rings are SPSC, so two
    concurrent pushes on one connection would interleave frames."""

    def __init__(self, c2s_name: str, s2c_name: str, capacity: int,
                 doorbell_addr: str = ""):
        self._c2s_shm = _attach_segment(c2s_name)
        self._s2c_shm = _attach_segment(s2c_name)
        self._doorbell = (_Doorbell(_doorbell_connect(doorbell_addr))
                          if doorbell_addr else None)
        self.c2s = ShmRing(self._c2s_shm, capacity, self._doorbell)
        self.s2c = ShmRing(self._s2c_shm, capacity, self._doorbell)
        # Serializes one fused round end to end; the ring waits under it
        # are the lock's purpose (BLOCKING_ALLOWED, analysis/lock_order.py)
        self._lock = checked_lock("ShmClientConnection._lock")

    def round_trip(self, messages: Iterator[Message],
                   timeout: float | None,
                   consume: Callable[[Iterator[memoryview]], T]) -> T:
        """One request/response exchange: encode the request messages
        into the ring, a frame each (``ShmRing.write_message``; what is
        left of encoding is the ``rpc/client/encode`` leg), then hand
        ``consume`` an iterator over the response frames, each read off
        the ring when the consumer asks for it, so the decode of frame k
        runs while the server encodes and writes frame k+1 and this end
        never holds more of the response than the receive pool.  Returns
        what ``consume`` returns.

        The round's three phases are spans of the caller's thread, one
        after another, which hold the legs in time
        (``obs_trace.phases``: a cut is one clock read, because the one
        between the first two response frames stands where the thread
        has no time to lose): ``rpc/round/send`` (from before the first
        message is asked of its source until the end marker is in the
        ring), ``rpc/round/turn`` (until the first response frame has
        been read: the server's drain of the push and its close) and
        ``rpc/round/receive`` (until the server's end marker has been
        read, the consumer's landing of every frame included).

        Everything happens INSIDE the round lock, and two things hold by
        construction.  The connection is never left half-read: frames the
        consumer did not take are drained to the server's end marker, and
        if it (or the message source, or a message's encoder halfway
        through its frame) raises, both rings are latched closed.
        No lazily-consumed iterator escapes the lock: the one ``consume``
        was given is exhausted or closed before this returns.  Each frame
        is a read-only view of a pool buffer (``ShmRing.read_frame``):
        take what is needed out of it before asking for the next."""
        deadline = time.monotonic() + (timeout if timeout else 3600.0)

        def response(phase) -> Iterator[memoryview]:
            frame = self.s2c.read_frame(deadline)
            # the round turns where the first response frame has left
            # the ring: until then the server drains the push and closes
            phase.next("rpc/round/receive")
            while frame is not None:
                yield frame
                frame = self.s2c.read_frame(deadline)

        with self._lock:
            if self.c2s.closed or self.s2c.closed:
                # latched by an earlier round's failure (below): frames of
                # that round may still sit in the ring, and a read that
                # finds bytes never looks at the latch
                raise ShmTransportError("shm connection latched closed")
            try:
                with obs_trace.phases("rpc/round/send") as phase:
                    for message in messages:
                        self.c2s.write_message(message, deadline,
                                               "rpc/client/encode")
                    self.c2s.write_end(deadline)
                    phase.next("rpc/round/turn")
                    answer = response(phase)
                    try:
                        result = consume(answer)
                        for _ in answer:  # what the consumer left unread
                            pass
                    finally:
                        answer.close()
            except ShmTransportError:
                raise
            except BaseException:
                # the MESSAGE SOURCE (lazy D2H fetch), an ENCODER (its
                # frame's length is out, the frame is torn) or the
                # CONSUMER (decode, the worker's converter) raised
                # mid-round: the stream is desynced — the server is
                # parked mid-round and would fold the NEXT round's frames
                # into this one, or is still writing a response nobody
                # reads.  Latch the rings closed so the server thread
                # exits (and is reaped) and the next attempt on this
                # connection downgrades to TCP; the original error still
                # propagates like the gRPC path's.
                for ring in (self.c2s, self.s2c):
                    try:
                        ring.close()
                    except (ValueError, OSError):
                        pass
                raise
        return result

    def close(self) -> None:
        # taking the round lock first means an in-flight fused round
        # finishes (or times out) before the segments unmap — raw-address
        # copies must never race the unmap
        with self._lock:
            for ring in (self.c2s, self.s2c):
                try:
                    ring.close()
                except (ValueError, OSError):  # segment already torn down
                    pass
            if self._doorbell is not None:
                self._doorbell.close()
            for shm in (self._c2s_shm, self._s2c_shm):
                try:
                    shm.close()
                except OSError:  # noqa: BLE001 — double-close at teardown
                    pass


class _ServerConnection:
    """PS-side endpoint: a dedicated thread drains request frames, feeds
    them through the fused handler, and streams the response frames
    back.  One thread per same-host worker — they park on the barrier
    condition variable exactly like gRPC handler threads do."""

    def __init__(self, index: int, handler: Callable, capacity: int,
                 on_exit: Callable[["_ServerConnection"], None]
                 | None = None):
        token = uuid.uuid4().hex[:8]
        self.index = index
        self._on_exit = on_exit
        # Exactly-once segment release (ISSUE 8: the PR-7 backup-crash
        # flake was a DOUBLE segment reap — the serve thread's exit reap
        # racing the shutdown path's unlink, second unmap pulling the
        # mapping out from under a native ring copy).  Every unmap now
        # routes through release_segments(), which latches.
        self._release_lock = checked_lock("_ServerConnection._release_lock")
        self._released = False
        self.c2s_name = f"psdt-{os.getpid()}-{index}-{token}-c2s"
        self.s2c_name = f"psdt-{os.getpid()}-{index}-{token}-s2c"
        self._listener, self.doorbell_addr = _doorbell_listener()
        self._c2s_shm = _create_segment(self.c2s_name,
                                        _HEADER + capacity)
        self._s2c_shm = _create_segment(self.s2c_name,
                                        _HEADER + capacity)
        self.c2s = ShmRing(self._c2s_shm, capacity)
        self.s2c = ShmRing(self._s2c_shm, capacity)
        self._doorbell: _Doorbell | None = None
        self._handler = handler
        self._thread = threading.Thread(
            target=self._serve_loop, daemon=True,
            name=f"shm-conn-{index}")
        self._thread.start()

    def _request_frames(self) -> Iterator[memoryview]:
        """Frames of ONE request (until the client's end marker); empty
        frames are legal data (an all-default GradientUpdate)."""
        while True:
            frame = self.c2s.read_frame(time.monotonic() + 3600.0)
            if frame is None:
                return
            yield frame

    def _serve_loop(self) -> None:
        from . import messages as m
        try:
            self._listener.settimeout(60.0)
            sock, _ = self._listener.accept()
        except OSError:
            # client never connected its doorbell (died mid-negotiation,
            # or teardown closed the listener): the rings are unused
            self.close()
            if self._on_exit is not None:
                self._on_exit(self)
            return
        finally:
            try:
                self._listener.close()
            except OSError:
                pass
        self._doorbell = _Doorbell(sock)
        self.c2s.doorbell = self._doorbell
        self.s2c.doorbell = self._doorbell
        try:
            self._serve_rounds(m)
        finally:
            if self._on_exit is not None:
                # client gone (orderly close or crash-latched ring):
                # release this connection's segments NOW instead of at PS
                # shutdown — elastic worker churn must not accrete
                # 2x-ring-sized /dev/shm leaks per former worker
                self._on_exit(self)

    def _serve_rounds(self, m) -> None:
        while True:
            try:
                # park (uncapped) for the next round's first frame, then
                # decode chunks as they arrive so the handler's fold
                # overlaps the client's remaining writes
                first = self.c2s.read_frame(time.monotonic() + 2**31)
            except ShmTransportError:
                return  # closed / torn down
            try:
                if first is None:
                    continue  # stray end marker (client retry teardown)
                # the generator below takes the frame out of this list: a
                # frame is a view of a receive buffer the ring reuses once
                # nothing refers to it, and a name bound for the whole
                # round would keep that buffer out of the pool
                pending = [first]
                del first
                drained = [False]
                # a shm round IS a fused PushPullStream round: give it
                # the same server-side span (adopting the caller's trace
                # context off the chunks — the field-999 plumbing the
                # ring transport otherwise bypasses) and the same flight
                # start/end stamps as the gRPC handler path
                t0 = time.perf_counter()
                flight.record("rpc.srv.start", note="PushPull/shm")
                holder = obs_trace.SpanHolder("rpc/server/PushPullStream",
                                              transport="shm")

                def chunks() -> Iterator[m.Message]:
                    # tensor payloads stay views of the frame until the
                    # handler has copied them out (Tensor.to_array) or
                    # folded them where they lie (a sink that folds at
                    # once), which it has before it asks for the next chunk
                    frame = pending.pop()
                    while frame is not None:
                        with obs_trace.span("rpc/server/decode",
                                            bytes=len(frame)):
                            chunk = m.GradientUpdate.decode(frame)
                        holder.adopt(getattr(chunk, "trace_context", b""),
                                     chunk.iteration)
                        yield chunk
                        frame = self.c2s.read_frame(
                            time.monotonic() + 3600.0)
                    drained[0] = True

                deadline = time.monotonic() + 3600.0
                try:
                    for resp in self._handler(chunks(), None):
                        self.s2c.write_message(resp, deadline,
                                               "rpc/server/encode")
                finally:
                    holder.finish()
                    flight.record(
                        "rpc.srv.end",
                        a=int(1e6 * (time.perf_counter() - t0)),
                        note="PushPull/shm")
                if not drained[0]:
                    # handler returned early (e.g. the empty-store fused
                    # refusal never reads the gradient chunks): consume the
                    # round's remaining frames so the NEXT round's first
                    # frame is really a first frame — and so a client
                    # blocked writing a ring-sized push gets unstuck
                    for _ in self._request_frames():
                        pass
                self.s2c.write_end(deadline)
            except ShmTransportError:
                return
            except Exception:  # noqa: BLE001 — keep serving other rounds
                log.exception("shm connection handler failed; closing")
                self.close()
                return

    def close(self) -> None:
        for ring in (self.c2s, self.s2c):
            try:
                ring.close()
            except (ValueError, OSError):
                pass
        for sock in (self._doorbell, self._listener):
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass

    def release_segments(self, unmap: bool = True) -> bool:
        """Exactly-once segment release — THE fix for the PR-7 backup
        crash flake.  Before the latch, two paths could both reach the
        unmap for one connection (the serve thread's exit reap and the
        shutdown path's unlink, under post-failover worker churn), and
        the loser unmapped a segment whose ring a native copy could still
        be dereferencing through its raw base pointer: SIGSEGV in the
        backup PS (docs/observability.md has the decoded flight-ring
        evidence).  Returns False on the duplicate call (recorded as
        ``shm.reap.dup`` — the flake's witness event), True when this
        call performed the release.  ``unmap=False`` unlinks only (the
        deferred path when the serve thread cannot be joined)."""
        with self._release_lock:
            if self._released:
                flight.record("shm.reap.dup", a=self.index)
                return False
            self._released = True
        flight.record("shm.reap", a=self.index, b=1 if unmap else 0)
        # drop the raw-address fast path BEFORE any unmap: a racing
        # span falls back to the memoryview, which fails cleanly
        for ring in (self.c2s, self.s2c):
            ring.invalidate()
        for shm in (self._c2s_shm, self._s2c_shm):
            try:
                if unmap:
                    shm.close()
                shm.unlink()
            except (OSError, FileNotFoundError):  # already gone
                pass
        return True

    def unlink(self) -> None:
        self.close()
        self._thread.join(timeout=2.0)
        if self._thread.is_alive():
            # still parked inside the handler (e.g. a barrier wait):
            # unmapping under it would turn a slow shutdown into a raw-
            # address crash — leave the segments mapped (daemon thread +
            # resource tracker clean up at process exit) and only unlink
            # the names so no new attach can find them
            log.warning("shm connection thread still running at teardown; "
                        "deferring segment unmap")
            self.release_segments(unmap=False)
            return
        self.release_segments()


class ShmServer:
    """PS-side registry: answers ``NegotiateShm`` and owns the per-
    connection segments/threads.  ``handler`` is the fused stream handler
    (``ParameterServerService.PushPullStream`` — request-chunk iterator
    in, response iterator out)."""

    def __init__(self, handler: Callable,
                 capacity: int | None = None):
        self._handler = handler
        self._capacity = capacity if capacity is not None else ring_bytes()
        self._host_id = host_id()
        # leaf: held only around the connection-registry dict ops
        self._lock = checked_lock("ShmServer._lock")
        self._conns: list[_ServerConnection] = []
        self._next_index = 0
        self._closed = False

    def _reap(self, conn: "_ServerConnection") -> None:
        """Called FROM a connection's serving thread as it exits (client
        closed, crashed, or never finished the handshake): drop it from
        the registry and release its segments immediately.  The registry
        removal under the lock makes reap-vs-shutdown exactly-once; the
        unmap is safe because the exiting serve thread is the segments'
        last user."""
        with self._lock:
            if conn not in self._conns:
                return  # shutdown path already owns it
            self._conns.remove(conn)
        conn.close()
        # exactly-once via the connection's release latch: the registry
        # check above already dedups reap-vs-shutdown, but the latch also
        # covers the paths that bypass the registry (a connection that
        # never finished negotiation racing its own accept-timeout reap —
        # the PR-7 flake's double-reap window)
        conn.release_segments()
        log.info("shm connection reaped (client disconnected)")

    def _refuse(self, why: str) -> ShmNegotiateResponse:
        log.info("shm negotiation refused: %s", why)
        flight.record("shm.refuse", note=why)
        return ShmNegotiateResponse(accepted=False, message=why,
                                    host_id=self._host_id)

    def negotiate(self, request: ShmNegotiateRequest) -> ShmNegotiateResponse:
        if not enabled():
            return self._refuse("shm transport disabled (PSDT_SHM=0)")
        if request.host_id != self._host_id:
            return self._refuse(
                f"host mismatch: client {request.host_id!r} vs server "
                f"{self._host_id!r}")
        capacity = self._capacity
        if request.ring_bytes:
            capacity = min(capacity, int(request.ring_bytes))
        with self._lock:
            if self._closed:
                return self._refuse("server shutting down")
            index = self._next_index
            self._next_index += 1
        # segment creation + page pretouch + doorbell listen run OUTSIDE
        # the lock (tens of ms of I/O — the lock's contract is registry
        # dict ops only, and N workers negotiating at startup must not
        # serialize behind each other's page-fault storms)
        try:
            conn = _ServerConnection(index, self._handler, capacity,
                                     on_exit=self._reap)
        except (OSError, ValueError, ImportError) as exc:
            # /dev/shm unavailable, exhausted, or shared_memory
            # missing: refuse — the client stays on TCP
            return self._refuse(f"shared memory unavailable: {exc}")
        with self._lock:
            registered = not self._closed
            if registered:
                self._conns.append(conn)
        if not registered:  # shutdown raced the construction
            conn.unlink()
            return self._refuse("server shutting down")
        log.info("shm connection %d negotiated (worker %d, ring %d MB x2)",
                 index, request.worker_id, capacity >> 20)
        flight.record("shm.negotiate", worker=request.worker_id, a=index,
                      b=capacity)
        return ShmNegotiateResponse(
            accepted=True, message="ok", c2s_name=conn.c2s_name,
            s2c_name=conn.s2c_name, ring_bytes=capacity,
            host_id=self._host_id, doorbell=conn.doorbell_addr)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            conns, self._conns = list(self._conns), []
        for conn in conns:
            conn.unlink()
