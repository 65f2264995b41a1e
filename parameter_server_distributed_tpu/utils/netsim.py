"""Userspace network-condition injection for loopback tests.

The top-k/bf16 wire encodings exist to win on a REAL network boundary
(DCN between PS hosts and workers), where bytes cost wall-clock; on
localhost the kernel moves bytes almost for free and the byte
advantage vanishes.  The honest
way to measure the wire win without two hosts is to inject latency and
a bandwidth cap into the path.  Kernel tools (tc netem / tbf) need
modules this environment's kernel doesn't ship, so this is a portable
userspace equivalent: a TCP relay that forwards byte-for-byte while

- delaying each chunk by ``delay_ms`` (one-way; applied in both
  directions, so round-trips see ~2x), WITHOUT serializing the stream —
  chunks are timestamped at read and released at read-time + delay,
  preserving pipelining exactly like a long link does, and
- pacing writes to ``mbps`` megabits/second per direction (token-bucket
  style: the writer owes ``bytes/rate`` seconds after each chunk).

gRPC/HTTP-2 traffic relays transparently (it is plain TCP).  One relay
fronts one backend port; a test starts one in front of a PS (or of one
worker's PS leg: the straggler of tests/test_quorum.py) and points the
client at the relay port (reference wire comparison: the reference's repeated-float
proto has no compression at all — reference proto/parameter_server.proto:19-24).
"""

from __future__ import annotations

import socket
import struct
import threading
import time
from queue import Queue

_CHUNK = 65536


class ThrottledRelay:
    """TCP relay 127.0.0.1:<listen_port> -> 127.0.0.1:<target_port> with
    one-way delay and a per-direction bandwidth cap.

    >>> relay = ThrottledRelay(target_port, delay_ms=10, mbps=500)
    >>> port = relay.start()     # connect clients here
    >>> relay.stop()
    """

    def __init__(self, target_port: int, delay_ms: float = 0.0,
                 mbps: float = 0.0, host: str = "127.0.0.1"):
        self.target = (host, int(target_port))
        self.delay_s = float(delay_ms) / 1e3
        # bytes/second; 0 = uncapped
        self.rate = float(mbps) * 1e6 / 8.0
        self._listener: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        # on-the-wire byte totals per direction, across all connections —
        # lets a test/bench assert what a transport change (e.g. packed
        # wire dtypes) actually put on the link, independent of what the
        # application THINKS it sent (counted at relay read, before
        # delay/pacing)
        self._byte_lock = threading.Lock()
        self.bytes_to_target = 0     # client -> backend (requests)
        self.bytes_from_target = 0   # backend -> client (responses)
        # chaos state (replication failover tests): live relayed sockets,
        # so drop_connections() can hard-close them all, and the refusal
        # latch that makes subsequent connects die too — a process
        # kill/partition without an OS-level kill in-tree
        self._conn_lock = threading.Lock()
        self._conns: list[socket.socket] = []
        self._refuse = False

    def byte_counts(self) -> tuple[int, int]:
        """(bytes_to_target, bytes_from_target) so far."""
        with self._byte_lock:
            return self.bytes_to_target, self.bytes_from_target

    def reset_byte_counts(self) -> None:
        with self._byte_lock:
            self.bytes_to_target = 0
            self.bytes_from_target = 0

    # ------------------------------------------------------------ lifecycle
    def start(self) -> int:
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind((self.target[0], 0))
        listener.listen(64)
        self._listener = listener
        accept = threading.Thread(target=self._accept_loop, daemon=True,
                                  name="netsim-accept")
        accept.start()
        self._threads.append(accept)
        return listener.getsockname()[1]

    def stop(self) -> None:
        self._stop.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        self.drop_connections(refuse_new=True)

    # --------------------------------------------------------------- chaos
    def drop_connections(self, refuse_new: bool = True) -> int:
        """Process-kill/partition chaos: hard-close every relayed
        connection (both endpoints observe an abrupt stream death, like a
        ``kill -9`` of the backend) and, with ``refuse_new`` (default),
        make later connects die immediately too — the shard stays "dead"
        until :meth:`restore_connections`.  Returns how many sockets were
        severed.  The failover tests use this to sever one PS shard
        without an OS-level kill in-tree."""
        with self._conn_lock:
            self._refuse = refuse_new
            conns, self._conns = self._conns, []
        for sock in conns:
            try:
                # RST, not FIN: linger-0 abort so the peer's in-flight
                # RPC fails NOW instead of waiting out a half-open drain
                sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        return len(conns)

    def restore_connections(self) -> None:
        """Lift the refusal latch set by :meth:`drop_connections`: NEW
        connections relay normally again (severed ones stay dead)."""
        with self._conn_lock:
            self._refuse = False

    def _register_conn(self, *socks: socket.socket) -> bool:
        """Track sockets for the chaos teardown; False when the relay is
        currently refusing (the caller must close them)."""
        with self._conn_lock:
            if self._refuse:
                return False
            self._conns.extend(socks)
            return True

    # ------------------------------------------------------------- internals
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            with self._conn_lock:
                refusing = self._refuse
            if refusing:
                # "dead host": accept then abort, so the client observes
                # an immediate connection failure, not a hang
                try:
                    conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                    struct.pack("ii", 1, 0))
                except OSError:
                    pass
                conn.close()
                continue
            try:
                upstream = socket.create_connection(self.target)
            except OSError:
                conn.close()
                continue
            if not self._register_conn(conn, upstream):
                # drop_connections raced the accept: sever both ends
                for sock in (conn, upstream):
                    try:
                        sock.close()
                    except OSError:
                        pass
                continue
            for src, dst, attr in ((conn, upstream, "bytes_to_target"),
                                   (upstream, conn, "bytes_from_target")):
                self._pump(src, dst, attr)

    def _pump(self, src: socket.socket, dst: socket.socket,
              count_attr: str) -> None:
        """One direction: a reader timestamps chunks into a queue, a
        writer releases each at read-time + delay and paces to the rate —
        the pipelined long-link model (latency does not serialize
        throughput, bandwidth is capped independently)."""
        q: Queue = Queue(maxsize=256)

        def reader():
            try:
                while not self._stop.is_set():
                    data = src.recv(_CHUNK)
                    if not data:
                        break
                    with self._byte_lock:
                        setattr(self, count_attr,
                                getattr(self, count_attr) + len(data))
                    q.put((time.monotonic(), data))
            except OSError:
                pass
            q.put((0.0, b""))          # EOF sentinel

        def writer():
            pace = time.monotonic()
            try:
                while True:
                    ts, data = q.get()
                    if not data:
                        break
                    release = ts + self.delay_s
                    if self.rate > 0:
                        pace = max(pace, time.monotonic())
                        release = max(release, pace)
                        pace = release + len(data) / self.rate
                    wait = release - time.monotonic()
                    if wait > 0:
                        time.sleep(wait)
                    dst.sendall(data)
            except OSError:
                pass
            # half-close so gRPC sees clean stream shutdown
            try:
                dst.shutdown(socket.SHUT_WR)
            except OSError:
                pass

        for fn in (reader, writer):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"netsim-{fn.__name__}")
            t.start()
            self._threads.append(t)
