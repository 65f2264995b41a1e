"""utils/netsim.ThrottledRelay: injected latency/bandwidth are real and
gRPC traffic relays transparently (the substrate of the straggler and
partition scenarios in test_quorum, test_replication, test_flight)."""
from __future__ import annotations

import socket
import threading
import time

import numpy as np
import pytest

from parameter_server_distributed_tpu.utils.netsim import ThrottledRelay


def _echo_server():
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(4)

    def loop():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            def pump(c=conn):
                while True:
                    try:
                        data = c.recv(65536)
                    except OSError:
                        return
                    if not data:
                        return
                    c.sendall(data)
            threading.Thread(target=pump, daemon=True).start()

    threading.Thread(target=loop, daemon=True).start()
    return srv, srv.getsockname()[1]


def test_relay_injects_round_trip_latency():
    srv, port = _echo_server()
    relay = ThrottledRelay(port, delay_ms=30.0)   # one-way 30 -> RTT ~60
    try:
        rport = relay.start()
        with socket.create_connection(("127.0.0.1", rport)) as conn:
            # warm the path, then measure echo RTTs
            conn.sendall(b"x")
            conn.recv(16)
            rtts = []
            for _ in range(3):
                t0 = time.perf_counter()
                conn.sendall(b"ping")
                assert conn.recv(16) == b"ping"
                rtts.append(time.perf_counter() - t0)
        rtt = min(rtts)
        assert rtt >= 0.055, f"RTT {rtt * 1e3:.1f}ms < injected 60ms"
        assert rtt < 0.5, f"RTT {rtt * 1e3:.1f}ms implausibly high"
    finally:
        relay.stop()
        srv.close()


def test_relay_caps_bandwidth_without_serializing_on_latency():
    """8 Mbit/s cap: 1 MB must take ~1 s; the 20 ms one-way delay must
    NOT multiply per chunk (a pipelined link adds latency once)."""
    srv, port = _echo_server()
    relay = ThrottledRelay(port, delay_ms=20.0, mbps=8.0)
    try:
        rport = relay.start()
        payload = np.random.default_rng(0).bytes(1_000_000)
        got = bytearray()
        with socket.create_connection(("127.0.0.1", rport)) as conn:
            t0 = time.perf_counter()

            def sender():
                conn.sendall(payload)

            th = threading.Thread(target=sender, daemon=True)
            th.start()
            while len(got) < len(payload):
                chunk = conn.recv(65536)
                assert chunk, "connection dropped mid-transfer"
                got.extend(chunk)
            dt = time.perf_counter() - t0
        assert bytes(got) == payload
        # 1 MB at 8 Mbit/s = 1.0 s per direction; the two directions
        # PIPELINE through the echo (like a real full-duplex link), so
        # total ~1 s — and if the 20 ms delay serialized per 64KB chunk
        # the 2 x 16 chunks would add >= 0.64 s on top
        assert dt >= 0.95, f"transfer {dt:.2f}s beat the 8 Mbit/s cap"
        assert dt < 1.8, f"transfer {dt:.2f}s: delay appears serialized"
    finally:
        relay.stop()
        srv.close()


@pytest.mark.slow
def test_pushpull_through_relay_roundtrips():
    """The PS gRPC data plane works unchanged through the relay."""
    from parameter_server_distributed_tpu.config import (
        ParameterServerConfig)
    from parameter_server_distributed_tpu.core.tensor import to_wire
    from parameter_server_distributed_tpu.rpc import messages as m
    from parameter_server_distributed_tpu.rpc.data_plane import PSClient
    from parameter_server_distributed_tpu.server.ps_service import (
        ParameterServer)

    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        autosave_period_s=3600.0, checkpoint_dir="/tmp"))
    port = ps.start()
    relay = ThrottledRelay(port, delay_ms=5.0, mbps=200.0)
    try:
        rport = relay.start()
        rng = np.random.default_rng(0)
        params = {"w": rng.standard_normal((256, 64)).astype(np.float32)}
        ps.core.initialize_parameters(params)
        client = PSClient(f"127.0.0.1:{rport}")
        grads = to_wire({"w": np.ones((256, 64), np.float32)},
                        m.WIRE_BF16)
        t0 = time.perf_counter()
        client.push_gradients(m.GradientUpdate(worker_id=0, iteration=1,
                                               gradients=grads))
        resp = client.pull_parameters(m.PullRequest(
            worker_id=0, iteration=1, wire_dtype=m.WIRE_BF16))
        dt = time.perf_counter() - t0
        assert resp.parameters
        # two RPCs x RTT 10ms minimum through the relay
        assert dt >= 0.02
        client.close()
    finally:
        relay.stop()
        ps.stop()
