#!/usr/bin/env python3
"""What a shm ring end can move on this machine, and what the ring makes of it.

    chiprun -- python3 scripts/ring_pace.py [--tree DIR] [--parts a,b,c,e] \
        [--total-mb 1625] [--name OUT]

No chip is needed, but it is THAT host's memory that counts.  The payload
is the PS cell's (``ps_round_gpt2m``: 1.6 GB a direction in about
twenty-five frames, two of 206 MB), every buffer warm, every reading GB/s
(decimal), the best of ``--repeat`` passes.  JSON lines, and
``chiprun_out/<name>.json``:

- ``a``: the tree's native copy, private memory to private memory, in calls
  of 1, 4, 8, 32 MB and whole, on one thread;
- ``b``: the same calls on k Python threads at once (the GIL is released),
  and, where the tree has it, 8 MB spans through the library's own
  fork-join at width k with plain stores (``native.copy_fn`` of PR 45
  on);
- ``c``: the payload through a 32 MB ring of the tree's
  ``rpc/shm_transport.py`` (``write_message`` -> ``read_frame``): between
  two threads of this process (the cell's shape) and between two processes
  (a deployment's), with the share of ``rpc.shm.bytes`` that moved wide;
- ``e``: the worker's serve side on ONE thread: a frame copied out of a
  ring's memory in spans of a quarter ring, then ``np.copyto`` of the frame
  into a warm buffer of the payload's size (the landing); the SUM is what
  the thread pays.  Plain stores against streaming stores (the mover's
  flag 2), at widths 1 to 4; PR 45's tree on.

``--tree`` is the checkout to probe (default: the one this file lies in), so
a parent unpacked under ``.perfbench_copies/parent`` is read by the same
script as the change (the issue's (c) is ``--tree`` the parent, (d) this one).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MB = 1_000_000
# the store of the scanned GPT-2 medium as the data plane frames it: chunks
# of about 32 MB (rpc/data_plane.py DEFAULT_CHUNK_BYTES), a larger tensor
# alone; shares of the whole, so --total-mb scales them
FRAME_SHARES = [206, 4] + [67] * 12 + [50] * 8 + [2, 1, 206]
RING = 32 << 20

def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def cpu_model() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    return line.partition(":")[2].strip()
    except OSError:
        pass
    return "unknown"


def frames(total: int) -> list[int]:
    scale = total / sum(FRAME_SHARES)
    return [max(1, int(share * scale)) for share in FRAME_SHARES]


def best_gbps(nbytes: int, fn, repeat: int) -> float:
    """GB/s of ``fn()`` over ``nbytes``, the best of ``repeat`` passes."""
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return round(nbytes / best / 1e9, 2)


_HELPERS = ThreadPoolExecutor(max_workers=8)


def on_threads(k: int, fn) -> None:
    """``fn(i, k)`` on k threads at once (this one and kept helpers)."""
    others = [_HELPERS.submit(fn, i, k) for i in range(1, k)]
    fn(0, k)
    for other in others:
        other.result()


def plain_copy(native):
    """``copy(dst_addr, src_addr, n)`` of the tree's library on the calling
    thread, and the library's span mover where it has one."""
    lib = native.lib()
    if lib is None:
        raise SystemExit("no native library on this machine")
    mover = getattr(lib, "psdt_ring_move", None)
    if mover is None:          # a tree before PR 45
        return lib.psdt_copy, None
    return (lambda dst, src, n: mover(dst, n, 0, src, n, 1, 1)), mover


def in_calls(copy, dst: int, src: int, a: int, b: int, call: int) -> None:
    for at in range(a, b, call):
        copy(dst + at, src + at, min(call, b - at))


def part_a_b(np, native, total: int, repeat: int, parts: set) -> dict:
    copy, mover = plain_copy(native)
    src = np.full(total, 7, np.uint8)
    dst = np.zeros(total, np.uint8)
    s, d = src.ctypes.data, dst.ctypes.data
    calls = {"1MB": 1 << 20, "4MB": 4 << 20, "8MB": 8 << 20,
             "32MB": 32 << 20, "whole": total}
    out: dict = {}
    if "a" in parts:
        out["a_one_thread_by_call"] = {
            name: best_gbps(total, lambda: in_calls(copy, d, s, 0, total, c),
                            repeat) for name, c in calls.items()}
        say(detail="a", **out)
    if "b" in parts:
        def cut(call):
            def run(i, k):
                in_calls(copy, d, s, total * i // k, total * (i + 1) // k,
                         call)
            return run
        out["b_python_threads_by_call"] = {
            name: {k: best_gbps(total, lambda: on_threads(k, cut(c)), repeat)
                   for k in (1, 2, 3, 4, 6, 8)}
            for name, c in calls.items() if name in ("1MB", "8MB", "whole")}
        if mover is not None:
            span = 8 << 20
            out["b_library_width_8MB_spans"] = {
                k: best_gbps(total, lambda: [
                    mover(d + at, span, 0, s + at, min(span, total - at), 1,
                          k) for at in range(0, total, span)], repeat)
                for k in (1, 2, 3, 4, 6, 8)}
        say(detail="b", **{k: v for k, v in out.items() if k[0] == "b"})
    return out


class _Bytes:
    """Bytes that already exist, as a message a ring can send."""

    def __init__(self, data):
        self.data = data

    def encoded_size(self):
        return len(self.data)

    def encode_into(self, writer):
        writer.write(self.data)


def produce(ring, payload, sizes: list[int], laps: int) -> None:
    deadline = time.monotonic() + 600
    for _ in range(laps):
        at = 0
        for n in sizes:
            ring.write_message(_Bytes(payload[at:at + n]), deadline,
                               "bench/encode")
            at += n
        ring.write_end(deadline)


def consume(ring, laps: int) -> list[float]:
    """Seconds of each lap, from its first frame's arrival to its end."""
    out = []
    for _ in range(laps):
        first = ring.read_frame(time.monotonic() + 600)
        t0 = time.perf_counter()
        del first
        while ring.read_frame(time.monotonic() + 600) is not None:
            pass
        out.append(time.perf_counter() - t0)
    return out


def child_consume(args) -> int:
    from parameter_server_distributed_tpu.rpc import shm_transport as st
    seg = st._attach_segment(args.child_consume)
    sock = st._doorbell_connect(args.doorbell)
    ring = st.ShmRing(seg, RING, st._Doorbell(sock))
    say(laps=consume(ring, args.laps))
    return 0


def child_produce(args) -> int:
    import numpy as np

    from parameter_server_distributed_tpu.rpc import shm_transport as st
    sizes = frames(args.total_mb * MB)
    payload = memoryview(np.full(sum(sizes), 7, np.uint8))
    seg = st._attach_segment(args.child_produce)
    listener, addr = st._doorbell_listener()
    say(doorbell=addr)
    sock, _ = listener.accept()
    produce(st.ShmRing(seg, RING, st._Doorbell(sock)), payload, sizes,
            args.laps)
    return 0


def shm_counters(stats) -> tuple:
    snap = stats.REGISTRY.snapshot()["counters"]
    return snap.get("rpc.shm.bytes", 0), snap.get("rpc.shm.wide_bytes")


def part_c(np, total: int, repeat: int) -> dict:
    from parameter_server_distributed_tpu.obs import stats
    from parameter_server_distributed_tpu.rpc import shm_transport as st

    sizes = frames(total)
    payload = memoryview(np.full(sum(sizes), 7, np.uint8))
    laps = 1 + repeat          # the first grows the receive buffers
    moved = sum(sizes) - sizes[0]
    out: dict = {"frames": len(sizes), "largest_mb": max(sizes) / MB}

    seg = st._create_segment(f"psdt-pace-{os.getpid()}-t", 64 + RING)
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    prod = st.ShmRing(seg, RING, st._Doorbell(a))
    cons = st.ShmRing(seg, RING, st._Doorbell(b))
    before = shm_counters(stats)
    th = threading.Thread(target=produce, args=(prod, payload, sizes, laps))
    th.start()
    took = consume(cons, laps)
    th.join()
    after = shm_counters(stats)
    out["c_two_threads_gbps"] = round(moved / min(took[1:]) / 1e9, 2)
    if after[1] is not None:
        out["wide_pct"] = round(
            100 * (after[1] - (before[1] or 0)) / (after[0] - before[0]), 2)
    del prod, cons
    seg.close()
    seg.unlink()
    say(detail="c_threads", **out)

    # both ends as processes of their own; a tree before PR 45 can die
    # here (a cursor read as zero across processes: PERF.md section 6,
    # PR 45), so a few tries
    me = [sys.executable, os.path.abspath(__file__), "--tree", os.getcwd(),
          "--total-mb", str(total // MB), "--laps", str(laps)]
    for attempt in range(5):
        seg = st._create_segment(f"psdt-pace-{os.getpid()}-p{attempt}",
                                 64 + RING)
        producer = subprocess.Popen(me + ["--child-produce", seg.name],
                                    stdout=subprocess.PIPE, text=True)
        addr = json.loads(producer.stdout.readline())["doorbell"]
        consumer = subprocess.Popen(
            me + ["--child-consume", seg.name, "--doorbell", addr],
            stdout=subprocess.PIPE, text=True)
        said = consumer.communicate(timeout=600)[0]
        codes = producer.wait(timeout=600), consumer.returncode
        seg.close()
        seg.unlink()
        if codes == (0, 0):
            took = json.loads(said)["laps"]
            out["c_two_processes_gbps"] = round(
                moved / min(took[1:]) / 1e9, 2)
            break
        out.setdefault("c_two_processes_died", []).append(codes)
    say(detail="c_processes", **out)
    return out


def part_e(np, native, total: int, repeat: int) -> dict:
    """Frame by frame on one thread: ring memory -> frame in spans of a
    quarter ring through the library's mover, then the landing."""
    _, mover = plain_copy(native)
    if mover is None:
        return {}
    sizes = frames(total)
    ring = np.full(RING, 7, np.uint8)
    frame = np.zeros(max(sizes), np.uint8)
    store = np.zeros(sum(sizes) // 4 + 1, np.float32)
    span = RING // 4

    def one_pass(flags, k):
        copy_s = land_s = 0.0
        at = 0
        for n in sizes:
            t0 = time.perf_counter()
            for off in range(0, n, span):
                mover(ring.ctypes.data, RING, off % RING,
                      frame.ctypes.data + off, min(span, n - off), flags, k)
            t1 = time.perf_counter()
            words = n // 4
            np.copyto(store[at:at + words],
                      frame[:4 * words].view(np.float32))
            land_s += time.perf_counter() - t1
            copy_s += t1 - t0
            at += words
        return copy_s, land_s

    out: dict = {}
    for name, flags in (("plain", 0), ("stream", 2)):
        for k in (1, 2, 3, 4):
            best = min((one_pass(flags, k) for _ in range(repeat)), key=sum)
            out[f"e_{name}_width{k}"] = {
                "copy_gbps": round(sum(sizes) / best[0] / 1e9, 2),
                "landing_gbps": round(sum(sizes) / best[1] / 1e9, 2),
                "sum_s": round(sum(best), 4)}
    say(detail="e", **out)
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=HERE)
    parser.add_argument("--parts", default="a,b,c,e")
    parser.add_argument("--total-mb", type=int, default=1625)
    parser.add_argument("--repeat", type=int, default=3)
    parser.add_argument("--name", default="ring_pace")
    parser.add_argument("--child-consume", default="")
    parser.add_argument("--child-produce", default="")
    parser.add_argument("--doorbell", default="")
    parser.add_argument("--laps", type=int, default=2)
    args = parser.parse_args()
    tree = os.path.abspath(args.tree)
    os.chdir(tree)
    sys.path.insert(0, tree)
    if args.child_consume:
        return child_consume(args)
    if args.child_produce:
        return child_produce(args)
    import numpy as np

    from parameter_server_distributed_tpu import native

    parts = set(args.parts.split(","))
    total = args.total_mb * MB
    report: dict = {"tree": tree, "total_mb": args.total_mb,
                    "cores": len(os.sched_getaffinity(0)), "cpu": cpu_model()}
    say(**report)
    report.update(part_a_b(np, native, total, args.repeat, parts))
    if "c" in parts:
        report.update(part_c(np, total, args.repeat))
    if "e" in parts:
        report.update(part_e(np, native, total, args.repeat))
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.name}.json"), "w") as f:
        json.dump(report, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
