"""``ps.shm_wide_pct_in_window`` (PR 45): its entry, its file, what its
reader makes of a program with and without the counter, and the rehearsed
cell.  The share of ``rpc.shm.bytes`` that moved in spans cut over more than
one thread: 99 or more on the chip, where every frame is tens of MB, and 0
at a rehearsal's size, where no frame reaches the 2 MB a span is cut from
(which is why it carries the suffix the runner's rehearsal lets read 0).
This cell and this metric only.  CPU only."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

NAME = "ps.shm_wide_pct_in_window"
WIDE, MOVED = "rpc.shm.wide_bytes", "rpc.shm.bytes"
CELL = "ps_round_gpt2m"
BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def observed(before, after, rounds=18):
    return {"rounds": rounds, "window": (0.0, 51.0),
            "registry_before": {"counters": before, "histograms": {}},
            "registry_after": {"counters": after, "gauges": {},
                               "histograms": {}}}


def test_the_entry_and_the_file_say_what_the_metric_is():
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "wire"
    assert entry["moves"] == "ps_tokens_per_s"
    assert CELL in entry["workloads"]
    assert harness.load_json(os.path.join(
        ROOT, "perfbench", "metrics", f"{NAME}.json")) == {
            "reader": "counter_ratio", "args": {
                "numerator": WIDE, "denominator": MOVED, "scale": 100.0}}


@pytest.mark.parametrize("before,after,expected", [
    # the chip's round: 6,500.6 MB through the rings, all but prefixes,
    # headers and end markers in wide spans
    ({WIDE: 10, MOVED: 20}, {WIDE: 10 + 18 * 6_490_000_000,
                             MOVED: 20 + 18 * 6_500_600_000},
     {NAME: 100 * 6_490_000_000 / 6_500_600_000}),
    # the ring fell into slivers, or the machine has no cores to spare
    ({WIDE: 0, MOVED: 0}, {WIDE: 0, MOVED: 6_500_600_000}, {NAME: 0.0}),
    # the parent counts the bytes and not the wide ones: nothing, no error
    ({MOVED: 1}, {MOVED: 9}, {}),
    # nothing went through a ring (TCP): nothing
    ({WIDE: 5, MOVED: 7}, {WIDE: 5, MOVED: 7}, {}),
], ids=["chip", "slivers", "parent", "no_ring"])
def test_reads_the_share_through_the_harness(before, after, expected):
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    only = dict(BENCHMARK, per_layer=[m for m in BENCHMARK["per_layer"]
                                      if m["name"] == NAME])
    got = harness.read_per_layer(only, cell, observed(before, after))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(expected)
    assert all(v["unit"] == "%" for v in got.values())


def test_the_rehearsed_cell_reports_it():
    """The traced rehearsal of the cell has the metric on its line (0 at
    the tiny size: its frames are a few hundred KB) beside the bytes it is
    a share of."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3000000029", "--seconds", "2",
         "--trace", "1", "--rehearse"], cwd=ROOT, capture_output=True,
        text=True, timeout=600, env=dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])["not_a_result"]
    assert line["correct"] is True
    assert line["metrics"][NAME]["unit"] == "%"
    assert 0.0 <= line["metrics"][NAME]["value"] <= 100.0
    assert line["metrics"]["ps.shm_mb_per_round"]["value"] > 0


def test_the_program_counts_under_that_name(monkeypatch):
    """A payload of a span or more through a ring moves the counter at
    both ends; the counter is made with the module, so a program that
    moved nothing wide reads 0, not nothing."""
    import socket
    import threading
    import time

    from parameter_server_distributed_tpu import native
    from parameter_server_distributed_tpu.obs import stats
    from parameter_server_distributed_tpu.rpc import shm_transport as st

    assert WIDE in stats.REGISTRY.snapshot()["counters"]
    if native.copy_fn() is None:
        pytest.skip("no native library on this machine")
    monkeypatch.setattr(st, "_MAX_WIDTH", 2)  # whatever cores it has

    class Payload:
        data = bytes(8 << 20)

        def encoded_size(self):
            return len(self.data)

        def encode_into(self, writer):
            writer.write(self.data)

    seg = st._create_segment(f"psdt-test-{time.monotonic_ns()}",
                             64 + (32 << 20))
    a, b = socket.socketpair(socket.AF_UNIX, socket.SOCK_STREAM)
    prod = st.ShmRing(seg, 32 << 20, st._Doorbell(a))
    cons = st.ShmRing(seg, 32 << 20, st._Doorbell(b))
    wide = stats.counter(WIDE)
    before = wide.value
    try:
        th = threading.Thread(target=prod.write_message, daemon=True, args=(
            Payload(), time.monotonic() + 30, "test/encode"))
        th.start()
        assert len(cons.read_frame(time.monotonic() + 30)) == 8 << 20
        th.join(timeout=30)
        assert wide.value - before == 2 * (8 << 20)
    finally:
        del prod, cons
        seg.close()
        seg.unlink()
