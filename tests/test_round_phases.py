"""The PS round by its phases (ISSUE 52): ``rpc/round/send``, ``/turn`` and
``/receive`` on the worker's thread, the server thread's ring legs tied to
their round, and the upload's wait apart from the step's.

A worker and a parameter server in one process over the shm rings, as
``tests/test_obs_legs.py`` assembles them; two recorded rounds, so that the
server's wait BETWEEN two rounds is there to be looked at."""

import collections

import pytest

from parameter_server_distributed_tpu.cli.worker_main import build_worker
from parameter_server_distributed_tpu.config import (CoordinatorConfig,
                                                     ParameterServerConfig,
                                                     WorkerConfig)
from parameter_server_distributed_tpu.obs import trace as obs_trace
from parameter_server_distributed_tpu.server.coordinator_service import (
    Coordinator)
from parameter_server_distributed_tpu.server.ps_service import ParameterServer
from parameter_server_distributed_tpu.worker import trainer as trainer_mod

PHASES = ("rpc/round/send", "rpc/round/turn", "rpc/round/receive")
RECORDED = (3, 4)
CLOCK = 2e-4      # two reads of time.time() around one instant


def end(span) -> float:
    return span["ts"] + span["dur"]


def iteration_of(span):
    return span.get("args", {}).get("iteration")


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    """Spans of two steady rounds recorded, the uploads waited for while
    they were, and both for a round with recording off."""
    patch = pytest.MonkeyPatch()
    # small buckets, chunks and ring: several frames a round each way, and
    # both ends of a ring wait for each other
    patch.setenv("PSDT_BUCKET_BYTES", str(64 << 10))
    patch.setenv("PSDT_STREAM_CHUNK_BYTES", str(64 << 10))
    patch.setenv("PSDT_SHM_RING_BYTES", str(8 << 10))
    waited = []
    wait = trainer_mod._wait_for_upload
    patch.setattr(trainer_mod, "_wait_for_upload",
                  lambda uploaded: (waited.append(1), wait(uploaded)))
    ps = ParameterServer(ParameterServerConfig(
        bind_address="127.0.0.1", port=0, total_workers=1,
        checkpoint_dir=str(tmp_path_factory.mktemp("ps")),
        learning_rate=0.05, autosave_period_s=600.0))
    ps_port = ps.start()
    coordinator = Coordinator(CoordinatorConfig(
        bind_address="127.0.0.1", port=0, ps_address="127.0.0.1",
        ps_port=ps_port, reap_period_s=600.0))
    port = coordinator.start()
    worker = build_worker(WorkerConfig(
        coordinator_address=f"127.0.0.1:{port}", worker_id=0, iterations=8,
        batch_size=16, model="mnist_mlp", heartbeat_period_s=600.0,
        fused_step=True))
    try:
        worker.initialize()
        for iteration in range(RECORDED[0]):    # seed, renegotiate, steady
            worker.run_iteration(iteration)
        assert worker._ps.shm_active
        del waited[:]
        obs_trace.clear()
        obs_trace.enable(True)
        for iteration in RECORDED:
            worker.run_iteration(iteration)
        obs_trace.enable(False)
        recorded = {"spans": obs_trace.spans(), "uploads": len(waited)}
        # the frame the server's thread parked in while recording was on
        # closes in the next round: let it, then look at a round of its own
        worker.run_iteration(RECORDED[-1] + 1)
        obs_trace.clear()
        del waited[:]
        worker.run_iteration(RECORDED[-1] + 2)
        recorded["spans_off"] = obs_trace.spans()
        recorded["uploads_off"] = len(waited)
    finally:
        obs_trace.enable(False)
        obs_trace.clear()
        worker.shutdown()
        coordinator.stop()
        ps.stop()
        patch.undo()
    steps = {iteration_of(s): s for s in recorded["spans"]
             if s["name"] == "worker/step"}
    assert sorted(steps) == list(RECORDED)
    recorded["steps"] = steps
    recorded["worker_tid"] = steps[RECORDED[0]]["tid"]
    return recorded


def named(rounds, name, iteration=None, mine=True):
    return [s for s in rounds["spans"] if s["name"] == name
            and (s["tid"] == rounds["worker_tid"]) == mine
            and (iteration is None or iteration_of(s) == iteration)]


@pytest.mark.parametrize("iteration", RECORDED)
def test_a_round_has_its_three_phases_once_in_order(rounds, iteration):
    step = rounds["steps"][iteration]
    call, = named(rounds, "rpc/client/PushPullStream", iteration)
    phases = []
    for name in PHASES:
        phase, = [s for s in rounds["spans"] if s["name"] == name
                  and iteration_of(s) == iteration]
        assert phase["tid"] == step["tid"]
        assert phase["parent_id"] == call["span_id"]
        assert phase["trace_id"] == step["trace_id"]
        phases.append(phase)
    send, turn, receive = phases
    assert call["ts"] - CLOCK <= send["ts"]
    assert end(send) <= turn["ts"] + CLOCK
    assert end(turn) <= receive["ts"] + CLOCK
    assert end(receive) <= end(call) + CLOCK
    # nothing of the call but its own glue lies between them
    assert sum(p["dur"] for p in phases) >= 0.9 * call["dur"]


@pytest.mark.parametrize("iteration", RECORDED)
def test_the_phases_hold_the_legs_in_time(rounds, iteration):
    """Every ring leg of the worker's thread lies inside one phase: the
    request frames in ``send``, the first response frame in ``turn``, the
    rest and every landing in ``receive``.  By time, not by id: a leg
    keeps the parent it had without the phases (``obs_trace.phases``)."""
    call, = named(rounds, "rpc/client/PushPullStream", iteration)
    phases = [named(rounds, name, iteration)[0] for name in PHASES]
    held = collections.Counter()
    for name in ("rpc/shm/copy", "rpc/shm/wait", "rpc/client/decode",
                 "rpc/client/encode", "worker/d2h"):
        for leg in named(rounds, name, iteration):
            if leg["ts"] < call["ts"]:
                continue            # the loss's fetch, inside worker/compute
            middle = leg["ts"] + leg["dur"] / 2
            phase, = [p for p in phases if p["ts"] <= middle < end(p)]
            assert phase["ts"] - CLOCK <= leg["ts"], (leg, phase)
            assert end(leg) <= end(phase) + CLOCK, (leg, phase)
            held[phase["name"], name] += 1
            assert leg["parent_id"] not in {p["span_id"] for p in phases}
    sent = len(named(rounds, "ps/fold", iteration, mine=False))
    assert held["rpc/round/send", "rpc/shm/copy"] == sent > 1
    assert held["rpc/round/send", "worker/d2h"] == sent - 1
    assert held["rpc/round/turn", "rpc/shm/copy"] == 1
    assert held["rpc/round/receive", "rpc/shm/copy"] > 1
    assert held["rpc/round/send", "rpc/client/decode"] == \
        held["rpc/round/turn", "rpc/client/decode"] == 0
    assert held["rpc/round/receive", "rpc/client/encode"] == \
        held["rpc/round/turn", "rpc/client/encode"] == 0
    # one frame, one carved wait at most: the wait for the server's close
    assert held["rpc/round/turn", "rpc/shm/wait"] <= 1


@pytest.mark.parametrize("iteration", RECORDED)
def test_the_servers_ring_legs_know_their_round(rounds, iteration):
    handler, = named(rounds, "rpc/server/PushPullStream", iteration,
                     mine=False)
    call, = named(rounds, "rpc/client/PushPullStream", iteration)
    assert handler["parent_id"] == call["span_id"]
    inside = [s for s in rounds["spans"] if s["tid"] == handler["tid"]
              and s["name"] in ("rpc/shm/copy", "rpc/shm/wait",
                                "rpc/server/encode", "ps/fold")
              and handler["ts"] <= s["ts"] and end(s) <= end(handler)]
    assert all(iteration_of(s) == iteration for s in inside)
    count = collections.Counter(s["name"] for s in inside)
    sent = count["ps/fold"]
    received = len([s for s in named(rounds, "rpc/client/decode", iteration)
                    if "bytes" in s["args"]])
    # every request frame but the round's first, which is read before its
    # context is known, the request's end marker, and every response frame
    assert count["rpc/shm/copy"] == sent - 1 + 1 + received
    apply, = named(rounds, "ps/apply", iteration, mine=False)
    assert apply["tid"] == handler["tid"]
    # the decode of a request frame's header: all but the first frame's
    decodes = [s for s in named(rounds, "rpc/server/decode", iteration,
                                mine=False) if "bytes" in s["args"]]
    assert len(decodes) == sent - 1


def test_the_wait_for_the_next_round_keeps_no_iteration(rounds):
    """Between two rounds the server's thread is parked in the ring for the
    next round's first frame: the worker's whole step.  That wait belongs
    to no round and must stay out of every sum by round."""
    first, second = (rounds["steps"][i] for i in RECORDED)
    compute, = named(rounds, "worker/compute", RECORDED[1])
    server_tid = named(rounds, "ps/apply", RECORDED[1], mine=False)[0]["tid"]
    unowned = [s for s in rounds["spans"] if s["tid"] == server_tid
               and iteration_of(s) is None]
    assert {s["name"] for s in unowned} <= {
        "rpc/shm/wait", "rpc/shm/copy", "rpc/server/decode"}
    parked = [s for s in unowned if s["name"] == "rpc/shm/wait"
              and s["ts"] <= compute["ts"] + CLOCK
              and end(s) >= end(compute) - CLOCK]
    assert len(parked) == 1
    assert end(first) - parked[0]["ts"] < first["dur"]   # began in round 3
    assert second["ts"] < end(parked[0]) < end(second)


@pytest.mark.parametrize("iteration", RECORDED)
def test_the_uploads_wait_lies_inside_the_device_wait(rounds, iteration):
    wait, = named(rounds, "worker/device_wait", iteration)
    upload, = named(rounds, "worker/device_wait/upload", iteration)
    assert upload["parent_id"] == wait["span_id"]
    assert wait["ts"] - CLOCK <= upload["ts"]
    assert end(upload) <= end(wait) + CLOCK
    assert rounds["uploads"] == len(RECORDED)


def test_recording_off_keeps_no_span_and_waits_for_no_upload(rounds):
    assert rounds["spans_off"] == []
    assert rounds["uploads_off"] == 0
