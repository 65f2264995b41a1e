"""Crash-surviving flight recorder: a per-process mmap-backed event ring.

PR 1's ``obs/`` layer is live-only: counters and in-memory spans die with
the process, so a backup PS that segfaults under post-failover churn (the
PR-7 known flake) leaves NO evidence.  This module is the black box the
postmortem toolchain (:mod:`obs.postmortem`, ``pst-trace``) reads after
the fact — including for processes that died by ``kill -9``.

Design constraints, in order:

1. **Crash-surviving.**  The ring is a fixed-size file under
   ``PSDT_FLIGHT_DIR`` mapped MAP_SHARED: every record lands in the page
   cache the instant it is written, so a SIGKILL/SIGSEGV loses at most
   the record being written (and the seq field is written LAST, so a torn
   record is recognizably invalid, never silently wrong).  No flush call
   is ever needed for survival — the kernel owns the pages.
2. **Always-on cheap.**  :func:`record` is one global truthiness check
   when no ring is open; with a ring it is one GIL-atomic counter
   increment + one ``struct.pack`` + two slice stores (~1-2 us) and takes
   NO lock — safe inside ``_state_lock`` and the striped fold hot path.
   The per-chunk fold class honors ``PSDT_FLIGHT_SAMPLE`` (record every
   Nth); paired start/end events are never sampled, so the postmortem's
   interval matching always reconstructs.
3. **Fixed decode.**  96-byte records: seq, wall-clock ts, tid, event
   code, (iteration, worker) — the postmortem join key — two i64 args and
   a 48-byte note (room for a full host:port).  The decoder needs only
   the header; unknown event codes stay decodable as ``ev<code>``.

Crash markers: a clean exit (atexit, or a chained SIGTERM handler) stamps
``clean=1`` in the header and records ``proc.exit``; a ring whose header
still says ``clean=0`` belonged to a process that DIED (kill -9, SIGSEGV,
OOM) — ``pst-trace`` flags it and its last records are the final evidence.
``faulthandler`` is armed at a ``crash-<pid>.txt`` sidecar in the same
directory, so fatal-signal tracebacks (SIGSEGV/SIGABRT/SIGBUS) survive
alongside the ring.

Env knobs: ``PSDT_FLIGHT_DIR`` (enables recording; the ring directory),
``PSDT_FLIGHT_RECORDS`` (ring capacity in records, default 65536 — 6 MB),
``PSDT_FLIGHT_SAMPLE`` (sample 1-in-N for the per-chunk fold records,
default 1 = everything).
"""

from __future__ import annotations

import atexit
import faulthandler
import itertools
import mmap
import os
import signal
import struct
import threading
import time
import uuid
from typing import Any

from ..analysis.lock_order import checked_lock

MAGIC = b"PSTFLT01"
HEADER_BYTES = 4096
RECORD_BYTES = 96
# header: magic, record_size, capacity, pid, start wall-clock, clean flag,
# reserved, role label
_HEADER_FMT = "<8sIIqdII64s"
# record: seq, ts, tid, code, flags, iteration, worker, a, b, note.
# The 48-byte note holds a full host:port address — the postmortem's
# PROMOTION/RETRY lines must name real endpoints, not truncated ones.
_RECORD_FMT = "<QdIHhiiqq48s"
assert struct.calcsize(_RECORD_FMT) == RECORD_BYTES
_NOTE_BYTES = 48

ENV_DIR = "PSDT_FLIGHT_DIR"
ENV_RECORDS = "PSDT_FLIGHT_RECORDS"
ENV_SAMPLE = "PSDT_FLIGHT_SAMPLE"
DEFAULT_RECORDS = 65536

# ---------------------------------------------------------------- event table
# One stable u16 code per structured event.  Append-only: codes are wire
# format for on-disk rings, so renumbering breaks old-ring decode.
EVENTS: dict[str, int] = {
    "proc.start": 1,
    "proc.exit": 2,
    "proc.sigterm": 3,
    # RPC edges, both ends (note = method name, truncated)
    "rpc.cli.start": 10,
    "rpc.cli.end": 11,       # a = duration_us, b = 1 ok / 0 error
    "rpc.srv.start": 12,
    "rpc.srv.end": 13,       # a = duration_us
    # worker step phases
    "step.start": 20,
    "step.end": 21,          # a = duration_us
    "fused.start": 22,
    "fused.end": 23,         # a = duration_us, b = 1 ok / 0 degraded
    "boot.seed": 24,         # worker seeded an empty store
    # PS barrier phase transitions (core/ps_core.py)
    "fold.reserve": 30,      # sampled; a = tensors in the chunk
    "push.commit": 31,       # a = contributors after, b = barrier width
    "barrier.seal": 32,      # a = contributors at seal
    "barrier.drain": 33,     # a = in-flight folds drained
    "apply.start": 34,
    "apply.end": 35,         # a = duration_us
    "barrier.publish": 36,   # a = contributors, b = barrier width
    "barrier.retry": 37,     # a failed close left the barrier retryable
    # replication / failover / resharding (replication/)
    "repl.ship.start": 40,   # a = bytes, b = params_version
    "repl.ship.end": 41,     # a = duration_us, b = params_version
    "repl.ack": 42,          # a = 1 ok / 0 refused, b = params_version
    "repl.install": 43,      # a = bytes, b = params_version
    "repl.refuse": 44,       # note = reason
    "repl.degrade": 45,      # replication permanently degraded
    "failover.report": 50,   # a = shard index; note = dead address
    "failover.promote": 51,  # a = shard index, b = new epoch; note = new
    "failover.retry": 52,    # a = shard index; note = replacement address
    "reshard.fence": 53,     # a = tensors retired, b = map epoch
    "reshard.install": 54,   # a = bytes, b = epoch
    "reshard.epoch": 55,     # a = new epoch, b = shard count
    # shm transport (rpc/shm_transport.py)
    "shm.negotiate": 60,     # a = connection index, b = ring bytes
    "shm.refuse": 61,        # note = reason
    "shm.attach": 62,        # client side; b = ring bytes
    "shm.downgrade": 63,     # note = reason
    "shm.reap": 64,          # a = connection index
    "shm.reap.dup": 65,      # second release attempt (latch hit)
    # codec selection (rpc/codec.py)
    "codec.select": 70,      # a = 1 native / 0 python
    "ckpt.restore": 71,
    # hierarchical aggregation (tiers/, ISSUE 9)
    "tier.elect": 80,        # a = group size, b = epoch (coordinator) or
                             # aggregate id (worker edge); note = leaf addr
    "tier.fold": 81,         # leaf edge, sampled: member push arriving;
                             # a = tensors, b = aggregate id
    "tier.seal": 82,         # leaf group sealed; a = contributors,
                             # b = group size (worker = aggregate id)
    "tier.upstream": 83,     # a = duration_us, b = quantized wire bytes
    "tier.downgrade": 84,    # permanent flat downgrade; note = reason
    # versioned delta serving + live weight publication (delta/, ISSUE 10)
    "serve.delta.build": 90,     # a = pair delta bytes, b = to_version
    "serve.delta.hit": 91,       # a = chain wire bytes, b = pairs served
    "serve.delta.miss": 92,      # a = held version, b = current version;
                                 # note = reason (no base / depth/reset /
                                 # dtype / disabled)
    "serve.delta.downgrade": 93,  # client-side permanent downgrade;
                                  # note = reason (checksum/UNIMPLEMENTED)
    "publish.subscribe": 94,     # a = held version, b = subscriber id
    "publish.swap": 95,          # a = new version, b = duration_us
    "publish.lag": 96,           # a = versions behind the training run
    # accelerator-resident sharded apply (core/device_apply.py, ISSUE 11)
    "apply.device": 100,          # device-resident barrier apply swapped
                                  # in; a = duration_us, b = stripes
    "apply.readback": 102,        # async D2H readback of the fresh store
                                  # started; a = tensors
    # elastic membership + quorum barriers (elastic/, ISSUE 13)
    "elastic.join": 110,          # member ACTIVE; a = membership epoch
    "elastic.drain": 111,         # DRAINING (ctl/SIGTERM) or graceful
                                  # leave; a = epoch; note = reason
    "elastic.evict": 112,         # coordinator reap marked GONE;
                                  # a = epoch
    "quorum.seal": 113,           # barrier closed at K of N; a =
                                  # contributors, b = width; note =
                                  # contributor ids (comma list)
    "stale.fold": 114,            # straggler folded forward into
                                  # `iteration`; a = staleness,
                                  # b = tensors folded
    # decode fleet control plane (fleet/, ISSUE 14)
    "fleet.register": 120,        # decode server ACTIVE; a = slots,
                                  # b = fleet epoch; note = address
    "fleet.drain": 121,           # server DRAINING (scale-in / ctl);
                                  # a = fleet epoch
    "fleet.evict": 122,           # coordinator reap marked GONE;
                                  # a = fleet epoch
    "fleet.route": 123,           # router pinned a stream; a = request
                                  # id, b = server id; note = address
    "fleet.scale": 124,           # scale decision/target; a = target,
                                  # b = fleet epoch (coordinator) or
                                  # current size (autoscaler edge)
    "fleet.rollout": 125,         # rolling update step; a = version,
                                  # b = server id; note = phase
    "fleet.swap": 126,            # decode server swapped its serving
                                  # version; a = version, b = server id
    # flat arena apply (core/arena.py, ISSUE 15)
    "apply.arena.pack": 130,      # packing table built / param slabs
                                  # packed; a = duration_us, b = stripes
    "apply.arena.repack": 131,    # table REBUILT on a store-shape
                                  # change (epoch bump); a = duration_us
    "apply.arena.fallback": 132,  # a close downgraded to the per-tensor
                                  # path; note = reason (coverage /
                                  # counts / epoch / slots / latched)
    "apply.arena": 133,           # flat close published; a =
                                  # dispatch_us, b = readback_us
    # free-running barrier-free training (freerun/, ISSUE 16)
    "freerun.apply": 140,         # apply-on-arrival landed; a =
                                  # staleness, b = damp scale in ppm
    "freerun.dup": 141,           # version-vector dedup dropped an RPC
                                  # replay; a = last applied worker step
    "freerun.publish": 142,       # coalesced publication; a = published
                                  # version, b = applies coalesced
    "damp.floor": 143,            # a contribution damped below
                                  # PSDT_DAMP_FLOOR (effectively
                                  # dropped); a = staleness, b = scale
                                  # in ppb
    # cross-replica sharded update (replication/sharded_update.py)
    "shard.install": 150,         # partition shard installed into the
                                  # store; a = bytes, b = params_version
    "shard.update.degrade": 151,  # sharded close degraded to the
                                  # replicated path; note = reason
    "apply.sharded": 152,         # sharded close published; a =
                                  # replica count, b = wire bytes;
                                  # note = duration
    # radix-tree prefix cache (models/prefix_tree.py, ISSUE 20)
    "serve.prefix.hit": 160,      # suffix-only admission; a = prefix
                                  # tokens reused, b = suffix tokens
                                  # forwarded
    "serve.prefix.evict": 161,    # byte-budget LRU pass; a = nodes
                                  # evicted, b = bytes pinned after
    "serve.prefix.split": 162,    # edge split at a divergence point;
                                  # a = split-node depth, b = tree nodes
    # a serving leg over obs/legs.py's limit (ISSUE 38)
    "serve.slow_leg": 170,        # a = wall_us, b = the thread's cpu_us;
                                  # note = leg and evidence
                                  # (legs.slow_leg_note)
}
EVENT_NAMES = {code: name for name, code in EVENTS.items()}

# High-frequency classes that honor PSDT_FLIGHT_SAMPLE.  Only the
# per-chunk fold record qualifies: RPC start/end events are PAIRED
# (the postmortem matches them into intervals), and sampling the two
# halves independently would destroy the pairing — every RPC would
# decode as permanently open.
# tier.fold is the same per-member-push class at the leaf edge — one
# record per member stream, sampled alongside the per-chunk folds
SAMPLED = frozenset({EVENTS["fold.reserve"], EVENTS["tier.fold"]})


class FlightRecorder:
    """One process's ring.  Constructed open; every :meth:`record` claims
    a slot via a GIL-atomic counter and writes it lock-free (distinct
    slots, single writer each; the seq field is stored last so a record
    is valid only once fully written)."""

    def __init__(self, directory: str, role: str = "",
                 records: int | None = None, sample: int | None = None):
        self.directory = directory
        self.role = role or f"proc-{os.getpid()}"
        self.capacity = int(records if records is not None
                            else os.environ.get(ENV_RECORDS,
                                                str(DEFAULT_RECORDS)))
        if self.capacity < 16:
            self.capacity = 16
        self.sample = max(1, int(sample if sample is not None
                                 else os.environ.get(ENV_SAMPLE, "1")))
        os.makedirs(directory, exist_ok=True)
        # pid + uniquifier: a pid alone recycles under churn drives, and
        # a recycled pid must never O_TRUNC a DEAD process's ring — the
        # crash evidence this recorder exists to preserve
        self.path = os.path.join(
            directory,
            f"flight-{os.getpid()}-{uuid.uuid4().hex[:6]}.ring")
        size = HEADER_BYTES + self.capacity * RECORD_BYTES
        fd = os.open(self.path, os.O_CREAT | os.O_RDWR | os.O_TRUNC, 0o644)
        try:
            os.ftruncate(fd, size)
            self._mm = mmap.mmap(fd, size, mmap.MAP_SHARED,
                                 mmap.PROT_READ | mmap.PROT_WRITE)
        finally:
            os.close(fd)
        self.start_wall = time.time()
        struct.pack_into(_HEADER_FMT, self._mm, 0, MAGIC, RECORD_BYTES,
                         self.capacity, os.getpid(), self.start_wall, 0, 0,
                         self.role.encode("utf-8", "replace")[:64])
        self._next = itertools.count()
        self._sample_next = itertools.count()
        self._closed = False
        self.record_event("proc.start", note=self.role[:16])

    # ------------------------------------------------------------- hot path
    def record_event(self, name_or_code: str | int, iteration: int = -1,
                     worker: int = -1, a: int = 0, b: int = 0,
                     note: str | bytes = b"") -> None:
        code = (name_or_code if isinstance(name_or_code, int)
                else EVENTS[name_or_code])
        if self.sample > 1 and code in SAMPLED \
                and next(self._sample_next) % self.sample:
            return
        if self._closed:
            return
        seq = next(self._next) + 1  # seq 0 = empty slot
        off = HEADER_BYTES + ((seq - 1) % self.capacity) * RECORD_BYTES
        if isinstance(note, str):
            note = note.encode("utf-8", "replace")
        rec = struct.pack(_RECORD_FMT, seq, time.time(),
                          threading.get_ident() & 0xFFFFFFFF, code, 0,
                          int(iteration), int(worker),
                          int(a), int(b), note[:_NOTE_BYTES])
        try:
            # seq zeroed FIRST, payload second, seq (bytes 0..8) LAST: a
            # write torn by a crash leaves a slot whose seq does not
            # match — invalid, never a plausible-but-wrong record.  The
            # zeroing matters once the ring has wrapped: without it the
            # slot's STALE seq (which maps to this same slot) would
            # validate a half-overwritten payload as an old record.
            self._mm[off:off + 8] = b"\x00" * 8
            self._mm[off + 8:off + RECORD_BYTES] = rec[8:]
            self._mm[off:off + 8] = rec[:8]
        except (ValueError, IndexError):  # ring closed under us (teardown)
            pass

    # ------------------------------------------------------------ lifecycle
    def mark_clean(self) -> None:
        """Stamp the clean-shutdown flag (header offset of the ``clean``
        u32: after magic+2*u32+q+d = 8+4+4+8+8 = 32)."""
        try:
            struct.pack_into("<I", self._mm, 32, 1)
        except ValueError:
            pass

    def set_role(self, role: str) -> None:
        self.role = role
        try:
            struct.pack_into("<64s", self._mm, 40,
                             role.encode("utf-8", "replace")[:64])
        except ValueError:
            pass

    def close(self, clean: bool = True) -> None:
        if self._closed:
            return
        self.record_event("proc.exit")
        if clean:
            self.mark_clean()
        self._closed = True
        try:
            self._mm.flush()
            self._mm.close()
        except (ValueError, OSError):
            pass


# --------------------------------------------------------------- module state
_rec: FlightRecorder | None = None
# serializes enable/disable/atexit (the file I/O under it is the lock's
# purpose — BLOCKING_ALLOWED in analysis/lock_order.py); never taken on
# the record() hot path
_lock = checked_lock("FlightRecorder._lock")
_signal_armed = False
_atexit_armed = False
_crash_file = None  # the faulthandler sidecar fd (one at a time)


def recorder() -> FlightRecorder | None:
    return _rec


def enabled() -> bool:
    return _rec is not None


def record(name_or_code: str | int, iteration: int = -1, worker: int = -1,
           a: int = 0, b: int = 0, note: str | bytes = b"") -> None:
    """Record one structured event into the process ring; no-op (one
    truthiness check) when the recorder is off."""
    rec = _rec
    if rec is None:
        return
    rec.record_event(name_or_code, iteration=iteration, worker=worker,
                     a=a, b=b, note=note)


def set_role(role: str) -> None:
    """Label this process's ring (e.g. ``ps:127.0.0.1:50051``,
    ``worker:0``, ``coordinator``) for the postmortem process listing."""
    with _lock:
        if _rec is not None:
            _rec.set_role(role)


def _at_exit() -> None:
    with _lock:
        if _rec is not None:
            _rec.close(clean=True)


def _arm_crash_handlers(directory: str) -> None:
    """faulthandler sidecar for fatal signals + a chained SIGTERM handler
    (servers normally die by SIGTERM, which skips atexit — without this
    their rings would read as crashes)."""
    global _signal_armed, _crash_file
    try:
        crash_path = os.path.join(directory,
                                  f"crash-{os.getpid()}.txt")
        # the fd stays open while armed — faulthandler needs a live fd at
        # signal time, and a 0-byte sidecar is the "no fatal signal"
        # marker pst-trace can skip.  Append mode: a recycled pid must
        # not truncate a dead predecessor's traceback.  One sidecar fd at
        # a time: re-arming (enable() into a new directory, bench arm
        # toggles) closes the previous one instead of leaking it.
        fh = open(crash_path, "a")
        faulthandler.enable(fh, all_threads=True)
        if _crash_file is not None:
            try:
                _crash_file.close()
            except OSError:
                pass
        _crash_file = fh
    except (OSError, ValueError, RuntimeError):
        pass
    if _signal_armed:
        return

    def _on_sigterm(signum, frame):
        rec = _rec
        if rec is not None:
            rec.record_event("proc.sigterm")
            rec.mark_clean()
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    try:
        if signal.getsignal(signal.SIGTERM) is signal.SIG_DFL:
            signal.signal(signal.SIGTERM, _on_sigterm)
            _signal_armed = True
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass


def enable(directory: str | None = None, role: str = "",
           records: int | None = None,
           sample: int | None = None) -> FlightRecorder:
    """Open (or replace) this process's ring under ``directory`` (default
    ``PSDT_FLIGHT_DIR``) and arm the crash handlers.  Idempotent per
    directory: re-enabling in the same directory keeps the open ring."""
    global _rec, _atexit_armed
    directory = directory or os.environ.get(ENV_DIR, "")
    if not directory:
        raise ValueError("flight.enable needs a directory "
                         f"(or {ENV_DIR} set)")
    with _lock:
        if _rec is not None and _rec.directory == directory:
            if role:
                _rec.set_role(role)
            return _rec
        if _rec is not None:
            _rec.close(clean=True)
        _rec = FlightRecorder(directory, role=role, records=records,
                              sample=sample)
    _arm_crash_handlers(directory)
    if not _atexit_armed:
        atexit.register(_at_exit)
        _atexit_armed = True
    return _rec


def disable() -> None:
    """Close the ring (clean).  Test hygiene; production rings stay open
    for the process lifetime."""
    global _rec
    with _lock:
        if _rec is not None:
            _rec.close(clean=True)
            _rec = None


def suppress_for_tool() -> None:
    """Analysis/status CLIs (pst-trace, pst-status, pst-analyze) call
    this first: when ``PSDT_FLIGHT_DIR`` is still exported from the shell
    that drove the cluster, the import-time auto-enable opened a ring for
    the TOOL process inside the very directory under analysis — which
    would then list the tool itself as a (possibly dead) cluster process.
    Closes the recorder, deletes its ring, and removes its crash sidecar
    while still empty."""
    global _rec
    with _lock:
        rec, _rec = _rec, None
    if rec is None:
        return
    rec.close(clean=True)
    try:
        os.unlink(rec.path)
    except OSError:
        pass
    crash = os.path.join(rec.directory, f"crash-{os.getpid()}.txt")
    try:
        if os.path.getsize(crash) == 0:
            os.unlink(crash)
    except OSError:
        pass


# ------------------------------------------------------------------- decoding
def decode_ring(path: str) -> dict[str, Any]:
    """Decode one on-disk ring (live or from a dead process) into
    ``{path, pid, role, start, clean, capacity, events}`` with events
    oldest-first.  Torn/empty slots are skipped; a seq that does not map
    to its slot (wraparound remnants, torn writes) is invalid."""
    with open(path, "rb") as fh:
        blob = fh.read()
    if len(blob) < HEADER_BYTES:
        raise ValueError(f"{path}: truncated flight ring")
    (magic, record_size, capacity, pid, start_wall, clean, _res,
     role_raw) = struct.unpack_from(_HEADER_FMT, blob, 0)
    if magic != MAGIC:
        raise ValueError(f"{path}: not a flight ring (magic {magic!r})")
    if record_size != RECORD_BYTES:
        raise ValueError(f"{path}: record size {record_size} unsupported")
    events: list[dict] = []
    n_slots = min(capacity, (len(blob) - HEADER_BYTES) // RECORD_BYTES)
    for slot in range(n_slots):
        off = HEADER_BYTES + slot * RECORD_BYTES
        (seq, ts, tid, code, _flags, iteration, worker, a, b,
         note) = struct.unpack_from(_RECORD_FMT, blob, off)
        if seq == 0 or (seq - 1) % capacity != slot:
            continue
        events.append({
            "seq": seq, "ts": ts, "tid": tid, "code": code,
            "event": EVENT_NAMES.get(code, f"ev{code}"),
            "iteration": iteration, "worker": worker, "a": a, "b": b,
            "note": note.rstrip(b"\x00").decode("utf-8", "replace"),
        })
    events.sort(key=lambda e: e["seq"])
    dropped = 0
    if events and events[0]["seq"] > 1:
        # the ring wrapped: seq numbering tells exactly how much history
        # was overwritten
        dropped = events[0]["seq"] - 1
    return {"path": path, "pid": pid,
            "role": role_raw.rstrip(b"\x00").decode("utf-8", "replace"),
            "start": start_wall, "clean": bool(clean),
            "capacity": capacity, "dropped": dropped, "events": events}


# Env wiring: PSDT_FLIGHT_DIR turns the recorder on for the process
# lifetime — the zero-code path for real cluster runs and chaos drives.
if os.environ.get(ENV_DIR, ""):
    enable()
