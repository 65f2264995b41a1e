"""Live weight subscription for the decode fleet (ISSUE 10).

``WeightFollower`` opens the ``SubscribeWeights`` extension RPC against
a training PS and tracks its store version by version: the server
streams a full serve first (establishing the base), then one delta pair
batch per optimizer apply — the same encode-once frames the worker
fan-out replays.  Each completed version is published to the consumer
(``poll()``), which hot-swaps it into a running DecodeServer between
decode rounds (models/serving.py ``swap_params``, cli/serve_main.py
``--follow``).

Downgrade discipline (the decode process must NEVER crash or stall on
the training side's health):

- UNIMPLEMENTED (reference PS / delta disabled) => permanent downgrade,
  the follower stops and the server keeps serving its boot weights;
- transport errors (PS death, partition) => bounded reconnect with
  backoff, then degraded — the server keeps serving the LAST GOOD
  weights it swapped in;
- checksum/base mismatch => the base is dropped and the subscription
  reopens from scratch (held_version 0 => full re-serve).
"""

from __future__ import annotations

import logging
import random
import threading
import time

import grpc
import numpy as np

from ..analysis.lock_order import checked_lock
from ..obs import flight
from ..rpc import messages as m
from ..rpc.service import RpcClient
from ..rpc.service import status_code as _status_code
from .client import DeltaBaseMismatch, DeltaPullState, apply_frames
from .messages import DELTA_PS_METHODS, SubscribeRequest

log = logging.getLogger("pst.delta.follow")


class WeightFollower:
    """Background subscriber thread + a one-slot mailbox of the newest
    complete weight version.  ``poll()`` is called by the serving loop
    between admissions; it returns ``(params copy, version)`` at most
    once per version (None when nothing new).  The copy matters: the
    follower keeps patching its own base in place, so the consumer gets
    arrays the next delta can never mutate under a running decode."""

    def __init__(self, target: str, subscriber_id: int = 0,
                 wire_dtype: int = m.WIRE_BF16,
                 reconnect_attempts: int = 5,
                 reconnect_backoff_s: float = 0.5):
        self.target = target
        self.subscriber_id = int(subscriber_id)
        self.wire_dtype = int(wire_dtype)
        self._attempts = int(reconnect_attempts)
        self._backoff = float(reconnect_backoff_s)
        # Decorrelated-jitter reconnect backoff (ISSUE 14 satellite): a
        # FLEET of followers losing one restarted PS must not thundering-
        # herd it back down — the old deterministic base*2^n schedule
        # made every follower retry in the same instant.  Each sleep
        # draws uniform in [base, min(cap, 3*previous sleep)] (cap =
        # base*8, the old schedule's ceiling), seeded per subscriber id
        # so a fleet decorrelates AND a given follower is reproducible.
        self._backoff_cap = self._backoff * 8.0
        self._prev_backoff = self._backoff
        self._jitter_rng = random.Random(0x9E3779B9 ^ self.subscriber_id)
        self._state = DeltaPullState()
        # one-slot mailbox (pending newest version) + status flags
        self._lock = checked_lock("WeightFollower._lock")
        self._cv = threading.Condition(self._lock)
        self._pending: tuple[dict, int] | None = None
        self.degraded = False
        self.degrade_reason = ""
        self.versions_received = 0
        self._stop = threading.Event()
        self._client: RpcClient | None = None
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"weight-follower-{subscriber_id}")

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "WeightFollower":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        client, self._client = self._client, None
        if client is not None:
            # closing the channel aborts the blocked response iterator
            client.close()
        self._thread.join(timeout=5.0)

    # ------------------------------------------------------------- consume
    def poll(self) -> tuple[dict, int] | None:
        """The newest complete (params, version) not yet consumed, or
        None.  Non-blocking; intermediate versions the consumer was too
        slow for are coalesced away (last-writer-wins mailbox)."""
        with self._lock:
            pending, self._pending = self._pending, None
            return pending

    def wait_for_update(self, timeout: float | None = None
                        ) -> tuple[dict, int] | None:
        """Block until a not-yet-consumed version lands, then consume it
        (poll()'s contract otherwise).  Returns None on timeout — or
        immediately on stop()/degrade, so a waiter never sleeps out its
        timeout against a follower that can no longer deliver."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cv:
            while (self._pending is None and not self.degraded
                   and not self._stop.is_set()):
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    break
                self._cv.wait(remaining)
            pending, self._pending = self._pending, None
            return pending

    @property
    def version(self) -> int:
        """Version of the newest weights RECEIVED (not yet necessarily
        consumed)."""
        with self._lock:
            return self._state.version

    def _next_backoff(self) -> float:
        """One decorrelated-jitter draw (see the constructor comment):
        uniform in [base, min(cap, 3 * previous sleep)], remembered as
        the next draw's upper-bound seed.  Bounds are the unit-test
        contract: every sleep is >= base and <= cap."""
        hi = max(self._backoff, min(self._backoff_cap,
                                    self._prev_backoff * 3.0))
        sleep = self._jitter_rng.uniform(self._backoff, hi)
        self._prev_backoff = sleep
        return sleep

    # -------------------------------------------------------------- thread
    def _publish(self) -> None:
        store = {name: np.array(arr, np.float32, copy=True)
                 for name, arr in self._state.base.items()}
        with self._cv:
            self._pending = (store, self._state.version)
            self.versions_received += 1
            self._cv.notify_all()

    def _degrade(self, reason: str) -> None:
        with self._cv:
            self.degraded = True
            self.degrade_reason = reason
            self._cv.notify_all()
        flight.record("serve.delta.downgrade", note=reason[:48])
        log.warning("weight follower degraded (%s): decode keeps serving "
                    "last-good weights (version %d)", reason,
                    self._state.version)

    def _run(self) -> None:
        failures = 0
        while not self._stop.is_set():
            try:
                client = RpcClient(self.target, m.PARAMETER_SERVER_SERVICE,
                                   DELTA_PS_METHODS)
                self._client = client
                held = self._state.version
                flight.record("publish.subscribe", a=max(held, 0),
                              b=self.subscriber_id)
                frames = client.call(
                    "SubscribeWeights",
                    SubscribeRequest(subscriber_id=self.subscriber_id,
                                     held_version=max(held, 0),
                                     wire_dtype=self.wire_dtype),
                    timeout=None)
                for batch in _version_batches(frames):
                    if self._stop.is_set():
                        return
                    apply_frames(iter(batch), self._state)
                    if self._state.base is not None:
                        self._publish()
                        failures = 0
                        self._prev_backoff = self._backoff  # healthy again
                if self._stop.is_set():
                    return
                failures += 1  # server ended the stream (PS shutdown)
            except DeltaBaseMismatch as exc:
                # base poisoned: drop it and resubscribe from scratch —
                # the next session opens with held_version 0 (full serve)
                log.warning("weight follower base mismatch (%s); "
                            "resubscribing full", exc)
                self._state.invalidate()
                failures += 1
            except grpc.RpcError as exc:
                if self._stop.is_set():
                    return
                if _status_code(exc) == grpc.StatusCode.UNIMPLEMENTED:
                    self._degrade("SubscribeWeights UNIMPLEMENTED "
                                  "(reference PS / delta disabled)")
                    return
                failures += 1
            except Exception as exc:  # noqa: BLE001 — never-crash
                # contract: an unexpected error (malformed frame bytes,
                # a decode bug) must DEGRADE — visible to waiters and
                # the serve loop — not kill this thread silently with
                # degraded still False
                log.exception("weight follower error")
                self._degrade(f"subscription error: {exc}")
                return
            finally:
                client, self._client = self._client, None
                if client is not None:
                    client.close()
            if failures > self._attempts:
                self._degrade(f"subscription lost after {failures} attempts")
                return
            if self._stop.wait(self._next_backoff()):
                return


def _version_batches(frames):
    """Group a SubscribeWeights frame stream into per-version batches:
    the apply_frames assembler consumes one complete serve (full or one
    delta pair) per call, so the follower can publish after EVERY
    version instead of only at stream end."""
    batch = []
    for frame in frames:
        batch.append(frame)
        if frame.last:
            yield batch
            batch = []
    if batch:
        yield batch
