"""Decode-fleet extension RPC messages (ISSUE 14).

Deliberately NOT in ``rpc/messages.py``: the analyzer's wire manifest
pins the reference contract and the fleet subsystem must leave it
byte-unchanged (asserted in tests/test_analysis.py).  Two surfaces:

- **``UpdateFleet``** — an extra method name on the existing coordinator
  gRPC service, the serving twin of elastic/'s ``UpdateMembership``: one
  RPC registers a DecodeServer, refreshes its load heartbeat (free
  slots, queue depth, weight version), announces a graceful leave,
  requests a drain, sets the manual scale target, and queries the
  epoch-numbered fleet table.  A reference coordinator answers
  UNIMPLEMENTED => the decode process keeps serving standalone (the
  PR-2/PR-13 permanent-downgrade discipline).
- **the decode service** (``psdt_fleet.Decode``) — a NEW gRPC service
  name (no reference collision possible): ``SubmitStream`` carries one
  request in and streams its tokens back (each chunk stamped with the
  weight version that decoded it — the version-skew evidence the router
  tests pin), and ``Control`` is the fleet-management side door (status
  probe, rolling weight swap, rollback-to-pinned-version, drain).  The
  router speaks ``SubmitStream`` on BOTH faces, so a client cannot tell
  a router from a single server.

Fleet member states reuse the elastic membership constants
(JOINING/ACTIVE/DRAINING/GONE — :mod:`..elastic.messages`): scale-in IS
the PR 13 drain-before-stop path, applied to serving processes.
"""

from __future__ import annotations

from ..elastic.messages import (MEMBER_ACTIVE, MEMBER_DRAINING,  # noqa: F401
                                MEMBER_GONE, MEMBER_JOINING, STATE_NAMES)
from ..rpc.messages import TRACE_FIELD_NUMBER
from ..rpc.wire import Field, Message

# UpdateFleet actions.  Append-only: values ride the wire.
FLEET_QUERY = 0      # pure read (router poll, pst-ctl fleet)
FLEET_REGISTER = 1   # decode server announces itself (JOINING -> ACTIVE)
FLEET_HEARTBEAT = 2  # load refresh: free slots / queue depth / version
FLEET_LEAVE = 3      # graceful leave (drain completed / shutdown)
FLEET_DRAIN = 4      # mark target_server_id DRAINING (scale-in, pst-ctl)
FLEET_SCALE = 5      # set the manual scale target (0 = autoscale)

# Control actions on the decode service.
CTRL_STATUS = 0      # status probe (no side effect)
CTRL_SWAP = 1        # swap to held version `version` (-1 = newest held)
CTRL_ROLLBACK = 2    # swap BACK to `version` and pin there: no newer
                     # version may serve a continuation until CTRL_UNPIN
CTRL_UNPIN = 3       # clear the rollback pin (auto/rolling swaps resume)
CTRL_DRAIN = 4       # stop admitting, finish in-flight streams, leave


class FleetEntry(Message):
    """One decode server's fleet row: identity, capacity, the load
    signals the router scores on, and the weight version it serves.
    ``prefix_fp`` is the server's radix prefix-cache fingerprint
    (packed chained-CRC32 block hashes — models/prefix_tree.py); empty
    from servers without a prefix cache (or older builds), in which
    case the router's overlap term is zero and scoring degrades to the
    PR 14 free-slot/queue-depth order."""
    FIELDS = (
        Field(1, "server_id", "int32"),
        Field(2, "address", "string"),
        Field(3, "slots", "int32"),
        Field(4, "free_slots", "int32"),
        Field(5, "queue_depth", "int32"),
        Field(6, "weight_version", "int32"),
        Field(7, "state", "int32"),
        Field(8, "epoch", "int32"),
        Field(9, "active_streams", "int32"),
        Field(10, "prefix_fp", "bytes"),
    )


class FleetRequest(Message):
    """Register-heartbeat-query in one RPC (see module docstring).
    ``target_server_id`` is read only for ``FLEET_DRAIN``;
    ``scale_target`` only for ``FLEET_SCALE``."""
    FIELDS = (
        Field(1, "server_id", "int32"),
        Field(2, "action", "int32"),
        Field(3, "address", "string"),
        Field(4, "slots", "int32"),
        Field(5, "free_slots", "int32"),
        Field(6, "queue_depth", "int32"),
        Field(7, "weight_version", "int32"),
        Field(8, "active_streams", "int32"),
        Field(9, "target_server_id", "int32"),
        Field(10, "scale_target", "int32"),
        Field(11, "prefix_fp", "bytes"),
        Field(TRACE_FIELD_NUMBER, "trace_context", "bytes"),
    )


class FleetResponse(Message):
    """``self_state`` answers the requesting server directly (the
    heartbeat-cadence drain poll needs only this field; -1 = unknown);
    ``scale_target`` echoes the manual target (0 = autoscale)."""
    FIELDS = (
        Field(1, "epoch", "int32"),
        Field(2, "success", "bool"),
        Field(3, "message", "string"),
        Field(4, "self_state", "int32"),
        Field(5, "entries", "message", message_type=FleetEntry,
              repeated=True),
        Field(6, "scale_target", "int32"),
    )


# --------------------------------------------------------- decode service
class DecodeRequest(Message):
    """One stream admission: the prompt as token ids, generation budget,
    and per-request sampling overrides (temperature < 0 = server
    default, matching DecodeServer.submit(temperature=None))."""
    FIELDS = (
        Field(1, "tokens", "int32", repeated=True),
        Field(2, "max_new", "int32"),
        Field(3, "temperature", "float"),
        Field(4, "stop", "int32", repeated=True),
        Field(TRACE_FIELD_NUMBER, "trace_context", "bytes"),
    )


class DecodeChunk(Message):
    """One streamed token (or the terminal chunk).  ``weight_version``
    stamps the params version that decoded THIS token — the router
    version-skew tests read it to prove a pinned rollback never serves
    a newer-version continuation.  ``error`` non-empty = the request
    failed (bad prompt, draining server); ``done`` closes the stream."""
    FIELDS = (
        Field(1, "request_id", "int32"),
        Field(2, "token", "int32"),
        Field(3, "done", "bool"),
        Field(4, "error", "string"),
        Field(5, "weight_version", "int32"),
    )


class DecodeControlRequest(Message):
    FIELDS = (
        Field(1, "action", "int32"),
        Field(2, "version", "int32"),
        Field(TRACE_FIELD_NUMBER, "trace_context", "bytes"),
    )


class DecodeControlResponse(Message):
    """The per-server status the controller and router poll: capacity,
    load, the serving version, held versions, and the rollback pin
    (-1 = unpinned)."""
    FIELDS = (
        Field(1, "success", "bool"),
        Field(2, "message", "string"),
        Field(3, "server_id", "int32"),
        Field(4, "state", "int32"),
        Field(5, "slots", "int32"),
        Field(6, "free_slots", "int32"),
        Field(7, "queue_depth", "int32"),
        Field(8, "weight_version", "int32"),
        Field(9, "pinned_version", "int32"),
        Field(10, "versions_held", "int32", repeated=True),
        Field(11, "streams_served", "int32"),
        # prompt-phase reuse accounting (ISSUE 20): tokens the prompt
        # phase actually forwarded vs prompt tokens admitted — the
        # fleet's prefill-computed/prompt ratio numerator and
        # denominator (0/0 from pre-radix builds)
        Field(12, "prefill_tokens", "int64"),
        Field(13, "prompt_tokens", "int64"),
    )


# Extra method on the existing coordinator service (extension — absent
# from the reference's method table and the pinned wire manifest).
FLEET_COORD_METHODS = {
    "UpdateFleet": (FleetRequest, FleetResponse),
}

# A NEW service name: the decode plane never shares a wire surface with
# the reference protocol.
DECODE_SERVICE = "psdt_fleet.Decode"
DECODE_METHODS = {
    "SubmitStream": (DecodeRequest, DecodeChunk, "unary_stream"),
    "Control": (DecodeControlRequest, DecodeControlResponse),
}
