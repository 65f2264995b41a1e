"""Cluster-wide observability subsystem.

Three layers, one package (the reference's observability was bare stdout
prints — SURVEY.md §5):

- :mod:`~parameter_server_distributed_tpu.obs.trace` — trace/span IDs with
  a thread-local current-span stack, propagated across processes via a
  high-numbered extension field on the RPC request messages (reference
  protoc gencode skips unknown fields, so C++ peers are unaffected —
  tests/test_wire_interop.py), exported as Chrome-trace (catapult) JSON so
  one distributed training step renders in ``chrome://tracing``/Perfetto;
- :mod:`~parameter_server_distributed_tpu.obs.stats` — cheap log-bucket
  histograms, counters, and gauges behind a process-wide registry; every
  RPC endpoint, step phase, and serving loop reports here;
- :mod:`~parameter_server_distributed_tpu.obs.export` — workers piggyback
  registry snapshots on heartbeats, the coordinator aggregates them
  per-worker, and ``pst-status --metrics`` prints the cluster rollup;
- :mod:`~parameter_server_distributed_tpu.obs.flight` — the
  crash-surviving flight recorder: an always-on mmap-backed event ring
  per process under ``PSDT_FLIGHT_DIR``, decodable after ``kill -9``;
- :mod:`~parameter_server_distributed_tpu.obs.postmortem` — merges the
  rings of all processes (dead ones included) into cross-process
  iteration postmortems with critical-path/straggler attribution; the
  ``pst-trace`` CLI renders them.

StepTimer, MetricsLogger, profile_trace and samples_per_sec (step timers,
JSONL metrics, profiler hook) live in ``obs/stats.py`` and are exported
here.
"""

from . import export, flight, postmortem, stats, trace
from .stats import (MetricsLogger, StepTimer, profile_trace,
                    samples_per_sec)

__all__ = ["trace", "stats", "export", "flight", "postmortem",
           "StepTimer", "MetricsLogger", "profile_trace",
           "samples_per_sec"]
