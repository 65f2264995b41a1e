"""Worker process entry point.

Argv contract mirrors the reference (reference: src/worker_main.cpp:6-18):

    python -m parameter_server_distributed_tpu.cli.worker_main \
        [coordinator_addr] [worker_id] [iterations] [worker_addr]
        [worker_port] [checkpoint_path] [flags...]

A non-empty checkpoint_path triggers a restore request at startup, tolerant
of failure (reference: src/worker_main.cpp:28-38).

Extension flags:
    --model=NAME     model from the registry (default mnist_mlp)
    --batch=N        per-worker batch size (default 32)
    --seed=N         data seed (defaults to worker_id so shards differ)
    --data=PATH      file-backed dataset (token .bin for LMs, npz x/y
                     otherwise); default synthetic
    --wire=ENC       tensor payload encoding: f32 (reference-compatible,
                     default), raw, bf16 (half the push/pull bytes),
                     int8 (quarter-size error-feedback gradient pushes,
                     bf16 pulls; requires a framework PS), or topk
                     (top-k sparsified pushes at --topk-density, unsent
                     mass carried by error feedback; bf16 pulls)
    --topk-density=F fraction of entries a topk push keeps (default 0.01)
    --dtype=bf16     model compute dtype (factories that take one)
    --remat / --no-remat / --scan-layers / --no-scan-layers
                     transformer LM layer-loop knobs (same semantics as
                     pst-train; absent = model default)
    --mesh=SPEC      intra-worker MODEL parallelism over the worker's
                     local chips (e.g. fsdp:2,data:2 or tensor:4): params
                     are sharding-constrained inside the jitted step, so
                     a model too big for one chip still speaks plain PS.
                     Default: pure local data parallelism over all chips
    --no-fused       disable the fused PushPullStream data plane (one RPC
                     round per step, docs/training.md) and run the
                     reference-shaped serial push/poll/pull protocol
    --tiers / --no-tiers
                     join (or refuse) the coordinator's two-tier
                     hierarchical-aggregation topology (tiers/): same-host
                     workers fold locally at an elected leaf aggregator,
                     one quantized contribution per group goes upstream.
                     Absent = PSDT_TIERS env (default off)
    --freerun        free-running barrier-free loop (freerun/,
                     docs/training.md "Free-running async training"):
                     push, pull whatever version the PS has published,
                     step again — never polls a barrier.  Pair with a
                     --freerun PS.  Absent = PSDT_FREERUN env
"""

from __future__ import annotations

import logging
import signal
import sys

from .. import freerun as freerun_mod
from ..config import WorkerConfig, parse_argv
from ..models.registry import get_model_and_batches
from ..utils.compile_cache import enable_compile_cache
from ..worker.trainer import Trainer
from ..worker.worker import Worker


def build_worker(config: WorkerConfig, seed: int | None = None) -> Worker:
    data_seed = config.worker_id if seed is None else seed
    model, batches = get_model_and_batches(config.model, config.batch_size,
                                           seed=data_seed,
                                           data_path=config.data_path,
                                           dtype=config.model_dtype,
                                           remat=config.remat,
                                           scan=config.scan_layers)
    mesh_config = rule_fn = None
    if config.mesh:
        from .train_main import parse_mesh
        from ..parallel.train_loop import _pick_rule

        mesh_config = parse_mesh(config.mesh)
        if mesh_config.pipeline > 1 or mesh_config.sequence > 1:
            # pipe needs the schedule machinery (pst-train); seq has no
            # param rule here — accepting it would leave chips silently
            # doing replicated work
            raise ValueError(
                "worker --mesh supports data/fsdp/tensor/expert axes; "
                "use pst-train for pipeline or sequence parallelism")
        rule_fn = lambda mesh: _pick_rule(config.model, mesh)  # noqa: E731
    return Worker(config, Trainer(model, mesh_config=mesh_config,
                                  rule_fn=rule_fn), batches)


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    positional, flags = parse_argv(argv)
    enable_compile_cache()
    config = WorkerConfig(
        coordinator_address=positional[0] if len(positional) > 0 else "127.0.0.1:50052",
        worker_id=int(positional[1]) if len(positional) > 1 else 0,
        iterations=int(positional[2]) if len(positional) > 2 else 10,
        address=positional[3] if len(positional) > 3 else "127.0.0.1",
        port=int(positional[4]) if len(positional) > 4 else 50060,
        checkpoint_path=positional[5] if len(positional) > 5 else "",
        model=flags.get("model", "mnist_mlp"),
        batch_size=int(flags.get("batch", 32)),
        model_dtype=flags.get("dtype", ""),
        remat=(False if "no-remat" in flags
               else True if "remat" in flags else None),
        scan_layers=(False if "no-scan-layers" in flags
                     else True if "scan-layers" in flags else None),
        data_path=flags.get("data", ""),
        wire_dtype=flags.get("wire", "f32"),
        # omit when unset so WorkerConfig's default governs (one owner)
        **({"topk_density": float(flags["topk-density"])}
           if "topk-density" in flags else {}),
        mesh=flags.get("mesh", ""),
        fused_step="no-fused" not in flags,
        tiers=(False if "no-tiers" in flags
               else True if "tiers" in flags else None),
        freerun="freerun" in flags or freerun_mod.enabled(),
    )
    worker = build_worker(config, seed=int(flags["seed"]) if "seed" in flags else None)
    worker.initialize()

    if config.checkpoint_path:
        # tolerant of failure, like the reference (src/worker_main.cpp:28-38)
        try:
            worker.load_checkpoint_from_server(config.checkpoint_path)
        except Exception as exc:  # noqa: BLE001
            logging.warning("checkpoint restore failed (continuing): %s", exc)

    # Graceful preemption (elastic/, ISSUE 13): the FIRST SIGTERM
    # latches a drain instead of killing the process mid-stream — the
    # in-flight iteration completes, the loop below stops, and
    # shutdown() deregisters so the barrier narrows at the next width
    # refresh.  A SECOND SIGTERM escalates: a worker wedged
    # mid-iteration (unreachable PS, barrier timeout) must still be
    # killable without resorting to kill -9.  (Replaces — does not
    # chain — any earlier handler: both exits run through the normal
    # path/atexit, which stamps the flight ring clean.)
    def _on_sigterm(_signum, _frame):
        if worker.drain_requested:
            logging.warning("worker %d: second SIGTERM — exiting now",
                            config.worker_id)
            raise SystemExit(143)
        logging.warning("worker %d: SIGTERM — draining after the "
                        "in-flight iteration", config.worker_id)
        worker.request_drain()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass  # not the main thread (embedded use)

    try:
        for i in range(config.iterations):
            if worker.drain_requested:
                print(f"Worker {config.worker_id} draining: deregistering "
                      f"after iteration {worker.iteration}", flush=True)
                break
            it = max(i, worker.iteration + 1)
            loss = worker.run_iteration(it)
            desc = "bootstrap: seeded PS init" if worker.last_bootstrap \
                else f"loss {loss:.4f}"
            print(f"Worker {config.worker_id} completed iteration {it} "
                  f"({desc})", flush=True)
    finally:
        worker.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
