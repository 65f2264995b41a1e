"""SPMD training CLI (pure-collectives mode, no PS process).

    python -m parameter_server_distributed_tpu.cli.train_main \
        --model=mnist_mlp --steps=100 --batch=64 --optimizer=adam --lr=1e-3 \
        --schedule=cosine --warmup=10 --clip-norm=1.0 --accum=2 \
        --data=/data/train.npz \
        --mesh=data:2,fsdp:2,tensor:2 --ckpt-dir=/tmp/ckpt --ckpt-every=50 \
        --ckpt-keep=3 --resume --metrics=/tmp/metrics.jsonl

``--attention=dense|ring|ulysses`` says how a transformer model's
attention uses the mesh's seq axis: dense = the sequence is not split,
ring / ulysses = sequence parallelism over the seq axis (pair with
--mesh=seq:N; K/V rotated around the ring, or an all-to-all that swaps
seq for heads).  Which implementation then runs on a device (the pallas
kernel, blockwise in plain XLA, the einsum) follows from the shapes the
device sees (models/transformer.device_arm); no flag chooses it.

``--dtype=bf16`` trains in bfloat16 (f32 MXU accumulation) for models
whose factory takes a dtype; ``--remat`` recomputes layer activations in
the backward pass (jax.checkpoint, transformer LMs) — the long-context
memory/FLOPs trade.  ``--no-remat`` forces it off for models that default
it on (lm_350m); neither flag keeps the model's default.
``--scan-layers`` / ``--no-scan-layers`` likewise force lax.scan over
stacked layer weights (depth-independent compile time) or the unrolled
loop (cross-layer XLA fusion) for transformer LMs.
``--remat-policy=full|dots`` picks what remat may keep (flagship LMs):
full recomputes the whole layer, dots saves the projection/MLP matmul
outputs and recomputes only the attention einsums (~5% extra FLOPs
instead of ~33%, for O(L·S·d) saved activations).  ``--lora=R[:ALPHA]``
switches to LoRA fine-tuning: rank-R adapters on the attention q/v
projections are the ONLY trainable parameters (base weights frozen, no
optimizer state allocated for them — models/lora.py; merge with
``models.lora.merge_lora`` for serving).  ``--seq=N``
overrides the LM sequence length (long-context runs; synthetic token
streams follow the model).

``--hf-gpt2=<checkout>`` / ``--hf-llama=<checkout>`` train the
CONVERTED transformers checkpoint instead of a registry preset
(models/hf.from_hf_gpt2 / from_hf_llama): the converted weights are the
initializer, ``--data`` feeds it (synthetic crops otherwise), and both
compose with ``--lora``, ``--ema``, and a ``pipe`` mesh axis — the
fine-tune flow for models the reference ecosystem ships (llama
conversions are the native arch, so every schedule applies).

``--mesh=pipe:P`` trains transformer models with pipeline parallelism
(parallel/pipeline.py): layer blocks live on their pipe rank,
microbatches stream through; ``--microbatches=M`` sets the schedule depth
(default P).  ``--pipeline-schedule=gpipe|1f1b`` picks the schedule:
gpipe (all forwards then all backwards via autodiff) or 1f1b (interleaved
one-forward-one-backward — O(P) instead of O(M) in-flight activations).
``--virtual-stages=V`` (with 1f1b) runs the Megatron INTERLEAVED
schedule: each rank holds V round-robin layer chunks, shrinking the
pipeline bubble ~V-fold at V x the ppermute count.  Requires n_layers
divisible by P*V; combine with data:N.  ``--attention`` stays dense
with a pipe axis (a stage attends through the einsum).

``--ema=0.999`` tracks a Polyak/EMA shadow of the parameters at that
decay inside the optimizer state (checkpointed and sharded like any
slot); with ``--eval-every`` the final summary reports
``ema_eval_loss`` next to the raw ``eval_loss``.

``--data`` switches from synthetic loaders to file-backed data
(data/files.py): a token shard (.bin/.u32 memmap) for LM models, an npz
with x/y arrays otherwise.  ``--eval-every=N`` runs a held-out
evaluation (mean loss over ``--eval-steps`` batches, no updates) every N
steps and at the end; ``--eval-data`` points it at a held-out file,
otherwise a shifted-seed synthetic stream is used.

The mesh spec names axes explicitly; unnamed axes default to 1.  For
multi-host runs set --coordinator=HOST:PORT --num-processes=N
--process-id=I (or run on a TPU pod where jax.distributed auto-configures).
``--per-process-data`` switches multi-host runs to per-process loading:
each host draws only batch/N rows at an independent seed and JAX stitches
the global batch from the local shards — no host materializes the full
batch (the scalable data path; default keeps every host loading the same
deterministic global batch).
"""

from __future__ import annotations

import json
import logging
import sys

from ..config import MeshConfig, parse_argv, require_flag_value


def parse_mesh(spec: str) -> MeshConfig:
    if not spec:
        return MeshConfig()
    names = {"data", "fsdp", "tensor", "sequence", "pipeline", "expert",
             "seq", "pipe"}
    alias = {"seq": "sequence", "pipe": "pipeline"}
    kwargs = {}
    for part in spec.split(","):
        name, _, size = part.partition(":")
        name = name.strip()
        if name not in names:
            raise ValueError(f"unknown mesh axis {name!r}")
        if not size.strip().isdigit():
            raise ValueError(
                f"mesh axis {name!r} needs an integer size, e.g. "
                f"'{name}:2' (got {part!r})")
        kwargs[alias.get(name, name)] = int(size)
    return MeshConfig(**kwargs)


KNOWN_FLAGS = frozenset({
    "model", "hf-gpt2", "hf-llama", "batch", "data", "seq", "eval-every",
    "eval-steps", "eval-data",
    "per-process-data", "prefetch", "attention", "microbatches",
    "pipeline-schedule", "virtual-stages", "dtype", "remat", "no-remat",
    "scan-layers", "remat-policy", "lora", "init-ckpt-dir", "ema",
    "no-scan-layers", "steps", "optimizer", "lr", "schedule", "warmup",
    "clip-norm", "accum", "mesh", "ckpt-dir", "ckpt-every", "ckpt-keep",
    "log-every", "seed", "resume", "metrics", "coordinator",
    "num-processes", "process-id",
})


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    _, flags = parse_argv(argv)
    if "help" in flags:
        print(__doc__)
        return 0
    unknown = set(flags) - KNOWN_FLAGS
    if unknown:
        # a typo'd flag silently falling back to its default is how a 64x
        # batch lands in a benchmark unnoticed — fail loudly instead
        raise SystemExit(f"unknown flag(s): {', '.join(sorted(unknown))}; "
                         f"--help lists the accepted flags")

    if "model" in flags and ("hf-gpt2" in flags or "hf-llama" in flags):
        raise SystemExit("--model and --hf-gpt2/--hf-llama both pick the "
                         "model; pass one (the converted checkpoint "
                         "defines its own architecture)")
    # a bare --lora would silently run a near-useless rank-1 adapter
    # (parse_argv's "1" sentinel); --lora=1 stays a deliberate choice
    require_flag_value(argv, "--lora",
                       hint="the R[:ALPHA] spec, e.g. --lora=8 or "
                            "--lora=8:16")
    if "coordinator" in flags or int(flags.get("num-processes", 1)) > 1:
        from ..parallel.distributed import initialize_multihost
        initialize_multihost(
            coordinator_address=flags.get("coordinator"),
            num_processes=int(flags.get("num-processes", 1)),
            process_id=int(flags.get("process-id", 0)))

    from ..parallel.train_loop import TrainLoopConfig, run_training
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = TrainLoopConfig(
        model=flags.get("model", "mnist_mlp"),
        hf_gpt2=flags.get("hf-gpt2", ""),
        hf_llama=flags.get("hf-llama", ""),
        batch_size=int(flags.get("batch", 64)),
        data_path=flags.get("data", ""),
        seq_len=int(flags.get("seq", 0)),
        eval_every=int(flags.get("eval-every", 0)),
        eval_steps=int(flags.get("eval-steps", 4)),
        eval_data_path=flags.get("eval-data", ""),
        per_process_data="per-process-data" in flags,
        prefetch=int(flags.get("prefetch", 2)),
        attention=flags.get("attention", "dense"),
        microbatches=int(flags.get("microbatches", 0)),
        pipeline_schedule=flags.get("pipeline-schedule", "gpipe"),
        virtual_stages=int(flags.get("virtual-stages", 1)),
        model_dtype=flags.get("dtype", ""),
        remat=(False if "no-remat" in flags
               else True if "remat" in flags else None),
        scan_layers=(False if "no-scan-layers" in flags
                     else True if "scan-layers" in flags else None),
        remat_policy=flags.get("remat-policy", ""),
        lora=flags.get("lora", ""),
        init_ckpt_dir=flags.get("init-ckpt-dir", ""),
        ema=float(flags.get("ema", 0.0)),
        steps=int(flags.get("steps", 100)),
        optimizer=flags.get("optimizer", "adam"),
        learning_rate=float(flags.get("lr", 1e-3)),
        schedule=flags.get("schedule", "constant"),
        warmup_steps=int(flags.get("warmup", 0)),
        clip_norm=float(flags.get("clip-norm", 0.0)),
        accum_steps=int(flags.get("accum", 1)),
        mesh=parse_mesh(flags.get("mesh", "")),
        checkpoint_dir=flags.get("ckpt-dir", ""),
        checkpoint_every=int(flags.get("ckpt-every", 0)),
        checkpoint_keep=int(flags.get("ckpt-keep", 0)),
        log_every=int(flags.get("log-every", 10)),
        seed=int(flags.get("seed", 0)),
        resume="resume" in flags,
        metrics_path=flags.get("metrics", ""),
    )
    summary = run_training(config)
    print(json.dumps(summary, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
