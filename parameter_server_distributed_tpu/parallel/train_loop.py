"""High-level SPMD training loop: the pure-collectives training mode.

This is "sync all-reduce mode" (BASELINE config 4) as a first-class entry
point: no PS process, no RPC on the data path — the sharded TrainState IS
the parameter server, the compiled step's collectives are the barrier, and
the coordinator/PS control plane is only needed for multi-process
elasticity (not for single-controller SPMD).

Features: donated-buffer steps, JSONL metrics (loss, step time, samples/s/
chip), periodic sharded checkpoints with resume, profiler hook.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import time

import jax
import numpy as np

from ..config import MeshConfig
from ..checkpoint import sharded as sharded_ckpt
from ..models.registry import get_model_and_batches
from ..obs import (MetricsLogger, StepTimer, profile_trace,
                   samples_per_sec)
from ..obs import stats as obs_stats
from .mesh import build_mesh, data_parallel_size
from .sharding import fsdp_rule, fsdp_tp_rule
from .train_step import ShardedTrainer, make_optimizer

log = logging.getLogger("pst.train")


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    model: str = "mnist_mlp"
    hf_gpt2: str = ""             # path to a transformers GPT-2 checkout:
                                  # train/fine-tune the CONVERTED model
                                  # (models/hf.from_hf_gpt2) instead of a
                                  # registry preset
    hf_llama: str = ""            # same for a LlamaForCausalLM checkout
                                  # (models/hf.from_hf_llama; native
                                  # rope/rms arch — every schedule and
                                  # composition applies)
    batch_size: int = 64          # global batch
    data_path: str = ""           # file-backed data; empty = synthetic
    seq_len: int = 0              # LM sequence-length override (0 = default)
    per_process_data: bool = False  # multi-host: each process loads only
                                    # its batch/process_count rows
    prefetch: int = 2             # batches placed on device ahead of the
                                  # loop (0 = synchronous loading)
    eval_every: int = 0           # held-out eval cadence in steps (0 = off)
    eval_steps: int = 4           # batches averaged per evaluation
    eval_data_path: str = ""      # held-out data; empty = shifted-seed
                                  # synthetic stream
    attention: str = "dense"      # how the seq axis is used (LM models):
                                  # models/transformer.ATTENTION_CHOICES
    microbatches: int = 0         # pipeline microbatches (0 = pipe size)
    pipeline_schedule: str = "gpipe"  # gpipe | 1f1b (pipe axis > 1)
    virtual_stages: int = 1       # interleaved 1F1B chunks per pipe rank
    model_dtype: str = ""         # "" = model default | f32 | bf16
    remat: bool | None = None     # per-layer jax.checkpoint (LM models);
                                  # None = model default, True/False force
    scan_layers: bool | None = None  # lax.scan over stacked layers (LMs);
                                     # tri-state like remat
    remat_policy: str = ""        # "" = model default | full | dots
                                  # (what remat may keep; flagship LMs)
    lora: str = ""                # "R" or "R:ALPHA" = LoRA fine-tune:
                                  # only rank-R adapters train, base
                                  # weights frozen (models/lora.py)
    ema: float = 0.0              # >0 = track a Polyak/EMA shadow of the
                                  # params at this decay (in opt state —
                                  # checkpointed/sharded for free); the
                                  # summary reports ema_eval_loss
    init_ckpt_dir: str = ""       # load params (only) from this sharded
                                  # checkpoint dir before training — the
                                  # pretrained-base fine-tune flow
    steps: int = 100
    optimizer: str = "adam"
    learning_rate: float = 1e-3
    schedule: str = "constant"    # constant | cosine | linear (+ warmup)
    warmup_steps: int = 0
    clip_norm: float = 0.0        # 0 = no gradient clipping
    accum_steps: int = 1          # microbatch gradient accumulation
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    checkpoint_dir: str = ""
    checkpoint_every: int = 0     # steps; 0 = disabled
    checkpoint_keep: int = 0      # retention: newest N kept (0 = all)
    log_every: int = 10
    seed: int = 0
    resume: bool = False
    metrics_path: str = ""

    def __post_init__(self):
        from ..models.transformer import check_attention
        check_attention(self.attention)


def _pick_rule(model_name: str, mesh):
    if mesh.shape["pipe"] > 1:
        from .pipeline import pipeline_rule
        return pipeline_rule(mesh)
    if ("lm" in model_name or "transformer" in model_name
            or model_name.startswith("vit")):
        # ViT stores use the transformer's param-name suffixes on purpose
        # (models/vit.py docstring) — same Megatron TP/fsdp layout
        from ..models.transformer import transformer_rule
        return transformer_rule(mesh)
    if mesh.shape["tensor"] > 1:
        return fsdp_tp_rule(mesh)
    return fsdp_rule(mesh)


def run_training(config: TrainLoopConfig) -> dict:
    # use the first N devices when the mesh is smaller than the machine
    devices = jax.devices()[:config.mesh.num_devices]
    mesh = build_mesh(config.mesh, devices=devices)
    # per-process data: each host draws an independent seed and only its
    # share of rows; the trainer stitches the global batch from the local
    # shards (put_batch_local).  Data remains iid across hosts.
    n_proc = jax.process_count()
    local_mode = config.per_process_data and n_proc > 1
    load_batch = config.batch_size
    load_seed = config.seed
    if local_mode:
        if config.batch_size % n_proc:
            raise ValueError(
                f"--per-process-data: global batch {config.batch_size} "
                f"must divide by process count {n_proc}")
        load_batch = config.batch_size // n_proc
        load_seed = config.seed + 7919 * (jax.process_index() + 1)
    hf_params = None
    hf_path = config.hf_gpt2 or config.hf_llama
    # the sharding rule keys on the model name; a converted checkpoint is
    # a transformer whatever config.model says
    rule_model = "transformer" if hf_path else config.model
    if hf_path:
        # converted-checkpoint training: model + weights come from the
        # transformers checkout, data from --data or the synthetic stream
        if config.hf_gpt2 and config.hf_llama:
            raise ValueError("--hf-gpt2 and --hf-llama both pick the "
                             "checkpoint; pass one")
        if config.init_ckpt_dir:
            raise ValueError("--hf-gpt2/--hf-llama and --init-ckpt-dir "
                             "are both parameter initializers; pass one")
        if config.seq_len or config.remat or config.remat_policy:
            raise ValueError("converted checkpoints fix seq (the HF "
                             "config's positions) and have no remat "
                             "wiring; drop --seq/--remat/--remat-policy")
        import transformers

        from ..models.hf import from_hf_gpt2, from_hf_llama
        from ..models.registry import lm_batches, resolve_dtype
        if config.hf_gpt2:
            hf_model = transformers.GPT2LMHeadModel.from_pretrained(
                config.hf_gpt2)
            convert, default_dtype = from_hf_gpt2, "f32"
        else:
            hf_model = transformers.LlamaForCausalLM.from_pretrained(
                config.hf_llama)
            convert, default_dtype = from_hf_llama, "bf16"
        model, hf_params = convert(
            hf_model,
            dtype=resolve_dtype(config.model_dtype or default_dtype),
            scan_layers=bool(config.scan_layers))
        batches = lm_batches(model, load_batch, seed=load_seed,
                             data_path=config.data_path)
        log.info("converted HF checkpoint %s: %d params", hf_path,
                 model.num_params())
    else:
        model, batches = get_model_and_batches(
            config.model, load_batch, seed=load_seed,
            data_path=config.data_path, dtype=config.model_dtype,
            remat=config.remat, scan=config.scan_layers,
            seq_len=config.seq_len, remat_policy=config.remat_policy)
    from ..models.transformer import Transformer
    if isinstance(model, Transformer):
        if mesh.shape["pipe"] > 1:
            # pipeline mode: wrap in the scheduled model (pipe + data axes;
            # blocks live on their pipe rank).  A stage attends whole
            # sequences on its own device: a sequence split over a seq
            # axis does not compose with it.
            if config.attention != "dense":
                raise ValueError(
                    f"--attention={config.attention} splits the sequence "
                    "over a seq axis, which a pipe axis does not compose "
                    "with (stage-internal attention runs inside shard_map)")
            from .pipeline import PipelinedTransformerLM
            model = PipelinedTransformerLM(
                model, mesh, num_microbatches=config.microbatches,
                schedule=config.pipeline_schedule,
                virtual_stages=config.virtual_stages)
        else:
            # give the model the mesh (activation sharding constraints, a
            # device's shard for the default attention) and how the seq
            # axis is used (models/transformer.select_attention)
            model.on_mesh(mesh, config.attention)
            if mesh.shape["seq"] > 1 and model.config.loss_chunk:
                # chunked cross-entropy scans over seq chunks, which
                # under sequence parallelism would slice single devices'
                # shards out of the seq-sharded activations and serialize
                # the LM head; per-device logits are already O(S/N *
                # vocab) there, so drop the chunking instead
                import dataclasses as _dc
                model.config = _dc.replace(model.config, loss_chunk=0)
    else:
        if config.attention != "dense":
            raise ValueError(
                f"--attention={config.attention} applies to transformer "
                f"models; {config.model!r} is not one")
        if mesh.shape["pipe"] > 1:
            raise ValueError(
                f"--mesh pipe axis applies to transformer models; "
                f"{config.model!r} is not one")
    loss_fn = model.loss
    if hf_params is not None:
        # the converted weights ARE the initializer; a pipelined model
        # restacks them into its blocks/* layout
        init_params = (model.restack_params(hf_params)
                       if hasattr(model, "restack_params")
                       else dict(hf_params))
    else:
        init_params = model.init_params(config.seed)
    optimizer = make_optimizer(config.optimizer, config.learning_rate,
                               schedule=config.schedule,
                               warmup_steps=config.warmup_steps,
                               total_steps=config.steps,
                               clip_norm=config.clip_norm,
                               ema_decay=config.ema)
    if config.init_ckpt_dir:
        # start from a PRETRAINED store (params only — fresh optimizer):
        # the dense-checkpoint -> fine-tune flow, incl. converted HF
        # checkpoints saved by checkpoint/sharded.  --resume, by
        # contrast, restores the full TrainState of the SAME run shape.
        last, restored = sharded_ckpt.restore_latest(config.init_ckpt_dir)
        if last is None:
            raise FileNotFoundError(
                f"--init-ckpt-dir: no step_N checkpoints under "
                f"{config.init_ckpt_dir!r}")
        init_params = (restored["params"] if isinstance(restored, dict)
                       else restored.params)
        log.info("initialized params from %s step %d",
                 config.init_ckpt_dir, last)
        from ..models.lora import lora_names
        if lora_names(init_params):
            # explicit over silent: with --lora, init_lora would OVERWRITE
            # the trained factors with fresh init; without it, the plain
            # loss never reads them and the run trains the base model
            # while the inert adapters still get optimizer state
            raise ValueError(
                f"--init-ckpt-dir store already contains LoRA adapters; "
                f"to continue that fine-tune use --resume "
                f"--ckpt-dir={config.init_ckpt_dir}, or merge first "
                f"(models.lora.merge_lora) to start a fresh run from the "
                f"adapted weights")
    grad_fn = getattr(model, "value_and_grad", None)
    if config.lora:
        # parameter-efficient fine-tuning: adapters join the store as
        # plain entries (sharding/checkpointing unchanged), the loss
        # materializes effective weights per step, and the optimizer is
        # masked so ONLY /lora_ entries train (models/lora.py).
        # Composes with pipeline (adapters follow the blocks/* restack;
        # the schedule's grad_fn is wrapped to differentiate through the
        # adapter collapse) and with --ema (freeze_base masks params_ema
        # to the adapters, so the shadow tracks exactly what trains; the
        # EMA eval below grafts the shadowed adapters onto the frozen
        # base)
        from ..models.lora import (freeze_base, init_lora, lora_loss,
                                   lora_names, lora_value_and_grad,
                                   split_rank_alpha)
        rank, alpha = split_rank_alpha(config.lora)
        init_params = init_lora(init_params, rank=rank,
                                rng=config.seed + 1)
        loss_fn = lora_loss(model.loss, alpha=alpha)
        if grad_fn is not None:
            grad_fn = lora_value_and_grad(grad_fn, alpha=alpha)
        optimizer = freeze_base(optimizer)
        log.info("LoRA fine-tuning: rank %d alpha %.1f — %d adapter "
                 "tensors train, base frozen", rank, alpha,
                 len(lora_names(init_params)))
    trainer = ShardedTrainer(
        loss_fn, mesh, _pick_rule(rule_model, mesh),
        optimizer,
        accum_steps=config.accum_steps,
        grad_fn=grad_fn)
    state = trainer.init_state(init_params)

    start_step = 0
    if config.resume and config.checkpoint_dir:
        last, restored = sharded_ckpt.restore_latest(config.checkpoint_dir,
                                                     template=state)
        if last is not None:
            state = restored
            start_step = int(np.asarray(state.step))
            log.info("resumed from step %d", start_step)

    eval_batches = None
    if config.eval_every:
        # a disjoint stream: the held-out file when given; otherwise the
        # TRAINING source at a shifted seed (different random crops of the
        # same file, or a shifted-seed synthetic stream) — never a
        # different distribution than training, which would make the
        # number meaningless
        eval_source = config.eval_data_path or config.data_path
        if config.data_path and not config.eval_data_path:
            log.warning(
                "--eval-every without --eval-data: evaluating on "
                "shifted-seed crops of the TRAINING file %s (overlapping "
                "data, not a held-out split)", config.data_path)
        if config.hf_gpt2:
            from ..models.registry import lm_batches
            eval_batches = lm_batches(model, load_batch,
                                      seed=load_seed + 100_003,
                                      data_path=eval_source)
        else:
            _, eval_batches = get_model_and_batches(
                config.model, load_batch, seed=load_seed + 100_003,
                data_path=eval_source,
                dtype=config.model_dtype, remat=config.remat,
                scan=config.scan_layers, seq_len=config.seq_len,
                remat_policy=config.remat_policy)

    def run_eval(state, batch_list=None) -> float:
        evaluate = trainer.eval_fn()
        if batch_list is None:
            batch_list = [place_batch(next(eval_batches))
                          for _ in range(max(1, config.eval_steps))]
        total = sum(float(evaluate(state, b)) for b in batch_list)
        return total / len(batch_list)

    log.info("config: %s", json.dumps(dataclasses.asdict(config),
                                      default=str, sort_keys=True))
    step_fn = trainer.step_fn()
    place_batch = (trainer.put_batch_local if local_mode
                   else trainer.put_batch)
    if config.prefetch > 0:
        # loader + H2D placement run on a background thread, staying
        # config.prefetch batches ahead of the compute loop
        from ..data.prefetch import prefetch_to_device
        placed_batches = prefetch_to_device(batches, place_batch,
                                            depth=config.prefetch)
    else:
        placed_batches = (place_batch(b) for b in batches)
    metrics_log = MetricsLogger(config.metrics_path or None)
    timer = StepTimer()
    n_chips = mesh.devices.size
    last_loss = float("nan")
    # obs registry mirrors of the JSONL stream: data-wait vs dispatch
    # split per step (cheap: two perf_counter reads) — what `pst-status
    # --metrics` style rollups and the benchmark's readers read without
    # parsing logs (the synced step time is in the JSONL stream)
    obs_data = obs_stats.histogram("train.data_s")
    obs_dispatch = obs_stats.histogram("train.dispatch_s")

    last_saved_step = -1
    last_eval = (-1, float("nan"))
    window_t0 = time.perf_counter()
    window_steps = 0
    try:
        with profile_trace("train_loop"):
            for step_idx in range(start_step, config.steps):
                t0 = time.perf_counter()
                batch = next(placed_batches)
                t1 = time.perf_counter()
                obs_data.observe(t1 - t0)
                state, metrics = step_fn(state, batch)
                obs_dispatch.observe(time.perf_counter() - t1)
                window_steps += 1
                if ((step_idx + 1) % config.log_every == 0
                        or step_idx == config.steps - 1):
                    last_loss = float(metrics["loss"])  # device sync point
                    # Steps dispatch asynchronously; the sync above drains
                    # the whole window, so per-step time is window wall
                    # time / steps.
                    dt = (time.perf_counter() - window_t0) / window_steps
                    timer.record(dt)
                    metrics_log.log(step=step_idx + 1, loss=last_loss,
                                    step_time_s=dt,
                                    samples_per_sec_chip=samples_per_sec(
                                        config.batch_size, dt, n_chips),
                                    grad_norm=float(metrics["grad_norm"]))
                    log.info("step %d loss %.4f (%.1f ms)", step_idx + 1,
                             last_loss, dt * 1e3)
                    window_t0 = time.perf_counter()
                    window_steps = 0
                if (config.eval_every
                        and (step_idx + 1) % config.eval_every == 0):
                    last_eval = (step_idx + 1, run_eval(state))
                    metrics_log.log(step=step_idx + 1,
                                    eval_loss=last_eval[1])
                    log.info("step %d eval_loss %.4f (%d batches)",
                             step_idx + 1, last_eval[1], config.eval_steps)
                    # eval synced the device; restart the timing window so
                    # its wall time is not booked to training steps
                    window_t0 = time.perf_counter()
                    window_steps = 0
                if (config.checkpoint_every and config.checkpoint_dir
                        and (step_idx + 1) % config.checkpoint_every == 0):
                    # async: the loop keeps stepping while orbax writes in
                    # the background; the finally fence below surfaces any
                    # write failure even if training dies first
                    path = sharded_ckpt.save_sharded(config.checkpoint_dir,
                                                     step_idx + 1, state,
                                                     asynchronous=True)
                    last_saved_step = step_idx + 1
                    log.info("checkpoint %s (async)", path)
                    if config.checkpoint_keep and jax.process_index() == 0:
                        # prunes COMMITTED checkpoints only; the save above
                        # is still writing under a tmp-suffixed name.
                        # process 0 only: deletion of the shared directory
                        # must not race across controllers
                        sharded_ckpt.prune_checkpoints(
                            config.checkpoint_dir, config.checkpoint_keep)
    finally:
        if hasattr(placed_batches, "close"):
            # stop the prefetch worker: otherwise it keeps placing device
            # batches while the final eval/checkpoint need the memory
            placed_batches.close()
        sharded_ckpt.wait_for_saves()
        if (config.checkpoint_keep and config.checkpoint_dir
                and jax.process_index() == 0):
            sharded_ckpt.prune_checkpoints(config.checkpoint_dir,
                                           config.checkpoint_keep)

    jax.block_until_ready(state.params)
    end_step = max(start_step, config.steps)
    summary = {"final_loss": last_loss, "steps": end_step,
               "dp_size": data_parallel_size(mesh), **timer.summary()}
    if config.eval_every:
        # reuse the loop's step-N result when training ended exactly on an
        # eval boundary (same params — a re-run would just burn eval_steps
        # forwards and report a different-batch number than the JSONL)
        if config.ema:
            # raw-vs-EMA on the SAME eval batches, else the gap the
            # feature exists to show is confounded by batch noise
            from .train_step import extract_ema, state_shardings
            shared = [place_batch(next(eval_batches))
                      for _ in range(max(1, config.eval_steps))]
            summary["eval_loss"] = run_eval(state, shared)
            ema_params = extract_ema(state.opt_state)
            if ema_params is not None:
                # the shadow is float32 (params_ema); cast back to the
                # model dtype so the eval jit sees the params' avals.
                # Under --lora the shadow is masked to the trainable
                # adapters (freeze_base wraps the whole chain), so frozen
                # entries hold MaskedNode placeholders — graft the
                # shadowed adapters onto the frozen base, which IS the
                # EMA of a store whose base never moves
                import optax
                ema_params = {
                    name: (p if isinstance(ema_params[name],
                                           optax.MaskedNode)
                           else ema_params[name].astype(p.dtype))
                    for name, p in state.params.items()}
                # opt-state slots are shape-matched to param shardings,
                # which under NAME-based rules (Megatron TP) can pick a
                # different-but-self-consistent layout; the eval jit
                # expects the params' own specs, so re-place first
                param_sh = state_shardings(
                    state, mesh, _pick_rule(rule_model, mesh)).params
                ema_placed = jax.tree.map(jax.device_put, ema_params,
                                          param_sh)
                ema_loss = run_eval(
                    dataclasses.replace(state, params=ema_placed), shared)
                summary["ema_eval_loss"] = (None if math.isnan(ema_loss)
                                            else ema_loss)
            else:
                # config.ema is on but no EmaState survived in opt_state —
                # a template-free checkpoint restore can degrade the
                # NamedTuple to a plain tuple.  Losing the metric silently
                # would read as "EMA converged to raw"; say what happened.
                log.warning(
                    "--ema is set but no EmaState found in opt_state "
                    "(template-free restore?); ema_eval_loss omitted")
        else:
            summary["eval_loss"] = (last_eval[1]
                                    if last_eval[0] == end_step
                                    else run_eval(state))
        if math.isnan(summary["eval_loss"]):
            summary["eval_loss"] = None  # strict-JSON safe, like final_loss
        else:
            # mean NLL in nats -> perplexity (LM-meaningful; harmless
            # but ignorable for classification losses)
            summary["eval_ppl"] = round(math.exp(
                min(summary["eval_loss"], 700.0)), 4)
    if math.isnan(summary["final_loss"]):
        summary["final_loss"] = None  # keep the summary strict-JSON safe
    if (config.checkpoint_every and config.checkpoint_dir
            and start_step < config.steps
            and last_saved_step != config.steps):
        summary["checkpoint"] = sharded_ckpt.save_sharded(
            config.checkpoint_dir, config.steps, state)
        if config.checkpoint_keep and jax.process_index() == 0:
            # the fallback save lands after the finally-block prune; prune
            # again so keep=N never ends the run with N+1 checkpoints
            sharded_ckpt.prune_checkpoints(config.checkpoint_dir,
                                           config.checkpoint_keep)
    return summary
