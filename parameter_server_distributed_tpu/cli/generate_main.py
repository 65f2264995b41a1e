"""Text generation CLI for the LM flagship (KV-cached decode).

    python -m parameter_server_distributed_tpu.cli.generate_main \
        --model=small_lm --prompt="the quick brown" --max-new=64 \
        [--ckpt=path.ckpt | --ckpt-dir=orbax_dir [--avg-last=K] \
         | --hf-gpt2=<local transformers checkout or hub name>] \
        [--temperature=0.8] [--top-k=40] [--top-p=0.9] \
        [--beam=4 [--length-penalty=0.6]] \
        [--seed=0] \
        [--dtype=bf16] [--tokens=1,2,3]

Parameters come from (in priority order) ``--ckpt`` (the host binary
checkpoint format — same files the PS writes), ``--ckpt-dir`` (latest
orbax sharded TrainState from pst-train), or fresh ``--seed`` init (demo
mode).  Either layer layout decodes: stores from ``--scan-layers``
training (stacked ``blocks/*``) and unrolled stores are converted to
whatever layout this process's model uses (``--scan-layers`` /
``--no-scan-layers`` / model default).  Prompts are byte-tokenized (data/text.ByteTokenizer, vocab 258 —
works for any registry LM whose vocab covers it); ``--tokens`` supplies
raw comma-separated token ids instead.  Output is the decoded
continuation (or raw ids with ``--tokens``).

The reference has no inference path at all (its gradient computation is a
0.01-constant stub — reference src/worker.cpp:316-329); this CLI completes
the train -> checkpoint -> generate loop.
"""

from __future__ import annotations

import logging
import sys

from ..config import parse_argv, require_flag_value


def draft_cost_ratio(flags: dict, draft, model) -> float:
    """--draft-cost-ratio if given, else the parameter-count proxy the
    adaptive depth controller's cost model defaults to (per-token decode
    cost tracks params, FLOPs- or bytes-bound alike).  Shared by
    pst-generate and pst-serve so the default cannot drift."""
    if "draft-cost-ratio" in flags:
        return float(flags["draft-cost-ratio"])
    return max(0.05, draft.num_params() / model.num_params())


def draft_ckpt_flags(path: str, lora_alpha: str = "") -> dict:
    """--draft-ckpt accepts either checkpoint form: a single-file host
    checkpoint (reference binary codec) or a sharded checkpoint DIRECTORY
    (what --ckpt-dir training runs write) — dispatch by what the path is,
    into the flag load_params reads for that form.  ``lora_alpha``
    (--draft-lora-alpha: the draft may be LoRA-trained with a DIFFERENT
    alpha than the target) forwards to the merge-on-load."""
    import os

    out = {"ckpt-dir": path} if os.path.isdir(path) else {"ckpt": path}
    if lora_alpha:
        out["lora-alpha"] = lora_alpha
    return out


def _merge_if_lora(params, flags: dict, what: str,
                   flag_name: str = "--lora-alpha"):
    """A checkpoint written by a --lora run carries adapter entries; fold
    them into dense weights before serving.  alpha must MATCH training
    (it scales the adapters), so it is demanded explicitly rather than
    silently defaulted.  ``flag_name`` is the user-facing flag that
    feeds this dict — --draft-lora-alpha for a DRAFT checkpoint."""
    from ..models.lora import lora_names, merge_lora

    if not lora_names(params):
        return params, what
    if not flags.get("lora-alpha"):
        raise SystemExit(
            f"{what} contains LoRA adapters; pass {flag_name}=A (the "
            f"ALPHA the run trained with, e.g. --lora=8:16 -> 16) to "
            f"merge them for serving")
    alpha = float(flags["lora-alpha"])
    return (merge_lora(params, alpha=alpha),
            f"{what} (LoRA merged, alpha {alpha:g})")


def load_params(flags: dict, model, seed: int,
                lora_flag: str = "--lora-alpha"):
    """Resolve the parameter source; returns (params, description).
    ``lora_flag`` names the user-facing alpha flag in merge errors
    (draft call sites pass --draft-lora-alpha)."""
    if flags.get("ckpt"):
        from ..checkpoint import codec
        epoch, iteration, params = codec.load(flags["ckpt"])
        return _merge_if_lora(
            params, flags,
            f"host checkpoint {flags['ckpt']} (iter {iteration})",
            lora_flag)
    if flags.get("ckpt-dir"):
        from ..checkpoint import sharded as sc
        avg_k = int(flags.get("avg-last", 0))
        if avg_k > 1:
            have = min(avg_k, len(sc._committed_steps(flags["ckpt-dir"])))
            step, state = sc.average_checkpoints(flags["ckpt-dir"], avg_k)
            what = f"average of last {have} checkpoints (newest step {step})"
        else:
            step, state = sc.restore_latest(flags["ckpt-dir"])
            what = f"sharded checkpoint step {step}"
        if step is None:
            raise FileNotFoundError(
                f"no step_N checkpoints under {flags['ckpt-dir']!r}")
        params = state["params"] if isinstance(state, dict) else state.params
        if avg_k > 1:
            from ..models.lora import lora_names
            if lora_names(params):
                # averaging A and B independently then merging computes
                # W + s*mean(A)@mean(B), which equals NONE of the
                # averaged models (the product is nonlinear in (A, B))
                raise SystemExit(
                    "--avg-last cannot average LoRA checkpoints (A@B is "
                    "nonlinear in the factors); merge each checkpoint "
                    "first (models.lora.merge_lora) or drop --avg-last")
        return _merge_if_lora(params, flags, what, lora_flag)
    return model.init_params(seed), f"fresh init (seed {seed})"


def match_layout(model, params):
    """Checkpoints port across layer layouts: convert a store to whatever
    layout this model instance uses (stacked blocks/* for scan_layers,
    unrolled layer<i>/* otherwise)."""
    from ..models.transformer import stack_layers, unstack_layers

    stacked_store = any(n.startswith("blocks/") for n in params)
    if model.config.scan_layers and not stacked_store:
        return stack_layers(params, model.config.n_layers)
    if not model.config.scan_layers and stacked_store:
        return unstack_layers(params)
    return params


KNOWN_FLAGS = frozenset({
    "model", "dtype", "scan-layers", "no-scan-layers", "seed", "ckpt",
    "ckpt-dir", "avg-last", "tokens", "prompt", "top-k", "top-p", "beam",
    "temperature", "max-new", "lora-alpha", "draft-lora-alpha",
    "draft-model", "draft-ckpt", "draft-seed",
    "draft-len", "adaptive-draft", "draft-cost-ratio",
    "length-penalty", "hf-gpt2",
})


def load_hf(flags: dict):
    """--hf-gpt2=<local dir or hub name>: convert a transformers GPT-2
    checkpoint (models/hf.py) and use its own tokenizer.  Returns
    (model, params, tokenizer_or_None)."""
    import jax.numpy as jnp
    import transformers

    from ..models.hf import from_hf_gpt2
    from ..models.registry import resolve_dtype

    src = flags["hf-gpt2"]
    hf_model = transformers.GPT2LMHeadModel.from_pretrained(src)
    dtype_flag = flags.get("dtype", "")
    dtype = resolve_dtype(dtype_flag) if dtype_flag else jnp.float32
    model, params = from_hf_gpt2(
        hf_model, dtype=dtype, scan_layers=("scan-layers" in flags))
    try:
        tok = transformers.AutoTokenizer.from_pretrained(src)
    except Exception:  # noqa: BLE001 — tokenizer files may be absent
        tok = None
    return model, params, tok


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    _, flags = parse_argv(argv)
    if "help" in flags:
        print(__doc__)
        return 0
    # bare --lora-alpha would merge with alpha 1 instead of the trained
    # value, silently mis-scaling every adapter
    require_flag_value(argv, "--lora-alpha", "--draft-lora-alpha",
                       "--draft-cost-ratio",
                       hint="the ALPHA the run trained with")
    unknown = set(flags) - KNOWN_FLAGS
    if unknown:
        # same contract as pst-train: a typo'd flag silently falling back
        # to its default corrupts results invisibly — fail loudly
        raise SystemExit(f"unknown flag(s): {', '.join(sorted(unknown))}; "
                         f"--help lists the accepted flags")

    import numpy as np

    from ..data.text import ByteTokenizer
    from ..models.generation import generate
    from ..models.registry import get_model_and_batches
    from ..models.transformer import Transformer
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seed = int(flags.get("seed", 0))
    hf_tok = None
    if flags.get("hf-gpt2"):
        if flags.get("ckpt") or flags.get("ckpt-dir"):
            raise ValueError("--hf-gpt2 provides its own weights; it does "
                             "not combine with --ckpt/--ckpt-dir")
        model, params, hf_tok = load_hf(flags)
        print(f"params: HF GPT-2 checkpoint {flags['hf-gpt2']} "
              f"({model.num_params() / 1e6:.1f}M params)", file=sys.stderr)
    else:
        model, _ = get_model_and_batches(
            flags.get("model", "small_lm"), 1, dtype=flags.get("dtype", ""),
            scan=(False if "no-scan-layers" in flags
                  else True if "scan-layers" in flags else None))
        if not isinstance(model, Transformer):
            raise ValueError(f"--model={flags.get('model')!r} is not an LM")
        params, source = load_params(flags, model, seed)
        print(f"params: {source}", file=sys.stderr)
        params = match_layout(model, params)

    tokenizer = ByteTokenizer()
    if flags.get("tokens"):
        ids = [int(t) for t in flags["tokens"].split(",")]
        decode_text = False
    elif hf_tok is not None:
        prompt_text = flags.get("prompt", "hello")
        ids = hf_tok.encode(prompt_text)
        decode_text = True
    elif flags.get("hf-gpt2"):
        raise ValueError("--hf-gpt2 checkpoint has no tokenizer files; "
                         "pass raw ids via --tokens=1,2,3")
    else:
        from ..data.text import require_vocab
        prompt_text = flags.get("prompt", "hello")
        require_vocab(model.config.vocab, tokenizer)
        ids = tokenizer.encode(prompt_text) or [tokenizer.BOS]
        decode_text = True
    if any(not 0 <= t < model.config.vocab for t in ids):
        raise ValueError(f"token id out of range for vocab "
                         f"{model.config.vocab}")

    top_k = int(flags.get("top-k", 0))
    top_p = float(flags.get("top-p", 0.0))
    beam = int(flags.get("beam", 0))
    # sampling flags imply sampling: temperature 0 (greedy) would silently
    # ignore top-k/top-p, so they default the temperature to 1.0
    default_temp = "1.0" if (top_k or top_p) else "0.0"
    temperature = float(flags.get("temperature", default_temp))
    prompt = np.asarray([ids], np.int32)
    max_new = int(flags.get("max-new", 64))
    draft_name = flags.get("draft-model", "")
    if beam <= 1 and "length-penalty" in flags:
        raise ValueError("--length-penalty applies to beam search; "
                         "pass --beam=W > 1")
    if draft_name:
        if beam > 1 or top_k or top_p:
            raise ValueError("--draft-model (speculative decoding) "
                             "supports greedy (default) or plain "
                             "--temperature sampling; it does not combine "
                             "with --beam/--top-k/--top-p")
        from ..models.generation import speculative_generate_batched
        draft, _ = get_model_and_batches(draft_name, 1,
                                         dtype=flags.get("dtype", ""))
        if not isinstance(draft, Transformer):
            raise ValueError(f"--draft-model={draft_name!r} is not an LM")
        dparams, dsource = load_params(
            draft_ckpt_flags(flags.get("draft-ckpt", ""),
                             flags.get("draft-lora-alpha", "")), draft,
            int(flags.get("draft-seed", seed + 1)),
            lora_flag="--draft-lora-alpha")
        dparams = match_layout(draft, dparams)
        print(f"draft params: {dsource}", file=sys.stderr)
        # whole-loop-on-device batched decoder (accept/resample jitted,
        # per-row ragged caches) — the serving path; the host-loop
        # speculative_generate stays as the tested reference
        # --adaptive-draft: --draft-len becomes the CAP; the first call
        # runs measured spec-vs-greedy probes and memoizes the winning
        # depth (one-shot CLI calls pay the calibration, so fixed depth
        # stays the default here — servers and repeated callers benefit)
        adaptive = "adaptive-draft" in flags
        rho = draft_cost_ratio(flags, draft, model)
        out, stats = speculative_generate_batched(
            model, params, draft, dparams, prompt, max_new,
            draft_len=int(flags.get("draft-len", 4)),
            temperature=temperature, seed=seed, adaptive=adaptive,
            draft_cost_ratio=rho)
        depth_note = (f", settled depth {stats['draft_depth']}"
                      if adaptive else "")
        print(f"speculative: {stats['tokens_per_target_forward']:.2f} "
              f"tokens/target-forward (incl. prefill), accept rate "
              f"{stats['draft_accept_rate']:.2f}{depth_note}",
              file=sys.stderr)
    elif beam > 1:
        if top_k or top_p or "temperature" in flags:
            raise ValueError("--beam is deterministic; it does not combine "
                             "with --temperature/--top-k/--top-p")
        from ..models.generation import beam_search
        # text mode: the tokenizer's EOS finishes beams early
        # (require_vocab above guaranteed the byte vocab is covered);
        # raw-token mode has no reserved stop id, HF or not
        if not decode_text:
            eos = None
        elif hf_tok is not None:
            eos = hf_tok.eos_token_id
        else:
            eos = tokenizer.EOS
        out, score = beam_search(
            model, params, prompt, max_new, beam_width=beam, eos_id=eos,
            length_penalty=float(flags.get("length-penalty", 0.0)))
        print(f"beam: width {beam}, joint logprob "
              f"{float(np.asarray(score)[0]):.3f}", file=sys.stderr)
    else:
        out = generate(model, params, prompt, max_new,
                       temperature=temperature, top_k=top_k, top_p=top_p,
                       rng=seed)
    tokens = np.asarray(out)[0]
    if decode_text:
        eos_id = (hf_tok.eos_token_id if hf_tok is not None
                  else tokenizer.EOS)
        stop = np.nonzero(tokens == eos_id)[0]
        if stop.size:  # trim at the first EOS (beam padding or natural)
            tokens = tokens[:int(stop[0])]
        text = (hf_tok.decode(tokens) if hf_tok is not None
                else tokenizer.decode(tokens))
        print(text, flush=True)
    else:
        print(",".join(str(int(t)) for t in tokens), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
