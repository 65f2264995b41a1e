"""Read the tolerances of ``perfbench/families/deepseek_v3.py`` on the chip:
the program's logits at the cell's own check (1 x 4,096, weights from the
seed, bfloat16) against the plain reference, sound and with each control's
fault put into the REFERENCE (or, ``e4m3``, its weights), a JSON line a
reading; then each control through the harness's own comparison
(``correct.compare_forward`` with the fault handed to the family's
``reference_forward``), a line a verdict: the sound run reads ``ok`` true
and every control ``ok`` false.

    chiprun --timeout 3000 -- python3 scripts/deepseek_controls.py \
        --seeds 3000000071,3000000072 --name pr54_controls

``--forms`` times, instead, the two forms a latent layer's extension can
take at 128 heads (a block of 256 queries against a row of 14,592): K and V
expanded a key block at a time inside the blockwise loop, which is what
``generation._latent_cache_attention`` runs, against the absorbed queries
run blockwise against the rows as they lie.  ``--rehearse`` tries the
script itself on the CPU at the tiny size.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

E4M3 = "e4m3"
CONTROLS = {
    "sound": None,
    E4M3: None,
    "rows_unturned": {"attention": {"rows_turned": False}},
    "gain_left_out": {"attention": {"gained": False}},
    "q_norm_left_out": {"attention": {"q_normed": False}},
    "group_limit_left_out": {"group_limit": False},
}


def through_e4m3(weights):
    """Every matrix through an 8-bit float, the nearest precision below
    the configuration's bfloat16."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, weights)


def forms(config, family, out, rehearse: bool) -> None:
    """The extension's attention of one layer, both forms, timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parameter_server_distributed_tpu.ops.blockwise_attention import (
        blockwise_attention)

    model = family.model(config, remat=False, n_layers=1)
    c = model.config
    held, block = (128, 32) if rehearse else (14592, 256)
    params = family.make_weights(model, 5)
    key = jax.random.key(3)
    q = jax.random.normal(key, (1, block, c.n_heads, c.head_dim + c.qk_shared),
                          c.dtype)
    rows = jax.random.normal(jax.random.fold_in(key, 1),
                             (1, held, c.latent_row), c.dtype)

    @jax.jit
    def by_key_block(q, rows, start):
        return blockwise_attention(
            q, rows, None, start, expand=lambda part: model.latent_expand(
                params, "layer0", part, wide_values=False))

    @jax.jit
    def absorbed(q, rows, start):
        up_k, up_v = model.latent_up(params, "layer0")
        inner = jnp.einsum("bthd,lhd->bthl", q[..., :c.head_dim], up_k,
                           preferred_element_type=jnp.float32)
        wide = jnp.concatenate(
            [inner.astype(c.dtype), q[..., c.head_dim:],
             jnp.zeros(q.shape[:3] + (c.latent_row - c.kv_latent
                                      - c.qk_shared,), c.dtype)], axis=-1)
        # (blockwise_attention scales by 1 / sqrt(its queries' width))
        wide = wide * ((c.latent_row / q.shape[-1]) ** 0.5)
        one = rows[:, :, None, :]
        summed = blockwise_attention(wide.astype(c.dtype), one, one, start)
        return jnp.einsum("bthl,lhd->bthd",
                          summed[..., :c.kv_latent], up_v,
                          preferred_element_type=jnp.float32).astype(c.dtype)

    for context in ((64,) if rehearse else (2048, 5803, 14336)):
        start = jnp.asarray([context], jnp.int32)
        results = {}
        for name, form in (("by_key_block", by_key_block),
                           ("absorbed", absorbed)):
            results[name] = np.asarray(form(q, rows, start), np.float32)
            times = []
            for _ in range(5):
                began = time.perf_counter()
                jax.block_until_ready(form(q, rows, start))
                times.append(time.perf_counter() - began)
            line = {"form": name, "context": context, "block": block,
                    "held": held, "ms_min": 1e3 * min(times),
                    "ms_median": 1e3 * sorted(times)[2],
                    "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
        apart = float(np.max(np.abs(results["by_key_block"]
                                    - results["absorbed"])))
        print(json.dumps({"forms_apart_max": apart, "context": context}),
              flush=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="3000000071,3000000072")
    parser.add_argument("--sound-seeds", default="")
    parser.add_argument("--name", default="deepseek_controls")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--forms", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax
    import numpy as np

    from perfbench import correct, families, harness

    harness.enable_compile_cache()
    _, _, config, traffic = harness.load_cell("serve_docs_deepseek_v3_ep16")
    family = families.of(config)
    check = dict(traffic["check"])
    if args.rehearse:
        config = family.tiny(config)
        check.update(tokens=64)
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("a tolerance is read on the chip")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.name + ".jsonl"), "a") as handle:
        if args.forms:
            forms(config, family, handle, args.rehearse)
            return 0
        model = family.model(config, remat=False)
        def reading(name):
            """the reference's readings under control ``name``; the 8-bit
            weights are made INSIDE the program, a layer at a time (a second
            store beside the first does not fit the chip)"""
            def read(w, t):
                return family.reference_readings(
                    config, through_e4m3(w) if name == E4M3 else w, t,
                    CONTROLS[name])
            return read

        readers = {name: jax.jit(reading(name))
                   for name in args.controls.split(",")}
        apply = jax.jit(model.apply)
        seeds = [int(s) for s in args.seeds.split(",") if s]
        sound_only = [int(s) for s in args.sound_seeds.split(",") if s]
        for seed in seeds + sound_only:
            params = family.make_weights(model, seed)
            tokens = correct.sample_tokens(config, seed, check["sequences"],
                                           check["tokens"])
            got = np.asarray(apply(params, tokens), np.float32)
            weights = family.reference_weights(config, params)
            for name, read in readers.items():
                if seed in sound_only and name != "sound":
                    continue
                started = time.time()
                logits, compared = read(weights, tokens)
                logits, compared = np.asarray(logits), np.asarray(compared)
                rms, worst = correct.logits_errors(got, logits)
                line = {"seed": seed, "control": name, "logits_rms": rms,
                        "logits_max": worst,
                        "finite": bool(np.all(np.isfinite(logits))),
                        "tokens_differing": compared[..., 0].tolist(),
                        "farthest_expert": float(compared[..., 1].max()),
                        "farthest_group": float(compared[..., 2].max()),
                        "seconds": time.time() - started,
                        "device": jax.devices()[0].device_kind}
                print(json.dumps(line), flush=True)
                handle.write(json.dumps(line) + "\n")
                handle.flush()
            del params, weights
            if seed in sound_only:
                continue
            # the comparison that decides ``correct``, as the cell runs it
            sound_forward = family.reference_forward
            for name in readers:
                family.reference_forward = functools.partial(
                    sound_forward, faults=CONTROLS[name])
                if name == E4M3:
                    family.reference_forward = (
                        lambda c, w, t: sound_forward(c, through_e4m3(w), t))
                try:
                    verdict = correct.compare_forward(config, model, seed,
                                                      check)
                finally:
                    family.reference_forward = sound_forward
                line = {"seed": seed, "control": name, "compare_forward":
                        verdict, "ok": verdict["ok"]}
                print(json.dumps(line), flush=True)
                handle.write(json.dumps(line) + "\n")
                handle.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
