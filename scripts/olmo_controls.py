"""Read the tolerances of ``perfbench/families/olmo_hybrid.py`` on the chip:
the program's logits at the cell's own check (1 x 2,048, weights from the
seed, bfloat16) against the plain reference, sound and with each control's
fault put into the REFERENCE, a JSON line a reading; then each control
through the harness's own comparison (``correct.compare_forward`` with the
fault handed to the family's ``reference_forward``), a line a verdict: the
sound run reads ``ok`` true and every control ``ok`` false.

    chiprun --timeout 3000 -- python3 scripts/olmo_controls.py \
        --seeds 3000000061,3000000062 --name pr50_controls

``--rehearse`` tries the script itself on the CPU at the tiny size.
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

CONTROLS = {
    "sound": None,
    "bf16_products": {"linear": {"product_bits": 7}},
    "bf16_state": {"linear": {"state_bits": 7}},
    "beta_undoubled": {"linear": {"beta_scale": 1.0}},
    "decay_sign": {"linear": {"decay_sign": 1.0}},
    "sqrt_96_for_sqrt_128": {"full": {"scale_dim": 96}},
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="3000000061,3000000062")
    parser.add_argument("--sound-seeds", default="")
    parser.add_argument("--name", default="olmo_controls")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax
    import numpy as np

    from perfbench import correct, families, harness

    harness.enable_compile_cache()
    _, _, config, traffic = harness.load_cell("serve_reasoning_olmo_hybrid")
    family = families.of(config)
    check = dict(traffic["check"])
    if args.rehearse:
        config = family.tiny(config)
        check.update(tokens=64)
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("a tolerance is read on the chip")
    model = family.model(config, remat=False)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    readers = {
        name: jax.jit(lambda w, t, faults=CONTROLS[name]:
                      family.reference_readings(config, w, t, faults))
        for name in args.controls.split(",")}
    apply = jax.jit(model.apply)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    sound_only = [int(s) for s in args.sound_seeds.split(",") if s]
    with open(os.path.join(out_dir, args.name + ".jsonl"), "a") as handle:
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
                logits, apart = read(weights, tokens)
                logits = np.asarray(logits)
                rms, worst = correct.logits_errors(got, logits)
                line = {"seed": seed, "control": name, "logits_rms": rms,
                        "logits_max": worst,
                        "finite": bool(np.all(np.isfinite(logits))),
                        "state_apart": [float(x) for x in apart],
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
