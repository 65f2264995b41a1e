"""Read the tolerances of ``perfbench/families/granite_hybrid.py`` on the
chip: the program's logits at the cell's own check (1 x 2,048, weights from
the seed, bfloat16) against the plain reference, sound and with each
control's fault put into the REFERENCE, a JSON line a reading, with the
verdict ``correct.compare_forward`` gives such a reading by the family's
limits (``ok``: both logit limits and, through ``reference_forward``, the
first state's).

    chiprun --timeout 3000 -- python3 scripts/granite_controls.py \
        --seeds 3000000071,3000000072 --name pr57_controls

``--rehearse`` tries the script itself on the CPU at the tiny size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CONTROLS = {
    "sound": None,
    "scale_an_eighth_for_a_64th": {"attention": {"scale": 0.125}},
    "skip_left_out": {"ssm": {"skip": False}},
    "norm_before_the_gate": {"ssm": {"gate_first": False}},
    "conv_bias_left_out": {"ssm": {"conv_bias": False}},
    "state_in_bfloat16": {"ssm": {"state_bits": 7}},
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="3000000071,3000000072")
    parser.add_argument("--sound-seeds", default="")
    parser.add_argument("--name", default="granite_controls")
    parser.add_argument("--controls", default=",".join(CONTROLS))
    parser.add_argument("--tokens", type=int, default=0,
                        help="another length than the check's (a served "
                             "request's replay is shorter)")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    import jax
    import numpy as np

    from perfbench import correct, families, harness

    harness.enable_compile_cache()
    _, _, config, traffic = harness.load_cell(
        "serve_manychat_granite_4_h_micro")
    family = families.of(config)
    check = dict(traffic["check"])
    if args.rehearse:
        config = family.tiny(config)
        check.update(tokens=64)
    if args.tokens:
        check.update(tokens=args.tokens)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("a tolerance is read on the chip")
    model = family.model(config, remat=False)
    limits = family.TOLERANCES
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
                finite = bool(np.all(np.isfinite(logits)))
                line = {"seed": seed, "control": name, "logits_rms": rms,
                        "logits_max": worst, "finite": finite,
                        "logits_std": float(np.std(logits)),
                        "state_apart_first": float(apart),
                        "ok": bool(finite and rms <= limits["logits_rms"]
                                   and worst <= limits["logits_max"]
                                   and float(apart)
                                   <= family.STATE_TOLERANCE),
                        "seconds": time.time() - started,
                        "device": jax.devices()[0].device_kind}
                print(json.dumps(line), flush=True)
                handle.write(json.dumps(line) + "\n")
                handle.flush()
            del params, weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
