#!/usr/bin/env python3
"""The serving and training programs of a tree, hashed as they are lowered:
what a refactor that must move no traced program is held to (PR 59).

    JAX_PLATFORMS=cpu python3 scripts/program_hashes.py [--tree DIR] > out.jsonl

Two sets, one JSON line a program.  ``v5e``: every configuration
tests/test_chip_compile.py compiles, at its widths, slots and context there,
lowered for a described v5e (no chip) with ``transformer._kernel_backend``
forced both ways (``kernel``: the pallas kernels go in through Mosaic;
``plain``: the einsums): the decode round (``serving._step_runner``), a
prefill of 512 positions (``_prefill_runner``), the extension of that row by
256 (``_extend_runner``) and, for GPT-2, the training step's gradient.
``tiny``: every family's ``tiny(config)`` on the CPU (between them all eight
mixers): the same three programs and the gradient of ``loss``.

A line holds two digests.  ``text``: sha256 of ``lowered.as_text()``, the
StableHLO without locations: the program.  (A Mosaic kernel travels in it as
serialized bytecode that carries the file, line and call stack of every
operation of the kernel's body, so two trees at two paths, or a caller whose
lines moved, never agree on the bytes: each body is parsed and stands in the
hashed text as its own assembly WITHOUT locations.)  ``scopes``: sha256 of every
operation's name stack in program order (``as_text(debug_info=True)``, file
names and line numbers dropped): the ``jax.named_scope`` paths that
perfbench/readers find a layer's share by.  Run it on two trees
(``--tree`` a ``git archive`` of the other) and compare the lines.
"""

import argparse
import base64
import hashlib
import json
import os
import re
import sys

# configuration file, layers, slots, context: tests/test_chip_compile.py's
V5E = {"gpt2-medium": (2, 32, 1024),
       "smallthinker-21b-a3b-8l": (4, 16, 16384),
       "minicpm-sala-12l": (3, 16, 65536),
       "lfm2-24b-a2b-10l": (4, 64, 4096),
       "k-exaone-236b-a23b-8l-ep8": (4, 32, 4096),
       "kimi-linear-48b-a3b-12l-ep8": (5, 64, 16384),
       "olmo-hybrid-7b-16l": (4, 12, 4096),
       "deepseek-v3-5l-ep16": (2, 32, 16384),
       "granite-4.0-h-micro": (10, 64, 2048)}
PREFIX, SUFFIX = 512, 256


def _kernels_without_locations(text: str) -> tuple[str, int]:
    """(``text`` with every Mosaic kernel's body (base64 of MLIR bytecode in
    a ``tpu_custom_call``'s config) replaced by the digest of its assembly
    printed without locations, how many there were)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def plain(match) -> str:
        context = mlir.make_ir_context()
        context.allow_unregistered_dialects = True    # ``stable_mosaic``
        with context:
            body = ir.Module.parse(base64.b64decode(match.group(1)))
            assembly = body.operation.get_asm(enable_debug_info=False)
        return "body " + hashlib.sha256(assembly.encode()).hexdigest()

    return re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, text)


def digests(lowered) -> dict:
    located = lowered.as_text(debug_info=True)
    # an alias a (name stack, call site): `#loc7 = loc("jit(run)/attn/..."(#loc3))`
    named = dict(re.findall(r'^(#loc\d+) = loc\("(jit\([^"]*)"', located,
                            re.MULTILINE))
    stacks = [named[alias] for alias in re.findall(
        r"loc\((#loc\d+)\)$", located, re.MULTILINE) if alias in named]
    text, kernels = _kernels_without_locations(lowered.as_text())
    return {"text": hashlib.sha256(text.encode()).hexdigest()[:16],
            "kernels": kernels,
            "scopes": hashlib.sha256("\n".join(stacks).encode()).hexdigest()[:16],
            "named": len(stacks)}


def programs(model, params, slots, max_len, place, prefix, suffix):
    """(name, lowered) of the round, a prefill, its extension and the
    gradient of the loss, every argument a shape ``place`` has placed."""
    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models import generation, serving

    def shaped(shape, dtype):
        return place(jax.ShapeDtypeStruct(shape, dtype))

    cache = place(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    rng = place(jax.eval_shape(lambda: jax.random.key(0)))
    lanes = shaped((slots,), jnp.int32)
    yield "round", serving._step_runner(model, slots, 0, 0.0, "native").lower(
        params, lanes, lanes, cache, lanes, shaped((slots,), jnp.float32), rng)
    scalar = shaped((), jnp.int32)
    prefill = serving._prefill_runner(model, prefix, "native")
    padded = shaped((1, prefix), jnp.int32)
    yield "prefill", prefill.lower(params, padded, scalar)
    row = place(jax.eval_shape(prefill, params, padded, scalar)[1])
    yield "extend", serving._extend_runner(
        model, prefix, suffix, "native").lower(
            params, row, shaped((1, suffix), jnp.int32), scalar, scalar)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to hash")
    parser.add_argument("--only", default="", help="v5e or tiny")
    args = parser.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp

    from parameter_server_distributed_tpu.models import generation, transformer
    from parameter_server_distributed_tpu.ops.pallas import (
        full_decode, fused_attention, latent_decode, ssd_decode)
    from perfbench import families

    assert transformer.__file__.startswith(tree), transformer.__file__
    jax.config.update("jax_enable_compilation_cache", False)

    def configs():
        for name, sizes in V5E.items():
            with open(os.path.join(tree, "perfbench", "configs",
                                   name + ".json")) as handle:
                yield name, json.load(handle), sizes

    def emit(group, name, arm, program, lowered):
        print(json.dumps({"set": group, "config": name, "arm": arm,
                          "program": program, **digests(lowered)}), flush=True)

    def fresh_runners():
        generation._RUNNERS = type(generation._RUNNERS)()

    if args.only in ("", "tiny"):
        for name, config, _ in configs():
            family = families.of(config)
            model = family.model(family.tiny(config), remat=False)
            params = jax.eval_shape(lambda: family.make_weights(model, 1))
            fresh_runners()
            for program, lowered in programs(model, params, 4, 128,
                                             lambda tree: tree, 64, 32):
                emit("tiny", name, "cpu", program, lowered)
            emit("tiny", name, "cpu", "train", jax.jit(jax.grad(
                model.loss)).lower(params, jax.ShapeDtypeStruct(
                    (2, 64), jnp.int32)))
    if args.only in ("", "v5e"):
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def place(tree):
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=chip), tree)

        for module in (full_decode, fused_attention, latent_decode,
                       ssd_decode):
            module.interpret_mode = lambda *_: False
        for name, config, (layers, slots, max_len) in configs():
            family = families.of(config)
            model = family.model(config, remat=False, n_layers=layers)
            params = place(jax.eval_shape(
                lambda: family.make_weights(model, 1)))
            for arm in ("kernel", "plain"):
                transformer._kernel_backend = lambda arm=arm: arm == "kernel"
                fresh_runners()
                for program, lowered in programs(model, params, slots,
                                                 max_len, place, PREFIX,
                                                 SUFFIX):
                    emit("v5e", name, arm, program, lowered)
                if name == "gpt2-medium":
                    emit("v5e", name, arm, "train", jax.jit(jax.grad(
                        model.loss)).lower(params, place(
                            jax.ShapeDtypeStruct((64, 1024), jnp.int32))))


if __name__ == "__main__":
    main()
