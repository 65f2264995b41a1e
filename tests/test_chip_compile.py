"""The serving programs compiled for the chip, without the chip: the TPU's
compiler is installed here and compiles for a v5e that is described and
not attached (``jax.experimental.topologies``).  Nothing runs; what is
held is what the compiled decode round DOES with the slot cache, at the
benchmark's real widths, slots and context (depth cut to keep the compile
to seconds): every part of the cache is updated where it lies.

All such tests live in this one file and describe the topology inside a
fixture: only one process may load the TPU's library, and the worker that
is given this file is the one that loads it.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models import generation, serving
from perfbench import families

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# configuration, layers compiled, and the cell's slots and context
CELLS = {"gpt2-medium": (2, 32, 1024),
         "smallthinker-21b-a3b-8l": (4, 16, 16384)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=sorted(CELLS))
def cell(request, one_chip):
    """(model, shapes of its weights, of its slot cache and of an admitted
    row of 512 positions, slots), every shape placed on the one chip."""
    layers, slots, max_len = CELLS[request.param]
    with open(os.path.join(ROOT, "perfbench", "configs",
                           request.param + ".json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=layers)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    c = model.config
    pack = generation.heads_per_row(c.kv_heads, c.head_dim)
    row = jax.ShapeDtypeStruct(
        (layers, 512, c.kv_heads // pack, pack * c.head_dim), c.dtype,
        sharding=one_chip)
    return (model, placed(jax.eval_shape(lambda: family.make_weights(model,
                                                                     1))),
            placed(jax.eval_shape(
                lambda: generation.init_cache(model, slots, max_len))),
            (row, row), slots, one_chip)


def _entry_operations(text):
    """(operation, name, elements) of every line of the ENTRY computation."""
    for line in text.split("\nENTRY")[1].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]*)\][^ ]* "
                     r"([\w\-]+)\(", line)
        if m:
            yield (m.group(3), m.group(1),
                   int(np.prod([int(d) for d in m.group(2).split(",") if d])))


def _held(compiled, cache):
    parts = [x for x in jax.tree.leaves(cache) if x.ndim > 2]
    text = compiled.as_text()
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry",
                        text.splitlines()[0]).group(1)
    smallest = min(int(np.prod(part.shape)) for part in parts)
    moved = [op for op in _entry_operations(text)
             if op[2] >= smallest and op[0] in (
                 "copy", "slice", "dynamic-slice", "transpose", "pad")]
    cache_bytes = sum(int(np.prod(part.shape)) * part.dtype.itemsize
                      for part in parts)
    return (aliased.count("alias)"), len(parts), moved,
            compiled.memory_analysis().temp_size_in_bytes, cache_bytes)


def test_the_decode_round_updates_every_part_where_it_lies(cell):
    model, params, cache, _, slots, chip = cell

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    rng = jax.tree.map(lambda x: on_chip(x.shape, x.dtype),
                       jax.eval_shape(lambda: jax.random.key(0)))
    compiled = serving._step_runner(model, slots, 0, 0.0, "native").lower(
        params, on_chip((slots,), jnp.int32), cache,
        on_chip((slots,), jnp.int32), on_chip((slots,), jnp.float32),
        rng).compile()
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert aliased >= parts
    assert moved == []
    # the round's temporaries are activations, not copies of the cache
    # (four padded copies of it made them three times the cache, PR 28)
    assert temporaries < cache_bytes / 4


def test_an_admission_splices_its_row_where_the_slot_lies(cell):
    model, _, cache, row, _, chip = cell
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = serving._splice_runner(model, 512, "native").lower(
        cache, row, scalar, scalar).compile()
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert aliased >= parts
    assert moved == []
    assert temporaries < cache_bytes / 4
