"""The cells' programs compiled for the chip, without the chip: the TPU's
compiler is installed here and compiles for a v5e that is described and
not attached (``jax.experimental.topologies``).  Nothing runs; what is
held is what the compiled decode round DOES with the slot cache, at the
benchmark's real widths, slots and context (depth cut to keep the compile
to seconds): every part of the cache is updated where it lies; and that
the compiled training step holds no ``[B, H, S, S]`` array: its attention
is the blockwise kernel, on one chip and on a shard of the 2 x 2 mesh.

All such tests live in this one file and describe the topology inside a
fixture: only one process may load the TPU's library, and the worker that
is given this file is the one that loads it.
"""

import functools
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
def topology():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

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
    yield topo
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


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


def _take_arm(monkeypatch, arm):
    """Force what ``transformer.round_arm`` answers on this CPU:
    ``kernel`` makes the backend a TPU's and sends the kernels of
    ops/pallas/full_decode.py and ops/pallas/ssd_decode.py through Mosaic
    for the described chip; ``plain`` leaves the einsums and the
    elementwise pass.  (A model a fixture shares keeps its traced runners:
    they are dropped, or the second arm would be handed the first one's
    program.)"""
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import (full_decode,
                                                              ssd_decode)

    monkeypatch.setattr(generation, "_RUNNERS", type(generation._RUNNERS)())
    monkeypatch.setattr(full_decode, "interpret_mode", lambda *_: False)
    monkeypatch.setattr(ssd_decode, "interpret_mode", lambda *_: False)
    monkeypatch.setattr(transformer, "_kernel_backend",
                        lambda: arm == "kernel")


def _full_kernels(compiled) -> int:
    """The kernel's calls in a compiled round (each under
    ``cache_attn/attn/full/attn_kernel``)."""
    return len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*attn/full/attn_kernel',
        compiled.as_text()))


def _compiled_round(model, params, cache, slots, chip):
    """The decode round as ``DecodeServer`` dispatches it: the round
    before's tokens as a device array (``prev``: what that round returned,
    not fetched) beside the host's (``fresh``), lengths and temperatures.
    What it returns first is what the next round takes as ``prev``."""
    def lanes(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=chip)

    rng = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(lambda: jax.random.key(0)))
    lowered = serving._step_runner(model, slots, 0, 0.0, "native").lower(
        params, lanes(jnp.int32), lanes(jnp.int32), cache, lanes(jnp.int32),
        lanes(jnp.float32), rng)
    tokens = lowered.out_info[0]
    assert (tokens.shape, tokens.dtype) == ((slots,), jnp.int32)
    return lowered.compile()


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_the_decode_round_updates_every_part_where_it_lies(cell, monkeypatch,
                                                           arm):
    """With the full layers through ops/pallas/full_decode.py (``kernel``:
    GPT-2's 8 rows of two heads and SmallThinker's 4 rows a position, both
    laid by position and handed to the kernel as they lie) and through
    the einsums (``plain``)."""
    model, params, cache, _, slots, chip = cell
    _take_arm(monkeypatch, arm)
    compiled = _compiled_round(model, params, cache, slots, chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert aliased >= parts
    assert moved == []
    full = sum(not model.config.layer_spec(i).window
               for i in range(model.config.n_layers))
    assert _full_kernels(compiled) == (full if arm == "kernel" else 0)
    # the round's temporaries are activations, not copies of the cache
    # (four padded copies of it made them three times the cache, PR 28)
    assert temporaries < cache_bytes / 4


def test_the_round_told_its_idle_lanes_keeps_its_grouped_matmuls(cell):
    """SmallThinker's round reads which lanes hold a request (PR 60: an
    idle lane's assignments belong to no group) and is still three grouped
    matmuls an experts layer over the same static rows, 16 slots x top-6 =
    96 (12 calls at the 4 layers compiled here, 24 at the cell's 8): nothing
    is dropped, nothing is sized by occupancy, and no cache part is copied
    for the mask.  GPT-2's round holds none and is not told."""
    model, params, cache, _, slots, chip = cell
    c = model.config
    experts = sum(c.layer_spec(i).ffn == "experts" for i in range(c.n_layers))
    assert bool(serving._mask_layers(model)) == bool(experts)
    compiled = _compiled_round(model, params, cache, slots, chip)
    calls = re.findall(r"%ragged-dot-none[.\d]* = f32\[(\d+),",
                       compiled.as_text())
    assert calls == [str(slots * c.moe_top_k)] * 3 * experts
    aliased, parts, moved, _, _ = _held(compiled, cache)
    assert aliased >= parts
    assert moved == []


def test_an_admission_splices_its_row_where_the_slot_lies(cell):
    model, _, cache, row, _, chip = cell
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = serving._splice_runner(model, 512, "native").lower(
        cache, row, scalar, scalar).compile()
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert aliased >= parts
    assert moved == []
    assert temporaries < cache_bytes / 4


def test_minicpm_salas_round_gathers_and_updates_in_place(one_chip):
    """``serve_longdocs_chat_minicpm_sala``'s decode round at its real
    widths, 16 slots x 65,536 positions, three layers (sparse, linear,
    linear): every part of the cache (K, V, compressed keys, states) is
    updated where it lies, and nothing as large as a state part, let alone
    a K/V part, is copied or sliced: the sparse layer gathers its 128
    block places from the part as it is stored."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "minicpm-sala-12l.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=3)
    slots, max_len = 16, 65536

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    assert [x.shape for x in cache.k] == [(16, 2, 65536, 128)]
    assert [x.shape for x in cache.ck] == [(16, 2, 4096, 128)]
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((16, 32, 128, 128), jnp.float32)]] * 2
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 5 and aliased >= parts
    # (_held's threshold is the smallest part: a state, 8.4M elements)
    assert moved == []
    assert temporaries < cache_bytes / 4


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_lfm2s_round_updates_k_v_in_place_beside_its_conv_states(
        one_chip, monkeypatch, arm):
    """``serve_manychat_lfm2_24b_a2b``'s decode round at its real widths,
    64 slots x 4,096 positions, four layers (conv and conv with the dense
    SwiGLU, then attention and conv with 64 experts): the attention
    layer's K and V are updated where they lie and nothing as large as one
    of them is copied or sliced.  A conv layer's state is a shift register:
    a round rewrites all of it (0.5 MB a layer), and the compiler stages it
    in fast memory first, which is the three copies of exactly a state's
    size that are let through here.  ``kernel``: the attention layer
    through ops/pallas/full_decode.py, 4 rows of two heads a position."""
    _take_arm(monkeypatch, arm)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "lfm2-24b-a2b-10l.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=4)
    slots, max_len = 64, 4096

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/mlp/w1"].shape == (2048, 11776)
    assert params["layer2/moe/w1"].shape == (64, 2048, 1536)
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    # 8 K/V heads of 64, two to a row of 128 lanes
    assert [x.shape for x in cache.k] == [(64, 4096, 4, 128)]
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((64, 2, 2048), jnp.bfloat16)]] * 3
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 5 and aliased >= parts
    state = 64 * 2 * 2048
    assert [op for op in moved if op[2] != state or op[0] != "copy"] == []
    assert len(moved) <= 3
    assert temporaries < cache_bytes / 4
    assert _full_kernels(compiled) == (arm == "kernel")


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_kimi_linears_round_copies_no_latent_and_no_matrix_part(
        one_chip, monkeypatch, arm):
    """``serve_agents_kimi_linear_ep8``'s decode round at its real widths,
    64 slots x 16,384 positions, five layers (the dense KDA layer, two KDA
    layers, an MLA layer and a KDA layer over 32 of 256 experts): the MLA
    layer's rows (640 lanes: 512 + 64 padded to whole registers) and the
    KDA layers' matrices are updated where they lie and nothing as large
    as one of them is copied or sliced; the absorbed attention reads the
    rows as they lie, on the chip through the kernel of
    ops/pallas/latent_decode.py (``kernel``: Mosaic takes it at these
    shapes and no [64, 32, 16384] scores are kept) and elsewhere in plain
    XLA.  A KDA layer's shift register is rewritten whole by a round
    (4.7 MB a layer) and the compiler stages it, as LFM2's: the copies of
    exactly a register's size are let through, and one of ``wkv_b`` (4 MB:
    its split by head)."""
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import latent_decode

    monkeypatch.setattr(latent_decode, "interpret_mode", lambda *_: False)
    monkeypatch.setattr(transformer, "_kernel_backend",
                        lambda: arm == "kernel")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "kimi-linear-48b-a3b-12l-ep8.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=5)
    slots, max_len = 64, 16384

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/attn/conv_q"].shape == (4, 4096)
    assert params["layer3/attn/wkv_b"].shape == (512, 8192)
    assert params["layer1/moe/w1"].shape == (32, 2304, 1024)
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    assert cache.k == () and [x.shape for x in cache.latent] == [
        (64, 16384, 640)]
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((64, 3, 12288), jnp.bfloat16),
             ((64, 32, 128, 128), jnp.float32)]] * 4
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 9 and aliased >= parts
    register, up = 64 * 3 * 12288, 512 * 8192
    assert [op for op in moved
            if op[0] != "copy" or op[2] not in (register, up)] == []
    assert len(moved) <= 4 + 1
    assert temporaries < cache_bytes / 4
    kernels = compiled.as_text().count("latent/cache/attn_kernel")
    if arm == "kernel":
        # the scores of 64 lanes x 32 heads x 16,384 positions (134 MB in
        # float32) live a block at a time in the kernel's own memory
        assert kernels > 0 and temporaries < 64 * 32 * 16384 * 4
    else:
        assert kernels == 0


def _deepseek(one_chip, layers):
    """(family, model of ``layers`` layers, shapes of its weights placed on
    the chip, the placing) of ``deepseek-v3-5l-ep16``."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "deepseek-v3-5l-ep16.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=layers)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/attn/wq_b"].shape == (1536, 128 * 192)
    assert params["layer1/moe/w1"].shape == (16, 7168, 2048)
    return family, model, params, placed


def test_deepseek_v3s_round_copies_no_latent_part_at_128_heads(
        one_chip, monkeypatch):
    """``serve_docs_deepseek_v3_ep16``'s decode round at its real widths,
    32 slots x 16,384 positions, the dense layer and an expert layer (16 of
    256 experts, 4 of 8 groups): both layers' rows (640 lanes) are updated
    where they lie, rotated, and nothing as large as one of them is copied
    or sliced; Mosaic takes the kernel of ops/pallas/latent_decode.py at
    128 heads (its [128, 1024] float32 scores and probabilities a block
    fit its memory) and no [32, 128, 16384] scores are kept.  A copy of
    ``wkv_b``'s size is its split by head, as Kimi Linear's."""
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import latent_decode

    monkeypatch.setattr(latent_decode, "interpret_mode", lambda *_: False)
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    _, model, params, placed = _deepseek(one_chip, 2)
    slots, max_len = 32, 16384
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    assert cache.k == () and cache.state == () and [
        x.shape for x in cache.latent] == [(32, 16384, 640)] * 2
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 2 and aliased >= parts
    assert [op for op in moved
            if op[0] != "copy" or op[2] != 512 * 128 * 256] == []
    assert temporaries < cache_bytes / 4
    assert temporaries < 32 * 128 * 16384 * 4
    text = compiled.as_text()
    assert text.count("latent/cache/attn_kernel") > 0
    for scope in ("latent/q", "latent/rows", "latent/absorb",
                  "moe/router/group_limit"):
        assert scope in text, scope


def test_deepseek_v3s_extension_expands_by_key_block(one_chip):
    """The program that extends the 14,336-position document by a turn of
    256 (``serving._extend_runner``: a one-slot cache of 14,592 rows), at
    the real widths: K and V of 128 heads are made a key block at a time
    inside the blockwise loop (``latent/expand`` inside a ``while``), never
    [1, 14592, 128, 192] (717 MB each), and the program's temporaries stay
    under 1.0 GB."""
    _, model, params, placed = _deepseek(one_chip, 2)
    pbucket, sbucket = 14336, 256
    row = placed((serving._no_layers(pbucket), serving._no_layers(pbucket),
                  jax.ShapeDtypeStruct((2, pbucket, 640), jnp.bfloat16)))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = serving._extend_runner(model, pbucket, sbucket, "native").lower(
        params, row, jax.ShapeDtypeStruct((1, sbucket), jnp.int32,
                                          sharding=one_chip),
        scalar, scalar).compile()
    text = compiled.as_text()
    assert re.search(r"attn/latent/while/[\w/]*/expand/", text)
    whole = (pbucket + sbucket) * 128 * 192
    assert [op for op in _entry_operations(text) if op[2] >= whole] == []
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_olmo_hybrids_round_updates_k_v_and_matrices_where_they_lie(
        one_chip, monkeypatch, arm):
    """``serve_reasoning_olmo_hybrid``'s decode round at its real widths, 12
    slots x 4,096 positions, one whole period (three gdn layers and a full
    one): the full layer's K and V (30 heads of 128, 755 MB together) and
    the gdn layers' matrices [12, 30, 96, 192] float32 are updated where
    they lie and nothing as large as one of them is copied, sliced or
    turned around; the single token takes the one-position recurrence, so
    no triangular solve is in the round.  A gdn layer's shift register is
    rewritten whole by a round (0.8 MB a layer) and the compiler may stage
    it, as LFM2's and Kimi Linear's: copies of exactly a register's size are
    let through.  ``kernel``: the full layer through
    ops/pallas/full_decode.py, which takes the parts turned to [12, 30,
    4096, 128]: the bytes as the device lays them, so the turn is no
    operation and nothing is copied in front of the kernel."""
    _take_arm(monkeypatch, arm)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "olmo-hybrid-7b-16l.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=4)
    slots, max_len = 12, 4096

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/attn/conv_v"].shape == (4, 5760)
    assert params["layer0/attn/wz"].shape == (3840, 5760)
    assert params["layer3/attn/q_norm/scale"].shape == (3840,)
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    assert [x.shape for x in cache.k] == [(12, 4096, 30, 128)]
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((12, 3, 11520), jnp.bfloat16),
             ((12, 30, 96, 192), jnp.float32)]] * 3
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 2 + 6 and aliased >= parts
    register = 12 * 3 * 11520
    assert [op for op in moved
            if op[0] != "copy" or op[2] != register] == []
    assert temporaries < cache_bytes / 4
    assert "triangular" not in compiled.as_text()
    # 30 rows of heads a position: the device lays the part by head, and
    # the round writes a head's row an index (generation._lies_by_head)
    assert generation._lies_by_head(30)
    assert re.search(r"cache_k_0_\S* = bf16\[12,4096,30,128\]\{3,1,2,0",
                     compiled.as_text())
    assert _full_kernels(compiled) == (arm == "kernel")
    # an admission: a row of 2,048 + 256 positions and its snapshot
    bucket = 2048 + 256
    row = placed((
        jax.ShapeDtypeStruct((1, bucket, 30, 128), jnp.bfloat16),) * 2 + (
        jax.ShapeDtypeStruct((3, 11520), jnp.bfloat16),
        jax.ShapeDtypeStruct((30, 96, 192), jnp.float32)) * 3)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    spliced = serving._splice_runner(model, bucket, "native").lower(
        cache, row, scalar, scalar).compile()
    aliased, parts, moved, temporaries, _ = _held(spliced, cache)
    assert aliased >= parts and moved == []
    assert temporaries < cache_bytes / 4


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_granites_round_updates_its_matrices_in_one_pass_where_they_lie(
        one_chip, monkeypatch, arm):
    """``serve_manychat_granite_4_h_micro``'s decode round at its real
    widths, 64 slots x 2,048 positions, one whole period (nine ssm layers
    around an attention layer): the ssm layers' matrices [64, 64, 64, 128]
    float32 (134 MB each) and the attention layer's K and V are updated
    where they lie and nothing as large as one of them is copied, sliced or
    turned around; the single token takes the one-position recurrence (no
    cumulative sum, no loop), and a layer's decay, write and read are ONE
    pass over its matrix: a round moves a state once each way.  ``plain``:
    one fusion a layer over the matrix of every lane.  ``kernel``: one
    call a layer of ops/pallas/ssd_decode.py, which takes the matrix in
    and gives it back as the SAME buffer (the call's own alias) and moves
    the lanes that hold a request alone; no fusion over a matrix is left;
    the attention layer (8 K/V heads of 64, two a row: LFM2's rows) goes
    through ops/pallas/full_decode.py.  A layer's shift register is
    rewritten whole by a round (1.7 MB a layer) and the compiler may stage
    it, as the delta-rule models': copies of exactly a register's size are
    let through."""
    _take_arm(monkeypatch, arm)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "granite-4.0-h-micro.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=10)
    slots, max_len = 64, 2048

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/ssm/in_proj"].shape == (2048, 8512)
    assert params["layer0/ssm/conv/bias"].shape == (4352,)
    assert params["layer5/attn/wk"].shape == (2048, 512)
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    assert [x.shape for x in cache.k] == [(64, 2048, 4, 128)]
    assert [[(x.shape, x.dtype) for x in layer] for layer in cache.state] \
        == [[((64, 3, 4352), jnp.bfloat16),
             ((64, 64, 64, 128), jnp.float32)]] * 9
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 2 + 18 and aliased >= parts
    register = 64 * 3 * 4352
    assert [op for op in moved
            if op[0] != "copy" or op[2] != register] == []
    assert temporaries < cache_bytes / 4
    text = compiled.as_text()
    assert "cumsum" not in text and " while(" not in text
    # one operation a layer takes a matrix in and gives it back
    entry = text.split("\nENTRY")[1]
    fusions = [line for line in entry.splitlines()
               if " fusion(" in line and "f32[64,64,64,128]" in line]
    calls = [line for line in entry.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "f32[64,64,64,128]" in line]
    updates = calls if arm == "kernel" else fusions
    assert (len(fusions), len(calls)) == ((0, 9) if arm == "kernel"
                                          else (9, 0))
    assert all("attn/linear/ssd" in line for line in updates)
    # the kernel's matrix operand (the seventh, behind the lanes' order
    # and count, the columns, the key and the query) is its second result
    assert all("output_to_operand_aliasing={{1}: (6, {})}" in line
               for line in calls)
    assert _full_kernels(compiled) == (arm == "kernel")


@pytest.mark.parametrize("heads,head_dim", [(12, 64), (20, 64), (8, 64),
                                            (16, 128)])
def test_a_round_writes_k_and_v_as_the_device_lays_them(one_chip, heads,
                                                        head_dim):
    """``generation._lies_by_head`` against the compiler, on GPT-2's block
    at other head counts (two layers, 12 slots x 1,024): 12 and 20 heads of
    64 (a served gpt2-small, gpt2-large) are 6 and 10 rows of heads a
    position, which the device lays BY HEAD, and the round writes a row an
    index; 8 heads of 64 and 16 of 128 are 4 and 16 rows, laid by position,
    written a window a slot.  Either way the part is updated where it lies
    and nothing as large as it is copied (by the other write, eight copies
    of a part a round: PERF.md section 6, PR 50)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "gpt2-medium.json")) as handle:
        config = dict(json.load(handle), n_head=heads,
                      n_embd=heads * head_dim)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=2)
    slots = 12

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, 1024)))
    rows = cache.k[0].shape[2]
    assert cache.k[0].shape == (12, 1024, rows, 128)
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert aliased >= parts and moved == []
    assert temporaries < cache_bytes / 4
    # (the parts as the round is handed them: its parameters)
    layouts = set(re.findall(
        r"bf16\[12,1024,%d,128\]\{([\d,]+)[^ ]* parameter\(" % rows,
        compiled.as_text().split("\nENTRY")[1]))
    assert layouts == {"3,1,2,0" if generation._lies_by_head(rows)
                       else "3,2,1,0"}
    assert generation._lies_by_head(rows) == (rows in (6, 10))


@pytest.mark.parametrize("arm", ["kernel", "plain"])
def test_k_exaones_round_updates_rings_of_128_where_they_lie(
        one_chip, monkeypatch, arm):
    """``serve_chat_k_exaone_ep8``'s decode round at its real widths, 32
    slots x 4,096 positions, four layers (the dense one and two expert
    layers over rings of 128, one expert layer over full attention), 16 of
    128 experts held: the rings and the full layer's K and V are updated
    where they lie, nothing as large as a ring is copied or sliced, and the
    grouped matmul's weights are the held experts' alone.  ``kernel``: the
    full layer through ops/pallas/full_decode.py, 8 rows of heads a
    position, 8 query heads a row."""
    _take_arm(monkeypatch, arm)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "k-exaone-236b-a23b-8l-ep8.json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, remat=False, n_layers=4)
    slots, max_len = 32, 4096

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: family.make_weights(model, 1)))
    assert params["layer0/mlp/w1"].shape == (6144, 18432)
    assert params["layer1/moe/w1"].shape == (16, 6144, 2048)
    assert params["layer1/moe/router/w"].shape == (6144, 128)
    assert params["layer1/moe/shared/w1"].shape == (6144, 2048)
    cache = placed(jax.eval_shape(
        lambda: generation.init_cache(model, slots, max_len)))
    # 8 K/V heads of 128, one to a row of 128 lanes
    assert [x.shape for x in cache.k] == [(32, 4096, 8, 128)]
    assert [x.shape for x in cache.wk] == [(32, 128, 8, 128)] * 3
    compiled = _compiled_round(model, params, cache, slots, one_chip)
    aliased, parts, moved, temporaries, cache_bytes = _held(compiled, cache)
    assert parts == 8 and aliased >= parts
    # (the compiler turns ONE layer's query projection round, and in this
    # cut of four layers one key projection: a copy of a weight, 100 MB
    # and 12.6 MB a round, 0.3 ms; no part of the cache is among them)
    weights = {6144 * 8192, 6144 * 1024}
    assert [op for op in moved if op[0] != "copy" or op[2] not in weights] \
        == []
    assert len(moved) <= 2
    assert temporaries < cache_bytes / 4
    assert _full_kernels(compiled) == (arm == "kernel")
    # three grouped matmuls an expert layer, each over [16, ...] weights
    text = compiled.as_text()
    assert len(re.findall(r"bf16\[16,6144,2048\]", text)) > 0
    assert "bf16[128,6144,2048]" not in text


@pytest.mark.parametrize("tpu,q,part,dtype,arm", [
    (True, (12, 1, 30, 128), (12, 4096, 30, 128), jnp.bfloat16, "kernel"),
    (True, (32, 1, 64, 128), (32, 4096, 8, 128), jnp.bfloat16, "kernel"),
    (True, (16, 1, 28, 128), (16, 16384, 4, 128), jnp.bfloat16, "kernel"),
    (True, (64, 1, 32, 64), (64, 4096, 4, 128), jnp.bfloat16, "kernel"),
    (True, (32, 1, 16, 64), (32, 1024, 8, 128), jnp.float32, "kernel"),
    # several tokens a lane: an extension, a speculative verify
    (True, (12, 4, 30, 128), (12, 4096, 30, 128), jnp.bfloat16, "dense"),
    (True, (32, 1, 16, 64), (32, 1024, 8, 128), jnp.int8, "dense"),
    (False, (12, 1, 30, 128), (12, 4096, 30, 128), jnp.bfloat16, "dense"),
    # max_len not in whole blocks; rows narrower than a register (a head
    # of 48 lanes alone in its row); a tiny test's shapes
    (True, (12, 1, 30, 128), (12, 4000, 30, 128), jnp.bfloat16, "dense"),
    (True, (4, 1, 6, 48), (4, 1024, 6, 48), jnp.bfloat16, "dense"),
    (True, (2, 1, 4, 8), (2, 64, 1, 32), jnp.float32, "dense")])
def test_full_decode_arms_table(monkeypatch, tpu, q, part, dtype, arm):
    """``transformer.round_arm``'s ``softmax`` kind (a FULL layer's K or V)
    from the shapes and the backend: a
    round's single token a lane on a TPU against an unquantised part in
    whole blocks and whole registers takes the kernel, at the five serving
    cells' shapes; everything else the einsums, never an error."""
    from parameter_server_distributed_tpu.models import transformer

    monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
    assert transformer.round_arm("softmax", q, part, dtype) == arm


# configuration, mesh axes: the two training cells (64 x 1,024 tokens a step)
STEPS = {"one-chip": ("gpt2-medium", {}),
         "fsdp2-tensor2": ("gpt2-large", {"fsdp": 2, "tensor": 2})}
BATCH, SEQ = 64, 1024


KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
COLLECTIVE = (r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute)(-start)?\(")


def _moved_over_tensor(text, axes, model):
    """The ``op_name`` of every all-reduce of a shard's activations
    ``[B / fsdp, S, d]`` over the ``tensor`` pairs in a step's text (the
    mesh lays fsdp outermost: chips 0,1 and 2,3 are the pairs)."""
    return [name for groups, name in re.findall(
        r"= \w+\[%d,%d,%d\]\S* all-reduce\(.*?replica_groups=(\S+?), "
        r".*?op_name=\"([^\"]*)\"" % (
            BATCH // axes.fsdp, SEQ, model.config.d_model), text)
        if groups in ("[2,2]<=[4]", "{{0,1},{2,3}}")]


@functools.cache
def _step_text(topology, layout, on_tpu, keeps=True):
    """The text of ``jit(step)`` of the cell's widths (2 layers, 64 x
    1,024, scan + remat, Adam) compiled for the described v5e, with the
    attention rule's one question about the backend answered ``on_tpu``;
    with it the configuration's mesh axes, the model and the bytes of the
    program's temporaries on a chip.  ``keeps`` false: with the layer's
    ``jax.checkpoint`` keeping nothing and no value named, whatever the
    mesh.  One compile a (layout, answer, keeps) for the file's tests."""
    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import fused_attention
    from parameter_server_distributed_tpu.parallel.mesh import (
        batch_sharding, build_mesh)
    from parameter_server_distributed_tpu.parallel.train_step import (
        TrainState, make_optimizer, make_train_step, state_shardings)

    name, axes = STEPS[layout]
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as handle:
        config = json.load(handle)
    family = families.of(config)
    model = family.model(config, n_layers=2)
    config_axes = MeshConfig(**axes)
    mesh = build_mesh(config_axes,
                      devices=topology.devices[:config_axes.num_devices])
    model.on_mesh(mesh)
    optimizer = make_optimizer("adam", 3e-4)
    state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer),
        jax.eval_shape(lambda: family.make_weights(model, 1)))
    shardings = state_shardings(state, mesh,
                                transformer.transformer_rule(mesh))
    placed = jax.tree.map(
        lambda x, sharding: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                 sharding=sharding),
        state, shardings)
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32,
                                  sharding=batch_sharding(mesh))
    with pytest.MonkeyPatch.context() as patch:
        # the chip is described, not attached: the backend here is the
        # CPU, so the test answers the rule's one question about the
        # backend itself
        patch.setattr(fused_attention, "interpret_mode", lambda *_: False)
        patch.setattr(transformer, "_kernel_backend", lambda: on_tpu)
        if not keeps:
            patch.setattr(transformer.Transformer, "_remat_policy",
                          lambda self: None)
            for module in (transformer, fused_attention):
                patch.setattr(module, "checkpoint_name", lambda x, _: x)
        compiled = jax.jit(
            make_train_step(model.loss, optimizer),
            in_shardings=(shardings, batch_sharding(mesh)),
            donate_argnums=0).lower(placed, tokens).compile()
    return (compiled.as_text(), config_axes, model,
            compiled.memory_analysis().temp_size_in_bytes)


@pytest.mark.parametrize("layout", sorted(STEPS))
def test_the_training_step_holds_no_score_tensor(topology, layout):
    """The compiled step: with the default rule its attention is THREE
    kernel calls (forward, dQ, dK/dV: the layer's ``jax.checkpoint`` keeps
    the forward's output and row sums, so the rematerialised forward runs
    no kernel, on one chip and under ``shard_map`` on a shard of the mesh)
    and no array of a shard's ``[B, H, S, S]`` exists in any dtype; with
    the rule forced to the einsum, the same search finds them."""
    found = {}
    for on_tpu in (True, False):
        text, axes, model, _ = _step_text(topology, layout, on_tpu)
        scores = re.compile(r"\w+\[%d,%d,%d,%d\]" % (
            BATCH // axes.fsdp, model.config.n_heads // axes.tensor,
            SEQ, SEQ))
        found[on_tpu] = (len(scores.findall(text)),
                         text.count(KERNEL_CALL))
    assert found[True] == (0, 3)
    assert found[False][0] > 0 and found[False][1] == 0


@pytest.mark.parametrize("layout", sorted(STEPS))
def test_q_k_v_reduce_their_input_gradient_over_tensor_once(topology,
                                                            layout):
    """What the compiled step's scanned layer moves between chips.  On
    ``fsdp 2 x tensor 2`` a layer's body holds FOUR all-reduces of a
    shard's activations over the ``tensor`` pairs, Megatron's floor for
    the layout: ``wo``'s and ``w2``'s partial sums in the forward loop;
    in the backward loop ``w1``'s input gradient and ONE under
    ``attn_qkv``: q, k and v are one contraction there, whose transpose
    is one dot (three dots paid three).  The remat forward repeats none:
    ``w2``'s is dead code there and ``wo``'s reduced output is kept
    across the backward (``Transformer._remat_policy``).  On one chip the
    program holds no collective and the projections are today's three
    dots."""
    text, axes, model, _ = _step_text(topology, layout, True)
    forward = "/jvp()/while/body/closed_call/"
    backward = "/transpose(jvp())/while/body/closed_call/checkpoint/"
    dots = [line for line in text.splitlines()
            if " convolution(" in line
            and forward + 'attn_qkv/dot_general"' in line]
    if axes.tensor == 1:
        assert not re.search(COLLECTIVE, text)
        assert len(dots) == 3
        return
    moved = _moved_over_tensor(text, axes, model)
    assert len(dots) == 1
    assert len([name for name in moved if "attn_qkv" in name]) == 1
    assert sorted(name.split("closed_call/")[-1] for name in moved
                  if forward in name) == [
        "attn_out/dot_general", "mlp/dot_general"]
    assert sorted(name.split("checkpoint/")[-1] for name in moved
                  if backward in name) == [
        "attn_qkv/dot_general", "mlp/dot_general"]
    assert not [name for name in moved if "rematted_computation" in name]
    assert len(moved) == 4


def _stacked_a_layer(text, rows):
    """(dtype, shape a layer) of every array of a shard's activations
    that a loop of the 2-layer step stacks a layer at a time: what the
    forward loop keeps for the backward one."""
    return sorted(
        (dtype, tuple(map(int, dims.split(","))))
        for dtype, dims in re.findall(
            r"= (\w+)\[2,(%d,[\d,]+)\]\S* dynamic-update-slice\(" % rows,
            text))


@pytest.mark.parametrize("layout", sorted(STEPS))
def test_full_remat_keeps_what_is_not_arithmetic_to_repeat(topology, layout):
    """What the layer's ``jax.checkpoint`` keeps follows the arm and the
    mesh, held against the bare step (policy ``None``, no value named),
    which stacks a layer's input and nothing else.  Wherever the blockwise
    kernel attends, also its output ``o`` in the model's dtype and its
    rows' logsumexp in float32 (134.2 + 4.2 MB a layer on one chip, 41.9 +
    1.3 MB on a shard of ``fsdp 2 x tensor 2``, out of the ``shard_map``
    as they lie): the bare step's four kernel calls become three, the
    rematerialised forward's is gone and no dot with it on one chip (the
    same number of products either way).  On ``fsdp 2 x tensor 2`` also
    the mixer branch's output in the model's dtype, 84 MB a layer and
    chip, which takes the remat forward's all-reduce (and ``wo``'s dot
    with it) out of the step: five all-reduces a layer become the four of
    ``test_q_k_v_reduce_their_input_gradient_over_tensor_once``.  Neither
    a float32 dot result (twice the bytes) nor q, k and v (three times
    ``o``) nor the FFN branch's output (dead in the remat forward anyway)
    is stacked.  The program's temporaries at 2 layers grow by no more
    than the kept arrays of two layers and a tenth on one chip (where
    they do not grow at all: at 2 layers the LM head's logits are the
    peak) and a quarter on four (a 2-layer program's peak is not the sum
    of what its loops carry; at the cell's 36 layers the step holds 36 x
    (o + lse) more than with ``MIXER_OUT`` alone, to the MB: PERF.md,
    PR 51)."""
    text, axes, model, temporaries = _step_text(topology, layout, True)
    bare, _, _, bare_temporaries = _step_text(topology, layout, True, False)
    config = model.config
    assert (bare.count(KERNEL_CALL), text.count(KERNEL_CALL)) == (4, 3)
    rows, item = BATCH // axes.fsdp, jnp.dtype(config.dtype).itemsize
    dtype = {2: "bf16", 4: "f32"}[item]
    layer_input = (dtype, (rows, SEQ, config.d_model))
    o = (dtype, (rows, SEQ, config.d_model // axes.tensor))
    lse = ("f32", (rows, config.n_heads // axes.tensor // 2, 2, SEQ))
    assert _stacked_a_layer(bare, rows) == [layer_input]
    kept = [o, lse]
    if axes.tensor == 1:
        assert not re.search(COLLECTIVE, text)
        assert text.count(" convolution(") == bare.count(" convolution(")
    else:
        assert (len(_moved_over_tensor(bare, axes, model)),
                len(_moved_over_tensor(text, axes, model))) == (5, 4)
        kept.append(layer_input)        # MIXER_OUT: a shard's [B, S, d]
    assert _stacked_a_layer(text, rows) == sorted([layer_input] + kept)
    kept_bytes = sum(np.prod(shape) * (4 if kind == "f32" else item)
                     for kind, shape in kept)
    assert kept_bytes == (138_412_032 if axes.tensor == 1 else 127_139_840)
    room = 1.1 if axes.tensor == 1 else 1.25
    assert temporaries - bare_temporaries <= room * 2 * kept_bytes


@pytest.mark.parametrize("heads,kv_heads,d,arm", [
    (16, 16, 64, "kernel"), (28, 4, 128, "kernel"), (8, 4, 64, "blockwise")],
    ids=["64", "128-grouped", "half-a-row"])
def test_ulysses_over_four_chips_attends_by_the_devices_arm(
        topology, monkeypatch, heads, kv_heads, d, arm):
    """``ulysses`` over ``seq:4`` of the described 2 x 2 host, forward and
    backward of one layer's attention at 8,192 positions: between the
    all-to-alls a device holds ``[1, 8192, H/4, D]`` and takes the arm
    ``device_arm`` names for that: the kernel (forward, dQ, dK/dV) where
    its share of the heads fills rows of lanes, the plain-XLA blocks where
    it does not; never a ``[1, H/4, S, S]`` array."""
    from jax.sharding import NamedSharding, PartitionSpec

    from parameter_server_distributed_tpu.config import MeshConfig
    from parameter_server_distributed_tpu.models import transformer
    from parameter_server_distributed_tpu.ops.pallas import fused_attention
    from parameter_server_distributed_tpu.parallel.mesh import build_mesh

    seq = 8192
    mesh = build_mesh(MeshConfig(sequence=4), devices=topology.devices)
    monkeypatch.setattr(fused_attention, "interpret_mode", lambda *_: False)
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    assert transformer.device_arm((1, seq, heads // 4, d),
                                  (1, seq, kv_heads // 4, d)) == arm
    attend = transformer.select_attention("ulysses", mesh)
    split = NamedSharding(mesh, PartitionSpec(None, "seq", None, None))
    q = jax.ShapeDtypeStruct((1, seq, heads, d), jnp.bfloat16, sharding=split)
    k = jax.ShapeDtypeStruct((1, seq, kv_heads, d), jnp.bfloat16,
                             sharding=split)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k).compile(
        ).as_text()
    assert "all-to-all" in text
    assert (text.count(KERNEL_CALL)
            == (3 if arm == "kernel" else 0))
    assert not re.search(r"\w+\[1,%d,%d,%d\]" % (heads // 4, seq, seq), text)
