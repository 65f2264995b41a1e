"""The kernel of ``ops/pallas/ssd_decode.py`` (an ssm layer's single
position over the matrix where it lies, for the lanes that hold a request)
against ``ops/ssd._one_position``, interpreted on the CPU: a live lane's
state and result are the plain pass's, an idle lane's matrix is bit for bit
what it was and its result zeros; and the one rule that says when it runs
(``transformer.round_arm``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_distributed_tpu.models import transformer
from parameter_server_distributed_tpu.ops import ssd as ssd_module
from parameter_server_distributed_tpu.ops.pallas import ssd_decode

# lanes, heads, a head's values, the state's width, groups: a small one
# (two groups, three steps of two heads a lane) and the cell's own block (a
# whole lane of Granite 4.0-H's: 64 heads of 64 over 128, 2 MB a step)
SHAPES = {"small": ((6, 6, 8, 128, 2), 2),
          "granite_block": ((4, 64, 64, 128, 1), None)}
LIVE = {"all": lambda lanes: np.ones(lanes, bool),
        "some_scattered": lambda lanes: np.arange(lanes) % 3 != 1,
        "one": lambda lanes: np.arange(lanes) == 1,
        "the_last_lane_only": lambda lanes: np.arange(lanes) == lanes - 1}


def _inputs(shape, seed=0):
    lanes, heads, dim, width, groups = shape
    keys = jax.random.split(jax.random.key(seed), 5)
    written = jax.random.normal(keys[0], (lanes, heads, dim), jnp.float32)
    fall = -jnp.abs(jax.random.normal(keys[1], (lanes, heads), jnp.float32))
    b, c = (jax.random.normal(key, (lanes, groups, width), jnp.float32)
            for key in keys[2:4])
    state = jax.random.normal(keys[4], (lanes, heads, dim, width),
                              jnp.float32)
    return written, fall, b, c, state


def _plain(written, fall, b, c, state, live):
    """``_one_position`` with an idle lane's step zero, by head."""
    lanes, heads, dim, width = state.shape
    by_group = (b.shape[1], heads // b.shape[1])
    y, after = jax.jit(ssd_module._one_position)(
        jnp.where(live[:, None, None], written, 0.0).reshape(
            lanes, *by_group, dim),
        jnp.where(live[:, None], fall, 0.0).reshape(lanes, *by_group), b, c,
        state.reshape(lanes, *by_group, dim, width))
    return (np.asarray(y).reshape(lanes, heads, dim),
            np.asarray(after).reshape(state.shape))


@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_moves_the_live_lanes_as_one_position_does(shape, live):
    """Live lanes: the plain pass's state and result (the same float32
    products; the sum over the state's width in another order).  Idle
    lanes: the matrix bit for bit what it was, the result zeros."""
    shape, heads = SHAPES[shape]
    written, fall, b, c, state = _inputs(shape)
    mask = LIVE[live](shape[0])
    got_y, got = ssd_decode.ssd_decode(written, jnp.exp(fall), b, c, state,
                                       jnp.asarray(mask), heads=heads)
    got_y, got = np.asarray(got_y), np.asarray(got)
    want_y, want = _plain(written, fall, b, c, state, jnp.asarray(mask))
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y[mask], want_y[mask], rtol=1e-5,
                               atol=1e-4)
    assert np.array_equal(got[~mask], np.asarray(state)[~mask])
    assert np.all(got_y[~mask] == 0)
    assert mask.all() or not np.allclose(want[mask], np.asarray(state)[mask])


def test_no_live_lane_moves_nothing():
    """No lane decodes: every grid step names one block, which goes back
    as it came."""
    written, fall, b, c, state = _inputs(SHAPES["small"][0])
    y, after = ssd_decode.ssd_decode(written, jnp.exp(fall), b, c, state,
                                     jnp.zeros(6, bool), heads=3)
    assert np.array_equal(np.asarray(after), np.asarray(state))
    assert not np.asarray(y).any()


def test_heads_a_step_fill_the_step():
    """A whole lane of Granite's (64 heads of 32 KB) is one step of 2 MB;
    heads twice as large go 32 a step; a number of heads a step divides the
    lane's."""
    assert ssd_decode.heads_a_step(64, 64 * 128 * 4) == 64
    assert ssd_decode.heads_a_step(64, 128 * 128 * 4) == 32
    assert ssd_decode.heads_a_step(12, 1 << 20) == 2
    assert ssd_decode.heads_a_step(7, 1 << 22) == 1


@pytest.mark.parametrize("counts", [(1, 1, 1), (1, 0, 1), (0, 0, 1)])
def test_a_single_position_through_ssd_takes_the_kernel_where_told(counts):
    """``ssd(..., kernel=True)`` at T = 1, two groups of two heads: the
    rows whose position is real read what the plain pass reads; a pad's
    state stays under both, and its result is zeros under the kernel."""
    keys = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(keys[0], (3, 1, 4, 8), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (3, 1, 4)))
    a = -jnp.exp(jax.random.normal(keys[2], (4,)))
    b, c = (jax.random.normal(key, (3, 1, 2, 128)) for key in keys[3:5])
    state = jax.random.normal(keys[5], (3, 4, 8, 128), jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    real = np.asarray(counts) > 0
    want_y, want = ssd_module.ssd(x, dt, a, b, c, state, counts)
    got_y, got = ssd_module.ssd(x, dt, a, b, c, state, counts, kernel=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y)[real],
                               np.asarray(want_y)[real], rtol=1e-5,
                               atol=1e-4)
    assert np.array_equal(np.asarray(got)[~real], np.asarray(state)[~real])
    assert np.array_equal(np.asarray(want)[~real], np.asarray(state)[~real])
    assert not np.asarray(got_y)[~real].any()


MATRIX = (64, 64, 64, 128)


@pytest.mark.parametrize("tpu,x,matrix,dtype,devices,arm", [
    # a round's single token, one TPU device, whole registers
    (True, (64, 1, 64, 64), MATRIX, jnp.float32, 1, "kernel"),
    (True, (4, 1, 12, 8), (4, 12, 8, 128), jnp.float32, 1, "kernel"),
    # an extension's block
    (True, (64, 256, 64, 64), MATRIX, jnp.float32, 1, "plain"),
    # a cache spread over a mesh
    (True, (64, 1, 64, 64), MATRIX, jnp.float32, 4, "plain"),
    # any backend but a TPU
    (False, (64, 1, 64, 64), MATRIX, jnp.float32, 1, "plain"),
    # a state of 16 (the tiny model's), values of 4: no whole registers
    (True, (4, 1, 12, 8), (4, 12, 8, 16), jnp.float32, 1, "plain"),
    (True, (4, 1, 12, 4), (4, 12, 4, 128), jnp.float32, 1, "plain"),
    # the values of another model than the matrix's
    (True, (64, 1, 32, 64), MATRIX, jnp.float32, 1, "plain"),
    # a quantised part
    (True, (64, 1, 64, 64), MATRIX, jnp.int8, 1, "plain"),
])
def test_the_rule_for_an_ssm_round(monkeypatch, tpu, x, matrix, dtype,
                                   devices, arm):
    """``transformer.round_arm``'s ``ssm`` kind, from the shapes, the
    backend and the devices a cache is spread over, and nothing else."""
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: tpu)
    assert transformer.round_arm("ssm", x, matrix, dtype, devices) == arm


def test_one_rule_for_every_kind(monkeypatch):
    """The three kinds answer through the one function: a token on a TPU
    takes each kind's kernel, a block or a mesh its plain form; the two
    older names (the function under a kind, one of them blind to a mesh)
    are gone."""
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: True)
    full = ((8, 1, 32, 64), (8, 2048, 4, 128), jnp.bfloat16)
    latent = ((16, 1, 64, 640), (16, 4096, 640), jnp.bfloat16)
    assert transformer.round_arm("softmax", *full) == "kernel"
    assert transformer.round_arm("latent", *latent) == "kernel"
    assert not hasattr(transformer, "full_decode_arm")
    assert not hasattr(transformer, "latent_decode_arm")
    for kind, shapes, plain in (("softmax", full, "dense"),
                                ("latent", latent, "dense"),
                                ("ssm", ((64, 1, 64, 64), MATRIX,
                                         jnp.float32), "plain")):
        assert transformer.round_arm(kind, *shapes, devices=4) == plain
        several = (shapes[0][0], 5) + shapes[0][2:]
        assert transformer.round_arm(kind, several, *shapes[1:]) == plain
    monkeypatch.setattr(transformer, "_kernel_backend", lambda: False)
    assert transformer.round_arm("softmax", *full) == "dense"
    assert transformer.round_arm("ssm", (64, 1, 64, 64), MATRIX) == "plain"


@pytest.mark.parametrize("counts", [(1, 1, 1), (0, 1, 0), (0, 0, 0)])
def test_a_single_position_shifts_the_register_or_leaves_it(counts):
    """``short_conv`` at T = 1 under ``counts`` (a round's mask on the
    shift register): a real position shifts it by one, a pad leaves it, as
    the block form's gather gives for a block whose first position is the
    only real one."""
    from parameter_server_distributed_tpu.ops.short_conv import short_conv

    keys = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(keys[0], (3, 1, 16), jnp.float32)
    kernel = jax.random.normal(keys[1], (4, 16), jnp.float32)
    state = jax.random.normal(keys[2], (3, 3, 16), jnp.float32)
    counts = jnp.asarray(counts, jnp.int32)
    conv, after = short_conv(x, kernel, state, counts)
    padded = jnp.concatenate([x, jnp.zeros((3, 2, 16), jnp.float32)], axis=1)
    want_conv, want = short_conv(padded, kernel, state, counts)
    assert np.array_equal(np.asarray(after), np.asarray(want))
    np.testing.assert_allclose(np.asarray(conv), np.asarray(want_conv)[:, :1],
                               rtol=1e-6)
    real = np.asarray(counts) > 0
    assert np.array_equal(np.asarray(after)[~real], np.asarray(state)[~real])
    assert np.array_equal(np.asarray(after)[real][:, :-1],
                          np.asarray(state)[real][:, 1:])
