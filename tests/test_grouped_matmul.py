"""`grouped_matmul` and its VJP against a per-group loop, the work list the
kernels walk (`_plan`) against the pairs counted row by row, and the
rotate-half rotary tables (`default` and YaRN) against numbers written out
by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.ops.grouped_matmul import _plan, grouped_matmul
from dalle_pytorch_tpu.ops.rotary import apply_rotary_half, rotary_cos_sin, rotary_inv_freq


def _loop(lhs, rhs, sizes):
    out, start = jnp.zeros((lhs.shape[0], rhs.shape[-1]), jnp.float32), 0
    for g, size in enumerate(sizes):
        out = out.at[start:start + size].set(lhs[start:start + size] @ rhs[g])
        start += size
    return out


@pytest.mark.parametrize("sizes", [
    (10, 0, 17, 5),    # an empty group, rows past the sizes
    (0, 0, 0, 0),      # nothing routed here at all
    (12, 8, 10, 10),   # the buffer exactly full
    (40, 0, 0, 0),     # everything to one expert
], ids=["empty_group", "no_rows", "full", "one_expert"])
def test_grouped_matmul_and_its_vjp_match_a_loop_over_groups(sizes):
    rows, k, n = 40, 8, 12
    keys = jax.random.split(jax.random.PRNGKey(sum(sizes)), 3)
    lhs = jax.random.normal(keys[0], (rows, k))
    rhs = jax.random.normal(keys[1], (len(sizes), k, n))
    gs = jnp.asarray(sizes, jnp.int32)
    # the cotangent of rows that belong to no group is 0, as the layer's is
    w = jax.random.normal(keys[2], (rows, n)) * (jnp.arange(rows) < sum(sizes))[:, None]
    live = sum(sizes)  # rows past the groups are not specified
    np.testing.assert_allclose(
        grouped_matmul(lhs, rhs, gs)[:live], _loop(lhs, rhs, sizes)[:live], atol=1e-5)
    got = jax.grad(lambda l, r: jnp.sum(grouped_matmul(l, r, gs) * w), (0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(_loop(l, r, sizes) * w), (0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0][:live], want[0][:live], atol=1e-5)
    # a group without rows gets a zero gradient, written by its own pair
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)


# (group sizes, buffer rows, rows a tile): rows past the sizes belong to no group
PLANS = {
    "empty_first": ((0, 10, 17, 5), 40, 8),
    "empty_middle": ((10, 0, 17, 5), 40, 8),
    "empty_last": ((10, 17, 5, 0), 40, 8),
    "two_empty_side_by_side": ((9, 0, 0, 23), 40, 8),
    "empty_where_the_buffer_ends": ((16, 24, 0, 0), 40, 8),
    "no_rows": ((0, 0, 0, 0), 40, 8),
    "every_group_full": ((8, 16, 8, 8), 40, 8),
    # a token step of the generation cell: 58 rows on 6 of 16 held experts
    "token_step": ((0, 0, 21, 0, 0, 9, 0, 1, 0, 0, 0, 14, 0, 0, 11, 2), 512, 128),
}


@pytest.mark.parametrize("empty_groups", [False, True], ids=["rows_products", "drhs"])
@pytest.mark.parametrize("case", PLANS)
def test_the_work_list_names_the_pairs_that_share_a_row(case, empty_groups):
    """The rows products (`gmm_fwd`, `gmm_dlhs`) walk the (tile, group) pairs
    that share a row and no other: a group without rows has no pair, so its
    matrix is never read. `gmm_drhs` gives such a group one pair besides, on
    the tile where it would start: that pair writes its zero matrix."""
    sizes, rows, tm = PLANS[case]
    n_tiles, groups = rows // tm, len(sizes)
    ends = np.cumsum(sizes)
    owner = np.searchsorted(ends, np.arange(ends[-1]), side="right")
    # for the rows products these and no other: none names a group without rows
    want = sorted({(int(r) // tm, int(g)) for r, g in enumerate(owner)})
    if empty_groups:  # and every group is there
        want += [(min(int(ends[g]) // tm, n_tiles - 1), g) for g in range(groups) if not sizes[g]]
        want.sort(key=lambda pair: (pair[1], pair[0]))  # a group's pairs together, in group order
    offsets, pair_group, pair_tile, n_pairs = jax.device_get(
        _plan(jnp.asarray(sizes, jnp.int32), n_tiles, tm, empty_groups=empty_groups))
    n = int(n_pairs[0])
    assert pair_group.shape == pair_tile.shape == (n_tiles + groups - 1,)
    assert list(zip(pair_tile[:n].tolist(), pair_group[:n].tolist())) == want
    # a tile's pairs back to back (`_rows_kernel` zeroes a tile on its first
    # visit), and a group's (`_drhs_kernel` stores on a group's last)
    for walked in (pair_tile[:n], pair_group[:n]):
        assert np.all(np.diff(walked) >= 0)
    # the steps past the last pair name its blocks again, inside the arrays
    last = max(n - 1, 0)
    assert np.all(pair_group[last:] == pair_group[last]) and 0 <= pair_group[last] < groups
    assert np.all(pair_tile[last:] == pair_tile[last]) and 0 <= pair_tile[last] < n_tiles
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))


def test_grouped_matmul_keeps_the_operands_dtype_and_float32_parameters():
    lhs = jnp.ones((16, 8), jnp.bfloat16)
    rhs = jnp.ones((2, 8, 4), jnp.float32)  # parameters stay float32
    gs = jnp.asarray([9, 7], jnp.int32)
    out, vjp = jax.vjp(lambda l, r: grouped_matmul(l, r, gs), lhs, rhs)
    dlhs, drhs = vjp(jnp.ones_like(out))
    assert (out.dtype, dlhs.dtype, drhs.dtype) == (jnp.bfloat16, jnp.bfloat16, jnp.float32)
    np.testing.assert_allclose(drhs[:, 0, 0], [9.0, 7.0])  # each group's own rows


# The published configuration: head_dim 128, theta 500000, YaRN factor 16 over
# 8192 original positions, beta_fast 32, beta_slow 1.
YARN = {"type": "yarn", "dim": 128, "theta": 500000.0, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
DEFAULT = {"type": "default", "dim": 128, "theta": 500000.0}


def test_yarn_inverse_frequencies_are_the_numbers_written_out_by_hand():
    """dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): dim(32) = 18.08,
    dim(1) = 34.98, so low = 18, high = 35 and the ramp is (i - 18) / 17
    clipped to [0, 1]: channels 0..18 keep theta^(-2i/128), channels 35..63
    are divided by 16, channel 26 is 8/17 of the way."""
    plain, yarn = rotary_inv_freq(DEFAULT), rotary_inv_freq(YARN)
    assert plain.shape == yarn.shape == (64,) and plain.dtype == np.float32
    np.testing.assert_allclose(plain[[0, 1, 63]],
                               [1.0, 500000 ** (-2 / 128), 500000 ** (-126 / 128)], rtol=1e-6)
    np.testing.assert_allclose(plain[[1, 63]], [0.81461723, 2.4551e-06], rtol=1e-4)
    np.testing.assert_array_equal(yarn[:19], plain[:19])
    np.testing.assert_allclose(yarn[35:], plain[35:] / 16, rtol=1e-6)
    ramp = 8 / 17
    np.testing.assert_allclose(yarn[26], plain[26] * (ramp / 16 + 1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(yarn[26], 4.8394e-03 * (ramp / 16 + 1 - ramp), rtol=1e-4)


def test_rotary_tables_are_float32_with_yarn_scaled_by_its_attention_factor():
    positions = np.array([0, 1, 8191])
    cos, sin = rotary_cos_sin(positions, YARN)
    assert cos.shape == sin.shape == (3, 128) and cos.dtype == sin.dtype == jnp.float32
    np.testing.assert_allclose(cos[0], np.full(128, 1.2772588722239782), rtol=1e-6)
    np.testing.assert_allclose(sin[0], 0.0, atol=1e-7)
    # position 8191 on the fastest channel: 8191 rad, exact in float32 where a
    # bf16 angle is off by up to 32 rad (PERF.md, fault 1)
    c, s = rotary_cos_sin(positions, DEFAULT)
    np.testing.assert_allclose([c[2, 0], s[2, 0]], [np.cos(8191.0), np.sin(8191.0)], atol=1e-3)
    np.testing.assert_allclose(c[:, :64], c[:, 64:])  # halves share the angle


def test_rotate_half_turns_pairs_of_halves_and_keeps_the_dtype():
    cos, sin = rotary_cos_sin(np.arange(4), {"type": "default", "dim": 8, "theta": 100.0})
    t = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8)).astype(jnp.bfloat16)
    out = apply_rotary_half(cos, sin, t)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(out[:, 0], t[:, 0])  # position 0 is not turned
    x = np.asarray(t[0, 3], np.float32)
    a = 3 * 100.0 ** (-np.arange(0, 8, 2) / 8)
    want = np.concatenate([x[:4] * np.cos(a) - x[4:] * np.sin(a),
                           x[4:] * np.cos(a) + x[:4] * np.sin(a)])
    np.testing.assert_allclose(np.asarray(out[0, 3], np.float32), want, atol=2e-2)
