"""`decode_grouped` (ops/grouped_decode.py), interpreted on the CPU, against
the path it took the place of: `dense_attention` under the mask that
`Attention._cached_grouped` built for a full layer's cached step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.attention import Attention
from dalle_pytorch_tpu.ops import grouped_decode as gd
from dalle_pytorch_tpu.ops.attention_core import dense_attention

LEAF, BLOCK = 70, 32  # two whole blocks and one that the leaf's end cuts at 6 of 32


def _operands(rows, hkv, group, n, leaf=LEAF, dh=16, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (rows, hkv, group * n, dh), dtype)
    k = jax.random.normal(keys[1], (rows, hkv, leaf, dh), dtype)
    v = jax.random.normal(keys[2], (rows, hkv, leaf, dh), dtype)
    return q, k, v


def _dense(q, k, v, lengths, n):
    """What `_cached_grouped` ran for a full layer until PR 40: the mask by
    true position, the group's heads and the step's positions as rows."""
    rows, _, query_rows, _ = q.shape
    leaf, index = k.shape[2], lengths - n
    at = index[:, None] + jnp.arange(n, dtype=index.dtype)  # [B, n]
    mask = jnp.arange(leaf, dtype=index.dtype)[None, None] <= at[:, :, None]
    mask = jnp.broadcast_to(mask[:, None, None], (rows, 1, query_rows // n, n, leaf))
    return dense_attention(q, k, v, mask=mask.reshape(rows, 1, query_rows, leaf))


# a row of length n (nothing cached before the step), one that ends inside the
# first block, at a block's edge, one position past it, inside the second
# block, at the second edge, inside the cut block and at the leaf's end
LENGTHS = {"first": None, "inside": 19, "edge": 32, "past_edge": 33, "second": 45,
           "second_edge": 64, "cut": 67, "leaf_end": 70}


@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("where", list(LENGTHS))
def test_kernel_matches_dense_attention_under_the_steps_mask(n, group, where):
    length = n if LENGTHS[where] is None else LENGTHS[where]
    # the row under test between two others, so that a row's length is its own
    lengths = jnp.asarray([LEAF, length, max(n, length - 7)], jnp.int32)
    q, k, v = _operands(3, 2, group, n, seed=n * 10 + group)
    # what lies past a row's length is never read as a value: poison it
    dead = jnp.arange(LEAF)[None, None, :, None] >= lengths[:, None, None, None]
    got = gd.grouped_decode_attention(q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v),
                                      lengths, n=n, block=BLOCK)
    want = _dense(q, k, v, lengths, n)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("n", [1, 2])
def test_a_steps_second_position_sees_one_position_more_than_its_first(n):
    """Query row r stands at `lengths - n + r % n`: moving V at the step's
    LAST position moves the rows of that position alone."""
    q, k, v = _operands(2, 2, 4, n)
    lengths = jnp.asarray([40, 64], jnp.int32)
    base = gd.grouped_decode_attention(q, k, v, lengths, n=n, block=BLOCK)
    moved = v.at[jnp.arange(2), :, lengths - 1].add(3.0)
    got = gd.grouped_decode_attention(q, k, moved, lengths, n=n, block=BLOCK)
    changed = np.abs(np.asarray(got - base)).max(axis=(0, 1, 3)) > 1e-3  # by query row
    assert changed.tolist() == [r % n == n - 1 for r in range(4 * n)]


def test_bf16_operands_round_where_the_dense_path_rounds():
    """In bf16 the kernel stands as near dense float32 as the dense bf16 path
    does (it normalises after the sum and not before the cast, so the two are
    not equal): scores in float32, weights cast to V's dtype."""
    q, k, v = _operands(3, 2, 8, 2, leaf=300, dh=32, dtype=jnp.bfloat16)
    lengths = jnp.asarray([2, 257, 300], jnp.int32)
    exact = _dense(*(t.astype(jnp.float32) for t in (q, k, v)), lengths, 2)
    got = gd.grouped_decode_attention(q, k, v, lengths, n=2, block=128).astype(jnp.float32)
    dense = _dense(q, k, v, lengths, 2).astype(jnp.float32)
    assert got.dtype == jnp.float32 and float(jnp.max(jnp.abs(got - exact))) < 0.03
    assert float(jnp.max(jnp.abs(got - exact))) < 2 * float(jnp.max(jnp.abs(dense - exact))) + 1e-3


def test_calls_records_one_entry_a_shape_and_forget_clears_it():
    gd.forget()
    q, k, v = _operands(3, 2, 4, 2)
    lengths = jnp.asarray([2, 40, 70], jnp.int32)
    gd.grouped_decode_attention(q, k, v, lengths, n=2)
    gd.grouped_decode_attention(q, k, v, lengths + 0, n=2)
    assert gd.calls == {(3, 2, 8, LEAF): LEAF}  # a leaf under a block is one block
    gd.grouped_decode_attention(q[:, :, :4], k, v, lengths, n=1, block=BLOCK)
    assert gd.calls == {(3, 2, 8, LEAF): LEAF, (3, 2, 4, LEAF): BLOCK}
    gd.forget()
    assert gd.calls == {}


@pytest.mark.parametrize("leaf,block,steps", [
    (16960, 2432, 7),  # `kexaone.decode.16k`: 6 x 2,432 and 2,368, not 6 x 2,560 and 1,600
    (16384, 2432, 7), (2560, 2560, 1), (2561, 1408, 2), (8480, 2176, 4), (129, 129, 1), (70, 70, 1),
])
def test_a_leaf_is_split_into_even_blocks_of_whole_lane_tiles(leaf, block, steps):
    assert gd.BLOCK_POSITIONS == 2560
    assert (gd._block(leaf), -(-leaf // gd._block(leaf))) == (block, steps)
    assert block == leaf or (block % 128 == 0 and block <= gd.BLOCK_POSITIONS)


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
@pytest.mark.parametrize("n", [1, 2])
def test_a_full_layers_cached_step_takes_the_kernel_and_a_window_layers_does_not(window, n):
    """`Attention._cached_grouped` chooses by the layer's kind alone: a full
    layer's step of n <= `step_positions` is the kernel's (one record a
    shape), a window layer's stays XLA's product over its ring; a chunk that
    starts the rows' sequences is neither's."""
    gd.forget()
    attn = Attention(dim=32, seq_len=24, heads=4, dim_head=8, kv_heads=2, window=window,
                     step_positions=2, causal=True, attn_impl="dense")
    cache = decode_cache.make(
        decode_cache.PER_LAYER, 1, kind="window" if window else "heads", batch=3, heads=2,
        dim_head=8, dim=32, max_len=24, per_row=True, ring=(window or 0) + 1)["layer_0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(0), (3, n, 32))
    variables = attn.init(jax.random.PRNGKey(1), x)
    _, started = attn.apply(variables, jax.random.normal(jax.random.PRNGKey(2), (3, 5, 32)),
                            cache=cache, start=True)
    assert gd.calls == {}
    out, stepped = attn.apply(variables, x, cache=started)
    assert out.shape == (3, n, 32) and stepped["index"].tolist() == [5 + n] * 3
    assert gd.calls == ({} if window else {(3, 2, 2 * n, 24): 24})
