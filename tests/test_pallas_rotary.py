"""The one-pass rotary kernel against `ops/rotary.py:apply_rotary`.

`rotary_split` turns the parts of t [B, n, parts x heads x dim_head] where the
projection wrote them (one part: a tensor turned in place); `apply_rotary` is
the oracle on the same values, forward and backward.
Runs in Pallas interpret mode on the CPU.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.ops import pallas_rotary as pr
from dalle_pytorch_tpu.ops.rotary import apply_rotary, build_dalle_rotary


def rotary_columns(angles, t):
    """One part: t [B, n, heads, dim_head] turned where it lies."""
    b, n, heads, dh = t.shape
    return pr.rotary_split(angles, t.reshape(b, n, heads * dh), heads, 1)[0]


def _case(b, n, heads, dh, d_rot, dtype, seed=0):
    rng = np.random.RandomState(seed)
    t, w = (jnp.asarray(rng.randn(b, n, heads, dh), dtype) for _ in range(2))
    # every channel its own angle: nothing may lean on a pair sharing one
    angles = jnp.asarray(rng.randn(n, d_rot), jnp.float32)
    return t, w, angles


# (batch, n, heads, dim_head, d_rot, VMEM budget): the flagship's head (60 of
# 64 channels turned), every channel turned, a head of 128, rows in several
# blocks with a ragged last one, a length no sublane tile divides
CASES = {
    "pair_60_of_64": (2, 48, 2, 64, 60, None),
    "all_channels": (1, 32, 4, 32, 32, None),
    "head_128": (2, 40, 1, 128, 42, None),
    "several_blocks_ragged": (2, 200, 2, 64, 60, 64 * 128 * 36),
    "odd_length": (1, 37, 2, 64, 20, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_rotary_columns_equals_apply_rotary(monkeypatch, case, dtype):
    b, n, heads, dh, d_rot, budget = CASES[case]
    if budget:
        monkeypatch.setattr(pr, "VMEM_BUDGET", budget)
        rows = pr._rows(n, heads * dh, jnp.dtype(dtype).itemsize)
        assert rows < n and n % rows != 0 and rows % 16 == 0
        pr._emit_split.clear_cache(); pr._emit_join.clear_cache()
    t, w, angles = _case(b, n, heads, dh, d_rot, dtype)
    f32 = lambda x: x.astype(jnp.float32)

    def kernel(t):
        out = rotary_columns(angles, t)
        return (f32(out) * f32(w)).sum(), out

    def oracle(t):
        out = apply_rotary(angles[None, :, None], t)
        return (f32(out) * f32(w)).sum(), out

    (_, got), grad_got = jax.value_and_grad(kernel, has_aux=True)(t)
    (_, want), grad_want = jax.value_and_grad(oracle, has_aux=True)(t)
    if budget:
        pr._emit_split.clear_cache(); pr._emit_join.clear_cache()
    assert got.dtype == grad_got.dtype == jnp.dtype(dtype) and got.shape == t.shape
    # bf16: the kernel rounds the sum once, the oracle each product too
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(f32(got), f32(want), atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(grad_got), f32(grad_want), atol=tol, rtol=tol)
    # the channels past d_rot pass through untouched, bit for bit
    np.testing.assert_array_equal(got[..., d_rot:], t[..., d_rot:])


# (batch, n, heads, dim_head, d_rot, parts, VMEM budget): the fused q, k, v
# projection at the flagship's head, two parts of a 128-wide head, and rows
# in several blocks with a ragged last one (the budget three parts plan for)
SPLIT_CASES = {
    "qkv_pair_60_of_64": (2, 48, 2, 64, 60, 3, None),
    "two_parts_head_128": (1, 40, 2, 128, 42, 2, None),
    "qkv_several_blocks_ragged": (2, 200, 2, 64, 60, 3, 64 * 128 * 68),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", SPLIT_CASES)
def test_rotary_split_equals_a_split_and_apply_rotary(monkeypatch, case, dtype):
    """The fused columns in, each part turned and out as its own array: held
    to `jnp.split` then `apply_rotary` a part, the results and the gradient of
    the fused operand (the backward's join writes the parts' cotangents side
    by side). Each part meets its own weights, so a part written to another's
    columns would show."""
    b, n, heads, dh, d_rot, parts, budget = SPLIT_CASES[case]
    if budget:
        monkeypatch.setattr(pr, "VMEM_BUDGET", budget)
        rows = pr._rows(n, heads * dh, jnp.dtype(dtype).itemsize, parts)
        assert rows < n and n % rows != 0 and rows % 16 == 0
        pr._emit_split.clear_cache(); pr._emit_join.clear_cache()
    rng = np.random.RandomState(2)
    t = jnp.asarray(rng.randn(b, n, parts * heads * dh), dtype)
    ws = [jnp.asarray(rng.randn(b, n, heads, dh), dtype) for _ in range(parts)]
    angles = jnp.asarray(rng.randn(n, d_rot), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)
    weighed = lambda outs: sum((f32(o) * f32(w)).sum() for o, w in zip(outs, ws))

    def kernel(t):
        outs = pr.rotary_split(angles, t, heads, parts)
        return weighed(outs), outs

    def oracle(t):
        outs = tuple(apply_rotary(angles[None, :, None], part.reshape(b, n, heads, dh))
                     for part in jnp.split(t, parts, axis=-1))
        return weighed(outs), outs

    (_, got), grad_got = jax.value_and_grad(kernel, has_aux=True)(t)
    (_, want), grad_want = jax.value_and_grad(oracle, has_aux=True)(t)
    if budget:
        pr._emit_split.clear_cache(); pr._emit_join.clear_cache()
    assert len(got) == parts and grad_got.shape == t.shape
    assert grad_got.dtype == jnp.dtype(dtype)
    tol = 1e-5 if dtype == "float32" else 3e-2  # as above: one rounding against several
    for a, w_ in zip(got, want):
        assert a.shape == (b, n, heads, dh) and a.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(f32(a), f32(w_), atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(grad_got), f32(grad_want), atol=tol, rtol=tol)


def test_rotary_columns_on_the_models_table_under_remat():
    """The table the model builds (pairs share an angle; text rows, then the
    image grid), inside `jax.checkpoint` and `jit` as the trainer runs it:
    the angles are constants of the step and carry no gradient."""
    text, fmap, dh = 9, 4, 64
    n = text + fmap * fmap - 1
    table = build_dalle_rotary(text, fmap, dh)[:n]
    t, w, _ = _case(2, n, 2, dh, table.shape[-1], "float32", seed=1)

    def loss(fn):
        return lambda t, table: (jax.checkpoint(fn)(table, t) * w).sum()

    got = jax.jit(jax.grad(loss(rotary_columns), (0, 1)))(t, table)
    want = jax.grad(loss(lambda a, t: apply_rotary(a[None, :, None], t)))(t, table)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    assert not np.any(np.asarray(got[1]))  # positions, not parameters


def test_rotary_columns_refuses_heads_that_fill_no_lane_row():
    t, _, angles = _case(1, 16, 3, 64, 20, "float32")
    with pytest.raises(AssertionError, match="lane rows"):
        rotary_columns(angles, t)
    with pytest.raises(AssertionError):
        rotary_columns(angles[:8], t[:, :, :2])
