"""The DALL-E rotary on a projection's columns, in one pass.

`ops/rotary.py:apply_rotary` turns t [..., n, dim_head]: it slices the
rotated channels off, pairs them as `[.., d_rot / 2, 2]`, stacks the swapped
pair and concatenates the rest back. On the chip none of those pieces keeps
the layout the projection wrote: a 60-wide slice and a 2-wide axis are laid
out positions-minor, so every piece is copied into that layout and the
result out of it, three tensors a layer, forward, again under remat and
backward (a third of `flagship.train`'s step: PERF.md, PR 34). XLA has no
single pass for a swap of neighbouring lanes either (a `roll` by one lane is
two misaligned slices, each written out), so the pass is a kernel: a lane
rotation each way and a select, on rows of whole heads. Given the fused q, k, v
projection it also does the split: each part is read where the projection
wrote it and written as its own array, and the backward writes the three
cotangents side by side, so neither a slice nor a concatenate runs.

Same mathematics: out = t * cos + swap(t) * sin with the pair's sign on the
sine, the channels past `d_rot` meeting a cosine of 1 and a sine of 0, the
tables in t's dtype as `apply_rotary` makes them; the products and the sum
are float32 and rounded once.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops import pallas_attention

#: what a grid step may hold: the blocks of the operand, of the results and
#: of both tables, each twice (Pallas double-buffers), and five float32 rows
#: of temporaries for the part in hand (t, its two rotations, the products)
VMEM_BUDGET = 10 * 1024 * 1024


def _rows(n: int, width: int, itemsize: int, parts: int = 1) -> int:
    """Rows of a block of `parts` tensors `width` wide: all of them where
    they fit, else the largest multiple of 16 (a bf16 tile's sublanes) that
    does; one that divides the length is preferred down to half of that
    (256 of 1,280 where 272 fit), else the last block is ragged."""
    fit = VMEM_BUDGET // (width * ((4 * parts + 4) * itemsize + 5 * 4))
    if n <= fit:
        return n
    most = max(fit // 16 * 16, 16)
    return next((r for r in range(most, most // 2, -16) if n % r == 0), most)


def _turned(t, cos_ref, sin_ref, dtype):
    """t [rows, width] turned by the block's tables: float32 products and
    sum, rounded once."""
    t = t.astype(jnp.float32)
    width = t.shape[-1]
    even = lax.broadcasted_iota(jnp.int32, (1, width), 1) % 2 == 0
    # a channel's partner is its neighbour in the pair; what the rotations
    # carry around the block's edge is never selected
    partner = jnp.where(even, pltpu.roll(t, width - 1, 1), pltpu.roll(t, 1, 1))
    turned = t * cos_ref[...].astype(jnp.float32) + partner * sin_ref[...].astype(jnp.float32)
    return turned.astype(dtype)


def _split_kernel(t_ref, cos_ref, sin_ref, *o_refs):
    """One block of the fused projection's columns in, each part's block out."""
    width = cos_ref.shape[-1]
    for part, o_ref in enumerate(o_refs):  # lane-aligned: width is whole lane rows
        o_ref[0] = _turned(t_ref[0, :, part * width:(part + 1) * width],
                           cos_ref, sin_ref, o_ref.dtype)


def _join_kernel(*refs):
    """Each part's block in, one block of the fused columns out."""
    *t_refs, cos_ref, sin_ref, o_ref = refs
    width = cos_ref.shape[-1]
    for part, t_ref in enumerate(t_refs):
        o_ref[0, :, part * width:(part + 1) * width] = _turned(
            t_ref[0], cos_ref, sin_ref, o_ref.dtype)


def _specs(b, n, width, itemsize, parts):
    """(grid, block of one part, block of the fused columns, table's block).
    The grid walks the batch innermost, so a block of the tables is fetched
    once for all of it."""
    rows = _rows(n, width, itemsize, parts)
    part = pl.BlockSpec((1, rows, width), lambda i, b_: (b_, i, 0))
    fused = pl.BlockSpec((1, rows, parts * width), lambda i, b_: (b_, i, 0))
    table = pl.BlockSpec((rows, width), lambda i, b_: (i, 0))
    return (pl.cdiv(n, rows), b), part, fused, table


_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _emit_split(t, cos, sin, *, parts, interpret):
    """t [B, n, parts x width] against tables [n, width]: `parts` results
    [B, n, width], each read where the projection wrote it."""
    b, n, cols = t.shape
    width = cols // parts
    grid, part, fused, table = _specs(b, n, width, t.dtype.itemsize, parts)
    return pl.pallas_call(
        _split_kernel,
        name="rotary_split",
        grid=grid,
        in_specs=[fused, table, table],
        out_specs=[part] * parts,
        out_shape=[jax.ShapeDtypeStruct((b, n, width), t.dtype)] * parts,
        compiler_params=_GRID,
        interpret=interpret,
    )(t, cos, sin)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _emit_join(ts, cos, sin, *, interpret):
    """The transpose of `_emit_split`: `ts`, each [B, n, width], turned and
    written side by side as [B, n, parts x width]."""
    b, n, width = ts[0].shape
    parts = len(ts)
    grid, part, fused, table = _specs(b, n, width, ts[0].dtype.itemsize, parts)
    return pl.pallas_call(
        _join_kernel,
        name="rotary_join",
        grid=grid,
        in_specs=[part] * parts + [table, table],
        out_specs=fused,
        out_shape=jax.ShapeDtypeStruct((b, n, parts * width), ts[0].dtype),
        compiler_params=_GRID,
        interpret=interpret,
    )(*ts, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _split(t, cos, sin, sin_swapped, parts, interpret):
    return tuple(_emit_split(t, cos, sin, parts=parts, interpret=interpret))


def _split_fwd(t, cos, sin, sin_swapped, parts, interpret):
    return _split(t, cos, sin, sin_swapped, parts, interpret), (cos, sin_swapped)


def _split_bwd(parts, interpret, tables, gs):
    """The pass again, joining: the swap is its own transpose, so a part's
    cotangent is g * cos + swap(g * sin) = g * cos + swap(g) * swap(sin),
    and the parts' lie side by side as the projection's cotangent wants
    them. The tables are positions, not parameters (`rotary_split` stops
    their gradient), so their cotangents are zeros."""
    cos, sin_swapped = tables
    zeros = jnp.zeros_like(cos)
    return _emit_join(tuple(gs), cos, sin_swapped, interpret=interpret), zeros, zeros, zeros


_split.defvjp(_split_fwd, _split_bwd)


def rotary_split(angles: jnp.ndarray, t: jnp.ndarray, heads: int, parts: int) -> tuple:
    """`apply_rotary(angles, .)` on each of the `parts` tensors whose columns
    lie side by side in t [B, n, parts x heads x dim_head] (a fused q, k, v
    projection as it writes them): a tuple of `parts` arrays
    [B, n, heads, dim_head], every head turned by the same `angles`
    [n, d_rot]. One pass reads t once and writes each part once; its
    backward reads each part's cotangent once and writes t's once, so
    nothing is sliced, concatenated or laid out anew on either side.
    `heads x dim_head` is a multiple of 128 lanes. The angles carry no
    gradient."""
    b, n, cols = t.shape
    dim_head, d_rot = cols // (parts * heads), angles.shape[-1]
    assert cols == parts * heads * dim_head, (t.shape, parts, heads)
    assert angles.shape == (n, d_rot) and d_rot <= dim_head and d_rot % 2 == 0, angles.shape
    assert (heads * dim_head) % 128 == 0, f"{heads} heads of {dim_head} do not fill lane rows"
    angles = lax.stop_gradient(angles).astype(t.dtype)
    sign = np.where(np.arange(dim_head) % 2 == 0, -1, 1).astype(t.dtype)  # (-x1, x0)
    cos = jnp.pad(jnp.cos(angles), ((0, 0), (0, dim_head - d_rot)), constant_values=1)
    sin = jnp.pad(jnp.sin(angles), ((0, 0), (0, dim_head - d_rot))) * sign
    sin_swapped = sin.reshape(n, dim_head // 2, 2)[..., ::-1].reshape(n, dim_head)
    cos, sin, sin_swapped = (jnp.tile(table, heads) for table in (cos, sin, sin_swapped))
    outs = _split(t, cos, sin, sin_swapped, parts, bool(pallas_attention._use_interpret()))
    return tuple(out.reshape(b, n, heads, dim_head) for out in outs)

