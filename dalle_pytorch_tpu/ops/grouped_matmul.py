"""The grouped matrix product of a routed feed-forward layer, as Pallas kernels.

`grouped_matmul(lhs [R, K], rhs [G, K, N], group_sizes [G])`: the rows of
`lhs` are sorted by group, group g owns the `group_sizes[g]` rows after
those of the groups before it, and each row is multiplied by its own
group's matrix. Rows past `sum(group_sizes)` belong to no group and cost
no arithmetic, so a buffer sized for the worst routing pays only for the
assignments really made; what comes out in them is NOT SPECIFIED (whole
tiles of them are never written: zeroing them would be a pass over the
buffer that costs as much as the product), and callers read the rows of
groups only.

The matrices multiply in `lhs`'s dtype (float32 parameters are cast on the
way in; bf16 stays bf16: one MXU pass) and accumulate in float32 scratch;
the result has `lhs`'s dtype. The backward is two products of the same
kind: `dlhs` is the same product against the matrices transposed, and
`drhs[g] = lhs_g^T dout_g` contracts the ragged rows, straight into the
parameters' own dtype.

How the rows are walked. They lie in tiles of `tm` rows, and a group's
edge falls where it falls, so a tile may belong to several groups. The
work is the list of (tile, group) pairs that share a row, at most `tiles +
groups - 1` of them: made on the device from `group_sizes` (`_plan`), handed
to the kernels as prefetched scalars, and walked by the grid. Every pair
multiplies its whole tile by its group's matrix and keeps the rows that are
the group's, so `tm` follows a group's even share of the buffer and not the
buffer's size (`_row_tile`, the one place that says the rule; what each
product traced so far got is in `row_tiles`). Three kernels, named for the
device trace: `gmm_fwd`, `gmm_dlhs` (the same body, the matrices read
transposed) and `gmm_drhs`. Interpreted on the CPU backend, like the flash
kernels.

A group without rows. The two rows products give it NO pair: a pair's grid
steps name the group's matrix, block by block, and the pipeline fetches
what a step names, so a pair that owns no row would read a whole matrix to
keep nothing of it (a token step's 58 rows fall on 5 or 6 of 16 held
experts: PERF.md, PR 32). Their grid walks `n_pairs` pairs, a bound read
on the device, and no step past them; where no group has a row it walks one
step that multiplies and stores nothing. `gmm_drhs` owes every group a
matrix, so there a group without rows keeps one pair, which owns no row and
stores the zeros it accumulated; its grid is the static `tiles + groups -
1`, and the steps past the last pair name that pair's blocks again (no copy)
and do nothing.

`lax.ragged_dot` is the same product and the chip compiles it to a grouped
kernel of its own; at the routed layer's shapes it was a third as fast
(PERF.md, PR 27), so it is not on the path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops.pallas_attention import _dot, _NT, _TN, _use_interpret

TILE_ROWS = 512  # rows a grid step multiplies: the MXU's rows, four times over
MXU_ROWS = 128  # the MXU's rows, once
TILE_CAP = 1024  # the widest tile along K or N
# what a grid step's blocks (each double-buffered) and its float32 scratch may
# take where `_fit` has to choose: under Mosaic's 16 MiB with room for its own
VMEM_BUDGET = 12 * 1024 * 1024

#: the row tile each product traced so far got, by (kernel, rows, groups);
#: tests pin it the way they pin `pallas_attention.tiles_chosen`.
row_tiles: dict = {}


def forget() -> None:
    """Drop the record of the tiles and the emitters' trace caches (tests)."""
    row_tiles.clear()
    for emit in (_emit_rows, _emit_drhs):
        emit.clear_cache()


def _tile(n: int) -> int:
    """The widest multiple of 128 that divides n, up to TILE_CAP (768 of
    2304, 896 of 896); the whole of a length 128 does not divide."""
    fits = [c for c in range(128, min(n, TILE_CAP) + 1, 128) if n % c == 0]
    return max(fits) if fits else n


def _fit(tm: int, k: int, n: int, itemsize: int):
    """(tm, tk, tn) of a rows product: `_tile` of K and of N, and, ONLY where
    one of them is a length 128 does not divide (1,856: taken whole, a block
    may not end inside it) and the step's blocks would pass VMEM_BUDGET, the
    other shortened to its next divisor, then the rows halved. A product whose
    lengths 128 divides keeps what `_tile` and `_row_tile` gave it, whatever
    its size: every cell before PR 43."""
    tk, tn = _tile(k), _tile(n)
    if tk % 128 == 0 and tn % 128 == 0:
        return tm, tk, tn
    held = lambda tm, tk, tn: 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    shorter = lambda t, whole: max([c for c in range(128, t, 128) if whole % c == 0], default=t)
    while held(tm, tk, tn) > VMEM_BUDGET:
        if tk % 128 == 0 and shorter(tk, k) < tk:
            tk = shorter(tk, k)
        elif tn % 128 == 0 and shorter(tn, n) < tn:
            tn = shorter(tn, n)
        elif tm > MXU_ROWS:
            tm //= 2
        else:
            break
    return tm, tk, tn


def _plan(group_sizes: jnp.ndarray, n_tiles: int, tm: int, *, empty_groups: bool):
    """(offsets [G + 1], pair_group [W], pair_tile [W], n_pairs [1]) for the
    (tile, group) pairs that share a row, in row order, so that a tile's
    pairs stand back to back, and a group's. With `empty_groups` (for
    `gmm_drhs`, which writes every group's matrix) a group without rows gets
    one pair too, on the tile where it would start; without (for the rows
    products, which would only read its matrix) it gets none, and `n_pairs`
    is 0 where no group has a row. W = tiles + groups - 1 is static, the
    pairs past `n_pairs` repeat the last one."""
    groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = jnp.minimum(starts // tm, n_tiles - 1)
    tiles_of = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, int(empty_groups))
    pair_ends = jnp.cumsum(tiles_of)
    n_pairs = pair_ends[-1]
    w = jnp.minimum(jnp.arange(n_tiles + groups - 1), jnp.maximum(n_pairs - 1, 0))
    # the group whose pairs end past w: `right` steps over the groups without pairs
    pair_group = jnp.minimum(jnp.searchsorted(pair_ends, w, side="right"), groups - 1)
    pair_tile = first[pair_group] + w - (pair_ends - tiles_of)[pair_group]
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    as_i32 = lambda x: x.astype(jnp.int32)
    return (as_i32(offsets), as_i32(pair_group), as_i32(jnp.clip(pair_tile, 0, n_tiles - 1)),
            as_i32(n_pairs)[None])


def _own_rows(offsets_ref, group, tile, tm):
    """[tm, 1] bool: the rows of this tile that are this group's."""
    row = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= offsets_ref[group]) & (row < offsets_ref[group + 1])


def _rows_kernel(offsets_ref, group_ref, tile_ref, n_ref, lhs_ref, rhs_ref, out_ref, acc_ref,
                 *, tm, transposed):
    """One (tile, group) pair: out[tile rows of the group] = lhs tile @ the
    group's matrix, accumulated over the K tiles in float32. A tile shared
    by several groups is visited once for each, back to back, and stays in
    VMEM between the visits: each keeps what the others wrote."""
    w, k = pl.program_id(1), pl.program_id(2)
    live = w < n_ref[0]  # false only in the one step of a walk without pairs

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _multiply():
        if transposed:
            acc_ref[...] += _dot(lhs_ref[...], rhs_ref[0], _NT)
        else:
            acc_ref[...] += _dot(lhs_ref[...], rhs_ref[0])

    @pl.when(live & (k == pl.num_programs(2) - 1))
    def _store():
        tile = tile_ref[w]
        first_visit = (w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != tile)
        kept = jnp.where(first_visit, jnp.zeros_like(out_ref), out_ref[...])
        own = _own_rows(offsets_ref, group_ref[w], tile, tm)
        out_ref[...] = jnp.where(own, acc_ref[...].astype(out_ref.dtype), kept)


def _drhs_kernel(offsets_ref, group_ref, tile_ref, n_ref, lhs_ref, dout_ref, out_ref, acc_ref,
                 *, tm):
    """One (tile, group) pair: acc += (lhs tile)^T @ dout tile, the group's
    rows only of both (a row of neither may be read: 0 x NaN); the group's
    [K tile, N tile] is stored on its last pair."""
    w = pl.program_id(2)
    group = group_ref[w]
    last_pair = n_ref[0] - 1

    @pl.when((w == 0) | (group_ref[jnp.maximum(w - 1, 0)] != group))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(w <= last_pair)
    def _multiply():
        own = _own_rows(offsets_ref, group, tile_ref[w], tm)
        lhs = jnp.where(own, lhs_ref[...], jnp.zeros_like(lhs_ref))
        dout = jnp.where(own, dout_ref[...], jnp.zeros_like(dout_ref))
        acc_ref[...] += _dot(lhs, dout, _TN)

    @pl.when((w == last_pair) | ((w < last_pair)
                                 & (group_ref[jnp.minimum(w + 1, pl.num_programs(2) - 1)] != group)))
    def _store():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _padded(x, tm):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _row_tile(rows: int, groups: int = 1) -> int:
    """Rows a tile holds: TILE_ROWS, or all the rows of a shorter buffer;
    where groups share the buffer and a group's even share of it is under a
    tile, no taller than the MXU, whatever the buffer's size. Every pair
    multiplies its WHOLE tile by its group's matrix and a bf16 matrix element
    does `tm` operations a byte read, so above 240 rows (197 TFLOP/s over 819
    GB/s) a pair is bound by arithmetic on rows it throws away: a verify
    step's 384 rows for 16 experts, 43 present on 8 of them, cost as one tile
    1.6 times its matrices' read (PERF.md, PR 38; a tile of 64 rows read 6%
    faster still and was not taken: there). The rows products ask with their
    groups; `gmm_drhs`, whose pairs read rows and no matrix, asks as one
    group, which throws nothing away."""
    whole = min(TILE_ROWS, -(-rows // 8) * 8)
    if groups == 1 or rows // groups >= TILE_ROWS:
        return whole
    return min(MXU_ROWS, whole)


@functools.partial(jax.jit, static_argnames=("transposed", "interpret"))
def _emit_rows(lhs, rhs, group_sizes, *, transposed, interpret):
    """`gmm_fwd` (rhs [G, K, N]) or `gmm_dlhs` (transposed: lhs is dout [R,
    N], the result [R, K], the matrices read as they lie)."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transposed else rhs.shape[2]
    name = "gmm_dlhs" if transposed else "gmm_fwd"
    tm, tk, tn = _fit(_row_tile(rows, rhs.shape[0]), k, n, lhs.dtype.itemsize)
    row_tiles[(name, rows, rhs.shape[0])] = tm
    lhs = _padded(lhs, tm)
    n_tiles = lhs.shape[0] // tm
    plan = _plan(group_sizes, n_tiles, tm, empty_groups=False)
    n_k = k // tk

    # no pair at all: the one step walked names one block of each operand
    def k_of(w, kk, n_ref):
        return jnp.where(w < n_ref[0], kk, n_k - 1)

    lhs_spec = pl.BlockSpec((tm, tk), lambda j, w, kk, o, g, t, n_: (t[w], k_of(w, kk, n_)))
    if transposed:
        rhs_spec = pl.BlockSpec((1, tn, tk), lambda j, w, kk, o, g, t, n_: (g[w], j, k_of(w, kk, n_)))
    else:
        rhs_spec = pl.BlockSpec((1, tk, tn), lambda j, w, kk, o, g, t, n_: (g[w], k_of(w, kk, n_), j))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, transposed=transposed),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, jnp.maximum(plan[3][0], 1), n_k),  # the pairs there are, not W
            in_specs=[lhs_spec, rhs_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda j, w, kk, o, g, t, n_: (t[w], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*plan, lhs, rhs)
    return out[:rows]  # tiles that no pair visits were never written


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _emit_drhs(lhs, dout, group_sizes, *, out_dtype, interpret):
    """`gmm_drhs`: [G, K, N], each group's lhs rows^T @ dout rows."""
    rows, k = lhs.shape
    n, groups = dout.shape[1], group_sizes.shape[0]
    tm, tk, tn = _row_tile(rows), _tile(k), _tile(n)
    row_tiles[("gmm_drhs", rows, groups)] = tm
    lhs, dout = _padded(lhs, tm), _padded(dout, tm)
    plan = _plan(group_sizes, lhs.shape[0] // tm, tm, empty_groups=True)
    return pl.pallas_call(
        functools.partial(_drhs_kernel, tm=tm),
        name="gmm_drhs",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, plan[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, w, o, g, t, n_: (t[w], i)),
                pl.BlockSpec((tm, tn), lambda i, j, w, o, g, t, n_: (t[w], j)),
            ],
            out_specs=pl.BlockSpec((1, tk, tn), lambda i, j, w, o, g, t, n_: (g[w], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*plan, lhs, dout)


@jax.custom_vjp
def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray):
    """[R, N]: row r of `lhs` times the matrix of the group that owns it."""
    return _emit_rows(lhs, rhs.astype(lhs.dtype), group_sizes, transposed=False,
                      interpret=_use_interpret())


def grouped_matmul_dlhs(rhs, group_sizes, dout):
    """`gmm_dlhs`: [R, K], the cotangent of `grouped_matmul`'s rows for the
    cotangent `dout` [R, N] of its result (which has the rows' dtype)."""
    return _emit_rows(dout, rhs.astype(dout.dtype), group_sizes, transposed=True,
                      interpret=_use_interpret())


def grouped_matmul_drhs(lhs, rhs, group_sizes, dout):
    """`gmm_drhs`: [G, K, N], the cotangent of `grouped_matmul`'s matrices."""
    return _emit_drhs(lhs, dout.astype(lhs.dtype), group_sizes, out_dtype=rhs.dtype,
                      interpret=_use_interpret())


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    return (grouped_matmul_dlhs(rhs, group_sizes, dout),
            grouped_matmul_drhs(lhs, rhs, group_sizes, dout), None)


grouped_matmul.defvjp(_fwd, _bwd)
