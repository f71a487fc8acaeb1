"""A Mamba-2 layer's token step: ONE pass over a row's state.

A Mamba-2 mixer (models/attention.py:Mamba2Mixer) keeps, per row and head, a
float32 state S [N, P] (N the state size, P the head's width; a row's heads
side by side: [N, H x P], as the cache keeps them). A token step is, with the
step's x [P], the scalars dt > 0, A < 0 and D of the head, and B, C [N] of
the head's GROUP (head i reads group i // (H / G)),

    S' = exp(dt A) S + B (dt x)^T          [N, P]
    y  = S'^T C + D x                      [P]

As XLA writes it the state is read for the update and the new one again for
the product with C; the work is one read and one write. `ssm_step` is the one
implementation on the path: a Pallas kernel that holds a row's groups (or
a block of them) in VMEM, with N on the sublanes and the heads' columns on the lanes (a
group of 8 heads of 64 is four whole lane tiles), so that B and C are one
column a group, broadcast along the lanes, the per-head scalars one row of
the block's lanes, and S'^T C a sum down the sublanes on the vector unit (a
product of one row against [N, P] would be the matrix unit's worst case). S'
is written back IN PLACE (`input_output_aliases`): the state never has a
second buffer. Interpreted on the CPU backend, like the other kernels. XLA's
fusion of the same equations lives in
benchmark/tests/chip_kernels_nemotron_h.py, which times both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _use_interpret() -> bool:
    """Interpret off the chip; a name of its own, so that a scratch compile
    for a described chip patches this module alone."""
    return jax.default_backend() == "cpu"


# groups whose states one grid step holds: a row's 8, 128 x 4,096 float32 = 2 MB
# in, as much out, each double-buffered. Timed on the chip at 192 rows x 8
# groups x 8 heads x 64 x 128 (PERF.md, PR 43; the bound is 0.99 ms): 1.81 ms at
# 1 group, 1.42 at 2, 1.41 at 4, 1.32 at 8; XLA's fusion of the same equations
# took 1.39 ms on [B, H, N, P] in a layout of its own choice and 6.22 on this leaf
GROUPS_PER_BLOCK = 8
LANES = 128


def _kernel(decay_ref, dtx_ref, skip_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref,
            *, groups, width):
    """Grid (row, block of groups). The state block is [N, groups x width]: a
    group's heads are `width` of its columns. B and C arrive with N on the
    sublanes ([N, groups]: a group's column broadcasts along its lanes), the
    per-column operands (the head's decay, dt x, D x) as one row of the
    block's columns."""
    for g in range(groups):
        cols = slice(g * width, (g + 1) * width)
        new = (s_ref[0, :, cols] * decay_ref[0, :, cols]
               + b_ref[0, 0, :, g:g + 1] * dtx_ref[0, :, cols])
        s_out_ref[0, :, cols] = new
        y_ref[0, :, cols] = (jnp.sum(new * c_ref[0, 0, :, g:g + 1], axis=0, keepdims=True)
                             + skip_ref[0, :, cols])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _emit(state, decay, dtx, skip, b, c, *, block, interpret):
    rows, n, columns = state.shape
    groups = b.shape[1]
    width = columns // groups
    blocks = groups // block
    # N onto the sublanes: [rows, blocks, N, block]
    cols = lambda t: t.reshape(rows, blocks, block, n).transpose(0, 1, 3, 2)
    vec = pl.BlockSpec((1, 1, n, block), lambda r, j: (r, j, 0, 0))
    wide = pl.BlockSpec((1, 1, block * width), lambda r, j: (r, 0, j))
    held = pl.BlockSpec((1, n, block * width), lambda r, j: (r, 0, j))
    lane = lambda t: t.reshape(rows, 1, columns)
    y, new = pl.pallas_call(
        functools.partial(_kernel, groups=block, width=width),
        name="ssm_step",
        grid=(rows, blocks),
        in_specs=[wide, wide, wide, vec, vec, held],
        out_specs=[wide, held],
        out_shape=[jax.ShapeDtypeStruct((rows, 1, columns), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},  # the state is the sixth operand
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(lane(decay), lane(dtx), lane(skip), cols(b), cols(c), state)
    return y.reshape(rows, columns), new


def ssm_step_operands(x, dt, a, d):
    """The per-column operands of a step, `(decay, dtx, skip)` [B, H x P]
    float32: a head's exp(dt A) along its P columns, dt x and D x. x [B, H,
    P], dt [B, H] (after its softplus), a, d [H]."""
    f32 = lambda t: t.astype(jnp.float32)
    x, dt = f32(x), f32(dt)
    flat = lambda t: jnp.broadcast_to(t, x.shape).reshape(x.shape[0], -1)
    return (flat(jnp.exp(dt * f32(a))[..., None]), flat(dt[..., None] * x),
            flat(f32(d)[:, None] * x))


def ssm_step(state, decay, dtx, skip, b, c, *, block=None):
    """`(y [B, H x P], S')` of one token step, all in float32: `state` [B, N,
    H x P], the heads' matrices side by side along the last axis, as the cache
    keeps them (S' is written over it where the caller's buffer is free: a
    loop's carry); `decay`, `dtx`, `skip` [B, H x P] (`ssm_step_operands`); b,
    c [B, G, N], a group's shared by its H / G heads."""
    columns, groups = state.shape[-1], b.shape[1]
    block = GROUPS_PER_BLOCK if block is None else block
    if groups % block or (block != groups and block * (columns // groups) % LANES):
        block = groups
    f32 = lambda t: t.astype(jnp.float32)
    return _emit(f32(state), f32(decay), f32(dtx), f32(skip), f32(b), f32(c),
                 block=int(block), interpret=_use_interpret())


def ssm_step_reference(state, x, dt, a, b, c, d):
    """The same step as the equations read, on states [B, H, N, P] (tests):
    x [B, H, P], dt [B, H], a, d [H], b, c [B, G, N] -> (y [B, H, P], S')."""
    per_head = lambda t: jnp.repeat(t, x.shape[1] // t.shape[1], axis=1)  # [B, H, N]
    b, c = per_head(b), per_head(c)
    new = (jnp.exp(dt * a)[..., None, None] * state
           + b[..., :, None] * (dt[..., None] * x)[..., None, :])
    y = jnp.einsum("bhnp,bhn->bhp", new, c, precision="highest") + d[:, None] * x
    return y, new
