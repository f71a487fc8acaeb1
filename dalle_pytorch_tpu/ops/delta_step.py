"""The gated delta rule's token step: ONE pass over a head's state.

A gated delta-rule layer (models/attention.py:GatedDeltaAttention) keeps,
per row and head, a float32 state S [d_k, d_v] in place of keys and values
(a row's heads side by side: [d_k, H x d_v], as the cache keeps them).
A token step is, with the step's q, k [d_k], v [d_v] and the scalars alpha
in (0, 1) and beta in (0, 2),

    u  = beta (v - alpha S^T k)            [d_v]
    S' = alpha S + k u^T                   [d_k, d_v]
    o  = S'^T q = alpha S^T q + (k . q) u  [d_v]

Written as three products it reads the state three times (for S^T k, for
the update, for S^T q) and writes it once; the work is one read and one
write, because o's second form needs only what a single pass over S gives.
`delta_step` is the one implementation on the path: a Pallas kernel that
holds a block of a row's head states in VMEM, computes S^T k and S^T q
there on the vector unit (a product of one row against a [d_k, d_v] matrix
would be the matrix unit's worst case), and writes S' back IN PLACE
(`input_output_aliases`): the state never has a second buffer. Interpreted
on the CPU backend, like the other kernels. XLA's fusion of the same
equations lives in benchmark/tests/chip_kernels_olmo.py, which times both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _use_interpret() -> bool:
    """Interpret off the chip; a name of its own, so that a scratch compile
    for a described chip patches this module alone."""
    return jax.default_backend() == "cpu"


# heads whose states one grid step holds: 10 x 73.7 KB in, as much out, each
# double-buffered. Timed on the chip at 48 rows x 30 heads x 96 x 192 (PERF.md,
# PR 33; the bound is 0.263 ms): 0.602 ms at 2 heads, 0.476 at 6, 0.477 at 10,
# 0.478 at 30 (0.33 inside a token step); XLA's fusion of the same equations
# took 0.672 ms on [B, H, 96, 192] (192 stored as 256) and 2.12 on this layout
HEADS_PER_BLOCK = 10
LANES = 128


def _group(block: int, dv: int) -> int:
    """Heads handled at once inside a block: the fewest whose columns end on
    a lane tile's edge (2 of 192), so that every slice of the state block is
    aligned; the whole block where there is no such count."""
    return next((g for g in range(1, block + 1) if block % g == 0 and g * dv % LANES == 0),
                block)


def _kernel(alpha_ref, beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref, s_out_ref,
            *, heads, block, group, dv):
    """Grid (row, block of heads). The state block is [d_k, block x d_v]: a
    head's matrix is `d_v` of its columns. q and k arrive with d_k on the
    sublanes ([d_k, block]: a head's column broadcasts along the state's
    lanes), v and o as one row of the block's columns, alpha and beta as
    scalars in SMEM. A group's per-head operands are laid side by side by
    selects on the column's head, and the group is then one matrix."""
    first = pl.program_id(0) * heads + pl.program_id(1) * block
    head_of = lax.broadcasted_iota(jnp.int32, (1, group * dv), 1) // dv
    for g in range(block // group):
        cols = slice(g * group * dv, (g + 1) * group * dv)

        def side_by_side(per_head):  # [.., 1] a head -> [.., group * dv]
            out = per_head(g * group)
            for j in range(1, group):
                out = jnp.where(head_of == j, per_head(g * group + j), out)
            return out

        k = side_by_side(lambda h: k_ref[0, 0, :, h:h + 1])  # [d_k, cols]
        q = side_by_side(lambda h: q_ref[0, 0, :, h:h + 1])
        kq = side_by_side(lambda h: jnp.sum(
            k_ref[0, 0, :, h:h + 1] * q_ref[0, 0, :, h:h + 1], axis=0, keepdims=True))
        alpha = side_by_side(lambda h: jnp.full((1, 1), alpha_ref[first + h], jnp.float32))
        beta = side_by_side(lambda h: jnp.full((1, 1), beta_ref[first + h], jnp.float32))
        s = s_ref[0, :, cols]
        sk = jnp.sum(s * k, axis=0, keepdims=True)  # S^T k, every head of the group
        sq = jnp.sum(s * q, axis=0, keepdims=True)
        u = beta * (v_ref[0, :, cols] - alpha * sk)
        s_out_ref[0, :, cols] = alpha * s + k * u
        o_ref[0, :, cols] = alpha * sq + kq * u


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _emit(state, q, k, v, alpha, beta, *, block, interpret):
    rows, heads, dk = q.shape
    dv = v.shape[-1]
    blocks = heads // block
    # d_k onto the sublanes: [rows, blocks, d_k, block]
    cols = lambda t: t.reshape(rows, blocks, block, dk).transpose(0, 1, 3, 2)
    vec = pl.BlockSpec((1, 1, dk, block), lambda b, g, *_: (b, g, 0, 0))
    wide = pl.BlockSpec((1, 1, block * dv), lambda b, g, *_: (b, 0, g))
    held = pl.BlockSpec((1, dk, block * dv), lambda b, g, *_: (b, 0, g))
    o, new = pl.pallas_call(
        functools.partial(_kernel, heads=heads, block=block, group=_group(block, dv), dv=dv),
        name="delta_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, blocks),
            in_specs=[vec, vec, wide, held],
            out_specs=[wide, held],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, heads * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operands count from the two scalar tables: the state is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(alpha.reshape(-1), beta.reshape(-1), cols(q), cols(k),
      v.reshape(rows, 1, heads * dv), state)
    return o.reshape(rows, heads, dv), new


def delta_step(state, q, k, v, alpha, beta, *, block=None):
    """`(o [B, H, d_v], S')` of one token step, all in float32: `state` [B,
    d_k, H x d_v], the heads' matrices side by side along the last axis, as
    the cache keeps them (models/decode_cache.py says why; S' is written over
    it where the caller's buffer is free: a loop's carry), q, k [B, H, d_k],
    v [B, H, d_v], alpha, beta [B, H]."""
    heads, dv = v.shape[1], v.shape[2]
    block = HEADS_PER_BLOCK if block is None else block
    if heads % block or (block != heads and block * dv % LANES):
        block = heads
    f32 = lambda t: t.astype(jnp.float32)
    return _emit(f32(state), f32(q), f32(k), f32(v), f32(alpha), f32(beta),
                 block=int(block), interpret=_use_interpret())


def delta_step_reference(state, q, k, v, alpha, beta):
    """The same step as the equations read, on states [B, H, d_k, d_v] (tests)."""
    a = alpha[..., None]
    u = beta[..., None] * (v - a * jnp.einsum("bhkv,bhk->bhv", state, k, precision="highest"))
    new = a[..., None] * state + k[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", new, q, precision="highest"), new
