"""Latent decode attention: every query head of a row against ONE latent.

Latent attention caches, per position, a compressed vector `c` [R] (from
which every head's keys and values are expanded) and one rotated key `k_r`
[dr] that all heads share (kept with the positions on its last axis:
models/decode_cache.py says why). At a token step the expansion is absorbed into
the query and the output (models/attention.py:LatentAttention), and what is
left is, for each row b and head h,

    s(p)  = (q_c[b, h] . c[b, p] + q_r[b, h] . k_r[b, p]) * sm_scale   p < length[b]
    o[b, h] = sum_p softmax(s)(p) c[b, p]                               [R]

128 heads share one [L, R + dr] stream: [H, R + dr] x [R + dr, L], then
[H, L] x [L, R], 2 H (2 R + dr) operations for (R + dr) cached numbers a
position, so the kernel stands at the chip's ridge and a score that visits
memory is its largest cost.

`latent_decode_attention` is the one implementation on the path: a Pallas
kernel (`decode_latent`) that streams blocks of positions past the row's
resident queries with a running softmax in float32 and stops at the row's
length: blocks past it are neither read nor computed. The blocks are
`even_block`'s (the rule `decode_grouped` shares), the operands the cache's
leaves as they are held, with no copy, pad or transpose. Interpreted on the
CPU backend, like the other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops.pallas_attention import (
    NEG_INF, _dot, _NT, _use_interpret, even_block,
)

# the most positions a grid step streams (`even_block` splits a leaf evenly
# under it: 8,480 are 3 blocks of 2,944, 3.4 MB of 576 bf16 each; 2,048 are
# one). Timed alone on the chip (PERF.md, PR 50; us a call) at 64 rows x
# 8,193..8,480 live of 8,480 x 128 heads: 1,270 at 1,024 (9 steps a row, the
# last of 288: what PR 31's powers of two chose), 1,234 at 2,048 (5, the last
# of 288), 1,110 at 1,792 (5), 1,049 at 2,176 (4), 1,035 at 2,944 (3: 73% of
# the live positions' roofline, 754 us by the bytes and by the products alike);
# 4,352 (2) does not fit the scoped VMEM; and at 16 rows x 2,048 of 2,048 live:
# 89 at 1,024 (2 steps), 84 at 2,048 (1). A grid step costs about 0.5 us
# whatever it holds and a block the length cuts short is computed whole. XLA's
# two batched products around a float32 softmax took 3,630 (PR 31)
BLOCK_POSITIONS = 2944


def _kernel(lengths_ref, qc_ref, qr_ref, c_ref, kr_ref, o_ref, m_ref, l_ref, acc_ref,
            *, sm_scale, block, n_blocks):
    """Grid (row, block of positions): the row's queries stay put while the
    latent's blocks stream through; a block at or past the row's length does
    nothing (and its index map names the last live block again: no copy)."""
    b, j = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < length)
    def _attend():
        c = c_ref[0]  # [block, R]
        # a block the cache's end cuts short holds anything past it: 0 there,
        # so that a weight of 0 times it is 0
        live = j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0) < length
        c = jnp.where(live, c, jnp.zeros_like(c))
        s = (_dot(qc_ref[0], c, _NT) + _dot(qr_ref[0], kr_ref[0])) * sm_scale
        col = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < length, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(c.dtype), c)

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block", "interpret"))
def _emit(q_c, q_r, latent, rope, lengths, *, sm_scale, block, interpret):
    rows, heads, width = q_c.shape
    n_blocks = -(-latent.shape[1] // block)

    def at(b, j, lengths_ref):  # the block read at step j: the last live one at most
        return (b, jnp.minimum(j, jnp.maximum(lengths_ref[b] - 1, 0) // block), 0)

    row = lambda b, j, lengths_ref: (b, 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, block=block, n_blocks=n_blocks),
        name="decode_latent",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, n_blocks),
            in_specs=[
                pl.BlockSpec((1, heads, width), row),
                pl.BlockSpec((1, heads, q_r.shape[2]), row),
                pl.BlockSpec((1, block, width), at),
                pl.BlockSpec((1, rope.shape[1], block), lambda b, j, n: (b, 0, at(b, j, n)[1])),
            ],
            out_specs=pl.BlockSpec((1, heads, width), row),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_c.shape, q_c.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_c, q_r, latent, rope)


def latent_decode_attention(q_c, q_r, latent, rope, lengths, *, sm_scale, block=None):
    """[B, H, R]: q_c [B, H, R] and q_r [B, H, dr] (the queries with the key
    expansion absorbed, and their rotated part) against `latent` [B, L, R]
    and `rope` [B, dr, L] (positions last, as the cache keeps it), row b over
    its first `lengths[b]` positions."""
    leaf = latent.shape[1]
    block = even_block(leaf, BLOCK_POSITIONS) if block is None else min(block, leaf)
    return _emit(q_c, q_r, latent, rope, lengths, sm_scale=float(sm_scale), block=int(block),
                 interpret=_use_interpret())
