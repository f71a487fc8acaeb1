"""Grouped decode attention: a step's queries of a GROUP of heads against the
ONE K/V head they share, over each row's live positions.

A full (window-less) layer whose K/V heads are each shared by `group` query
heads caches k, v [B, Hkv, L, dh]. At a cached step of n positions a row
(models/attention.py:Attention._cached_grouped; a verify step takes two) the
group's heads and the step's positions are one operand's rows, q [B, Hkv,
group * n, dh] (row r is head r // n of the group at the step's position
r % n), and, for each row b and K/V head h, after the step's write,

    s(r, p) = (q[b, h, r] * scale) . k[b, h, p]     p <= index[b] + r % n
    o[b, h, r] = sum_p softmax(s(r, .))(p) v[b, h, p]

with index[b] = lengths[b] - n the position the step's first token takes:
4 group n dh operations for 2 dh cached numbers a position, 16 operations a
byte of bf16 at 16 query rows, far under the chip's ridge, so the read of K
and V is the work and a score that visits memory only adds to it.

`grouped_decode_attention` is the one implementation on the path: a Pallas
kernel (`decode_grouped`) that streams blocks of positions of one K/V head
past the head's resident queries with a running softmax in float32 and stops
at the row's length: blocks past it are neither read nor computed. The
operands are the cache's leaves as they are held, with no copy, pad or
transpose. It rounds where `ops/attention_core.py:dense_attention` rounds
(`q * scale` in the compute dtype, float32 scores, weights cast to V's dtype
for the second product, float32 accumulation) but normalises after the sum.
Interpreted on the CPU backend, like the other kernels.
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

# the most positions of one K/V head a grid step streams (`_block` splits a
# leaf evenly under it). Timed alone on the chip at 24 rows x 8 K/V heads x
# 16,386..16,960 live of 16,960 x 16 query rows (PERF.md, PR 40; ms a call):
# 2.76 at 1,024, 2.36 at 2,048, 2.42 at 4,096 and 2.52 at 8,192, whose last
# block holds 576 positions; 2.27 at 1,920, 2.24 at 2,176, 2.22 at 2,432,
# 3,456, 4,352, 5,760 and 8,576, whose blocks are nearly even (90% of the
# live K/V's read); XLA's grouped product over the whole leaf took 3.15
BLOCK_POSITIONS = 2560


def _block(leaf: int) -> int:
    """The positions a grid step streams: `even_block`'s, under BLOCK_POSITIONS."""
    return even_block(leaf, BLOCK_POSITIONS)


#: the block each call traced so far got, by (rows, K/V heads, query rows a
#: K/V head, cached positions); tests pin it the way they pin
#: `grouped_matmul.row_tiles`.
calls: dict = {}


def forget() -> None:
    """Drop the record of the calls and the emitter's trace cache (tests)."""
    calls.clear()
    _emit.clear_cache()


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, sm_scale, n, block, n_blocks, leaf):
    """Grid (row, K/V head, block of positions): the head's queries stay put
    while its K and V blocks stream through; a block at or past the row's
    length does nothing (and its index map names the last live block again:
    no copy). A block that every query sees whole is two products and the
    running softmax between them; the row's last takes the mask as well."""
    b, j = pl.program_id(0), pl.program_id(2)
    length = jnp.minimum(lengths_ref[b], leaf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(last):
        q = q_ref[0, 0] * sm_scale  # [group * n, dh], in the compute dtype
        v = v_ref[0, 0]  # [block, dh]
        s = _dot(q, k_ref[0, 0], _NT)  # [group * n, block]
        if last:
            # what lies past the row's length, or past the leaf's end in the
            # block it cuts short, is anything: 0 there, so that a weight of
            # 0 times it is 0
            live = j * block + lax.broadcasted_iota(jnp.int32, (block, 1), 0) < length
            v = jnp.where(live, v, jnp.zeros_like(v))
            # query row r stands at position length - n + r % n and sees up to itself
            at = length - n + lax.rem(lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0), n)
            col = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= at, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(v.dtype), v)

    whole = (j + 1) * block <= length - n + 1  # every query sees all of it
    pl.when(whole)(lambda: attend(False))
    pl.when(~whole & (j * block < length))(lambda: attend(True))

    @pl.when(j == n_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "block", "interpret"))
def _emit(q, k, v, lengths, *, n, block, interpret):
    rows, heads, query_rows, dh = q.shape
    leaf = k.shape[2]
    n_blocks = -(-leaf // block)

    def at(b, h, j, lengths_ref):  # the block read at step j: the last live one at most
        last = jnp.clip(lengths_ref[b] - 1, 0, leaf - 1) // block
        return (b, h, jnp.minimum(j, last), 0)

    head = lambda b, h, j, lengths_ref: (b, h, 0, 0)
    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=dh ** -0.5, n=n, block=block,
                          n_blocks=n_blocks, leaf=leaf),
        name="decode_grouped",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, heads, n_blocks),
            in_specs=[
                pl.BlockSpec((1, 1, query_rows, dh), head),
                pl.BlockSpec((1, 1, block, dh), at),
                pl.BlockSpec((1, 1, block, dh), at),
            ],
            out_specs=pl.BlockSpec((1, 1, query_rows, dh), head),
            scratch_shapes=[
                pltpu.VMEM((query_rows, 1), jnp.float32),
                pltpu.VMEM((query_rows, 1), jnp.float32),
                pltpu.VMEM((query_rows, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # what XLA schedules its own copies around: every row at the leaf's end
        cost_estimate=pl.CostEstimate(
            flops=4 * q.size * leaf, transcendentals=q.size // dh * leaf,
            bytes_accessed=(k.size + v.size) * k.dtype.itemsize + 2 * q.size * q.dtype.itemsize),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)


def grouped_decode_attention(q, k, v, lengths, *, n, block=None):
    """[B, Hkv, group * n, dh]: q [B, Hkv, group * n, dh] (a K/V head's group
    of query heads at a step's n positions, position fastest) against k, v
    [B, Hkv, L, dh] as the cache holds them AFTER the step's write, row b
    over its first `lengths[b]` positions, of which the step's own are the
    last n: a query at the step's position i sees all but the n - 1 - i
    after it."""
    leaf = k.shape[2]
    block = _block(leaf) if block is None else min(block, leaf)
    calls[(q.shape[0], q.shape[1], q.shape[2], leaf)] = block
    return _emit(q, k, v, lengths, n=int(n), block=int(block), interpret=_use_interpret())
