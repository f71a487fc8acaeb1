"""The lightning indexer's score: one cheap number a cached position.

Learned sparse attention keeps, beside a layer's latent cache, ONE small key
`k_I` [Di] a position (models/decode_cache.py, leaf `index_k`). At a token
step the indexer's query heads `q_I` [Hi, Di] and a weight a head `w` [Hi]
score every live position of the row,

    I(p) = sum_j w[j] * relu(q_I[j] . k_I[p])          p < length[b]

and the expensive attention then reads the `index_topk` best alone
(ops/index_select.py, ops/sparse_latent_decode.py). 2 Hi Di operations for Di
cached numbers a position: 64 heads of 128 in bf16 stand at 64 operations a
byte, under the chip's ridge, so the kernel is bound by the read of the keys.

`index_scores` is the one implementation on the path: a Pallas kernel
(`dsa_index`) that streams blocks of positions past the row's resident
queries, float32 products and sums, and writes NEG_INF at and past the row's
length (blocks wholly past it are not read). Interpreted on the CPU backend,
like the other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops.pallas_attention import NEG_INF, _dot, _NT, _use_interpret

# positions a grid step streams: 2 MB of 128 bf16, its [Hi, block] float32
# scores 2 MB more (0.270 ms a layer at 16 x 32,768, 0.291 at 4,096, 0.345 at 1,024; XLA 0.314:
# PERF.md, PR 39); a last block that overhangs the cache reads anything
# there, which the length masks
BLOCK_POSITIONS = 8192


def _kernel(lengths_ref, q_ref, w_ref, k_ref, o_ref, *, block):
    """Grid (row, block of positions): the row's queries and weights stay put
    while the keys' blocks stream through; a block at or past the row's
    length writes NEG_INF (and its index map names the last live block again:
    no copy)."""
    b, j = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(j * block < length)
    def _score():
        s = jnp.maximum(_dot(q_ref[0], k_ref[0], _NT), 0.0)  # [Hi, block]
        total = jnp.sum(s * w_ref[0], axis=0, keepdims=True)  # [1, block]
        col = j * block + lax.broadcasted_iota(jnp.int32, total.shape, 1)
        o_ref[0] = jnp.where(col < length, total, NEG_INF)

    @pl.when(j * block >= length)
    def _dead():
        o_ref[0] = jnp.full(o_ref.shape[1:], NEG_INF, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _emit(q, w, keys, lengths, *, block, interpret):
    rows, heads, width = q.shape
    n_blocks = -(-keys.shape[1] // block)

    def at(b, j, lengths_ref):  # the block read at step j: the last live one at most
        return (b, jnp.minimum(j, jnp.maximum(lengths_ref[b] - 1, 0) // block), 0)

    row = lambda b, j, lengths_ref: (b, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, block=block),
        name="dsa_index",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, n_blocks),
            in_specs=[
                pl.BlockSpec((1, heads, width), row),
                pl.BlockSpec((1, heads, 1), row),
                pl.BlockSpec((1, block, width), at),
            ],
            out_specs=pl.BlockSpec((1, 1, block), lambda b, j, n: (b, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 1, n_blocks * block), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, w[..., None].astype(jnp.float32), keys)
    return out[:, 0, :keys.shape[1]]


def index_scores(q, w, keys, lengths):
    """[B, L] float32: `q` [B, Hi, Di] (the indexer's query heads of one
    token a row) and `w` [B, Hi] (a weight a head, every constant folded in)
    against `keys` [B, L, Di], row b over its first `lengths[b]` positions;
    NEG_INF at and past them."""
    block = min(BLOCK_POSITIONS, keys.shape[1])
    return _emit(q, w, keys, lengths, block=block, interpret=_use_interpret())
