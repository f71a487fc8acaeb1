"""The selection of learned sparse attention: from a score a cached position
to the `k` positions the attention reads, as INDICES.

    S = the min(k, length) live positions of largest score; ties go to the
        lower position

Nothing is sorted. The k-th largest score of a row is found exactly by
counting (`ops/sampling.py:kth_largest`, 32 fused compare-and-count passes),
which gives the set as a mask: everything above the threshold, and of the
entries equal to it the first few by position. `selected_indices` then
compacts the mask to indices without a sort, a scatter or a gather, all of
which the chip does a row at a time: positions stand in blocks of 128, a
block's running count is one product with a triangular matrix, the block that
holds the j-th selected position is found by comparing j with the blocks'
totals, that block's running counts are fetched by a one-hot product, and the
lane is where they pass j's rank in the block. Two small products on the MXU
(their operands are 0, 1 and counts up to 128: exact in bfloat16) and
compares on the VPU; the indices come out in ascending order.
"""

from __future__ import annotations

import jax.numpy as jnp

from dalle_pytorch_tpu.ops.sampling import kth_largest

LANES = 128  # positions a block of the compaction holds


def selected_mask(scores: jnp.ndarray, lengths: jnp.ndarray, k: int):
    """`(mask [..., L] bool, count [...])`: the `count = min(k, length)`
    positions below `lengths` [...] of largest `scores` [..., L] float32,
    exactly that many a row: entries equal to the threshold are taken from
    the lowest position up. A row of length 0 selects nothing."""
    n = scores.shape[-1]
    live = jnp.arange(n) < lengths[..., None]
    scores = jnp.where(live, scores, -jnp.inf)
    count = jnp.minimum(lengths, k).astype(jnp.int32)
    kth = kth_largest(scores, jnp.maximum(count, 1))
    above = live & (scores > kth)
    equal = live & (scores == kth)
    need = count - jnp.sum(above, axis=-1, dtype=jnp.int32)
    rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) - equal  # equal entries before this one
    return above | (equal & (rank < need[..., None])), count


def selected_indices(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """[B, k] int32: the positions `mask` [B, L] holds, ascending; a row with
    fewer than k has 0 in the slots past its count (the caller knows the
    count: `selected_mask`)."""
    rows, n = mask.shape
    blocks = -(-n // LANES)
    m = jnp.pad(mask, ((0, 0), (0, blocks * LANES - n))).reshape(rows, blocks, LANES)
    upto = jnp.arange(LANES)[:, None] <= jnp.arange(LANES)[None, :]
    # a block's selected positions up to and with each lane
    running = jnp.einsum("bnw,wv->bnv", m.astype(jnp.bfloat16), upto.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    ends = jnp.cumsum(running[..., -1].astype(jnp.int32), axis=-1)  # [B, blocks]
    slots = jnp.arange(k, dtype=jnp.int32)
    block = jnp.sum(ends[:, None, :] <= slots[None, :, None], axis=-1, dtype=jnp.int32)  # [B, k]
    at = block[..., None] == jnp.arange(blocks)  # [B, k, blocks]; none where the slot is empty
    before = jnp.sum(jnp.where(at, (ends - running[..., -1].astype(jnp.int32))[:, None, :], 0),
                     axis=-1)
    mine = jnp.einsum("bkn,bnw->bkw", at.astype(jnp.bfloat16), running.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    rank = (slots[None, :] - before).astype(jnp.float32)  # the slot's place in its block
    lane = jnp.sum(mine <= rank[..., None], axis=-1, dtype=jnp.int32)
    return jnp.where(block < blocks, block * LANES + lane, 0)
