"""Latent decode attention over SELECTED positions: learned sparse attention's
token step.

`ops/latent_decode.py` attends every live position of a row. Here a row
attends the `counts[b]` positions `indices[b]` names (ops/index_select.py)
and reads nothing else of the cache:

    s(j)    = (q_c[b, h] . c[b, i_j] + q_r[b, h] . k_r[b, i_j]) * sm_scale   j < counts[b]
    o[b, h] = sum_j softmax(s)(j) c[b, i_j]

Two parts, both under the caller's scope (`mla_attend`: the attend with its
fetch). The FETCH gathers the selected positions into dense arrays of `k`
positions a row; the ATTEND is the dense kernel `decode_latent` over those,
row b to its `counts[b]`. The softmax does not care for the order of its
positions, so nothing is put back in place. The attend's cost is that of `k`
positions whatever the cache holds, and it is small (0.02 ms a layer at 16 x
2,048); the fetch is what a step pays for reading a sixteenth of a 32k cache,
and it pays by the ROWS it touches, not by their bytes: 27 ns a selected
position a leaf, the 128-byte rotary key's fetch dearer than the 1,024-byte
latent's (PERF.md, PR 39 and PR 41). So the cache of a layer that selects
(models/decode_cache.py, an indexed latent layer) keeps a position's latent
and its rotary key side by side in one row, the fetch is ONE gather of `k`
rows, and the two operands `decode_latent` takes are cut from the fetched rows
(38 MB a layer at 16 x 2,048 x 576, where the leaf is 692). A DMA a position
out of a leaf whose position is one whole tile compiles and was timed at 41 ns
a position, 2.4 times the gather's 17 (scripts/chip_fetch_selected.py).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from dalle_pytorch_tpu.ops.latent_decode import latent_decode_attention

_take = functools.partial(jnp.take_along_axis, mode="promise_in_bounds")


def split_rows(rows, rank: int, rope_dim: int):
    """`(latent [B, n, rank], rope [B, rope_dim, n])` cut from `rows` [B, n,
    >= rank + rope_dim], positions that each hold their latent, then their
    rotary key (then padding): what a two-leaf cache's readers are handed."""
    return rows[..., :rank], rows[..., rank:rank + rope_dim].transpose(0, 2, 1)


def fetch_selected(latent, rope, indices, widths=None):
    """`(latent [B, k, R], rope [B, dr, k])` of the positions `indices` [B, k]
    (in bounds). Out of an indexed layer's rows (`rope` None: `latent` is [B,
    L, >= R + dr] and `widths` (R, dr)) by one gather; out of the two leaves
    `latent` [B, L, R] and `rope` [B, dr, L] by one each."""
    if rope is None:
        return split_rows(_take(latent, indices[:, :, None], axis=1), *widths)
    return _take(latent, indices[:, :, None], axis=1), _take(rope, indices[:, None, :], axis=2)


def sparse_latent_decode_attention(q_c, q_r, latent, rope, indices, counts, *, sm_scale):
    """[B, H, R]: q_c [B, H, R] and q_r [B, H, dr] against the positions
    `indices` [B, k] of the cache as its layer keeps it (`fetch_selected`:
    rows with `rope` None, or the two leaves), row b over its first
    `counts[b]` indices."""
    picked, turned = fetch_selected(latent, rope, indices, (q_c.shape[-1], q_r.shape[-1]))
    return latent_decode_attention(q_c, q_r, picked, turned, counts, sm_scale=sm_scale)
