"""Latent decode attention over SELECTED positions: learned sparse attention's
token step.

`ops/latent_decode.py` attends every live position of a row. Here a row
attends the `counts[b]` positions `indices[b]` names (ops/index_select.py)
and reads nothing else of the cache:

    s(j)    = (q_c[b, h] . c[b, i_j] + q_r[b, h] . k_r[b, i_j]) * sm_scale   j < counts[b]
    o[b, h] = sum_j softmax(s)(j) c[b, i_j]

Two parts, both under the caller's scope (`mla_attend`: the attend with its
fetch). The FETCH gathers the selected positions' latent rows and rotary
columns into two dense arrays of `k` positions a row; the ATTEND is the dense
kernel `decode_latent` over those, row b to its `counts[b]`. The softmax does
not care for the order of its positions, so nothing is put back in place.
The attend's cost is that of `k` positions whatever the cache holds; the
fetch moves `k` rows of the latent a row, and that is what a step pays for
reading a sixteenth of a 32k cache (PERF.md, PR 39).
"""

from __future__ import annotations

import jax.numpy as jnp

from dalle_pytorch_tpu.ops.latent_decode import latent_decode_attention


def fetch_selected(latent, rope, indices):
    """`(latent [B, k, R], rope [B, dr, k])` of the positions `indices` [B, k]
    (in bounds) out of `latent` [B, L, R] and `rope` [B, dr, L]."""
    picked = jnp.take_along_axis(latent, indices[:, :, None], axis=1, mode="promise_in_bounds")
    turned = jnp.take_along_axis(rope, indices[:, None, :], axis=2, mode="promise_in_bounds")
    return picked, turned


def sparse_latent_decode_attention(q_c, q_r, latent, rope, indices, counts, *, sm_scale):
    """[B, H, R]: q_c [B, H, R] and q_r [B, H, dr] against the positions
    `indices` [B, k] of `latent` [B, L, R] and `rope` [B, dr, L] (as the
    cache keeps them), row b over its first `counts[b]` indices."""
    picked, turned = fetch_selected(latent, rope, indices)
    return latent_decode_attention(q_c, q_r, picked, turned, counts, sm_scale=sm_scale)
