"""Rotary position embeddings for the joint text+image sequence.

Re-derivation of the reference's dual rotary scheme
(`/root/reference/dalle_pytorch/transformer.py:306-330`): each head gets
three rotary blocks —

  1. a 1-D "language" rotary over text positions, with every image position
     pinned to the far-away sentinel position 8192;
  2. a 2-D axial "pixel" rotary over the image feature-map grid (row and
     column coordinates in linspace(-1, 1)), with every text position pinned
     to the off-grid sentinel coordinate -10 on both axes.

rot_dim = dim_head // 3 per block; pairs are interleaved (adjacent even/odd
channels form a rotation pair), matching the rotary-embedding-torch
convention used by the reference (`attention.py:32-35`).

Everything here is precomputed host-side once and closed over by the jitted
step functions — it is static data, not traced computation.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def rotary_freqs_lang(rot_dim: int, theta: float = 10000.0) -> np.ndarray:
    """Inverse-frequency vector for ordinary (language) rotary embeddings."""
    return 1.0 / (theta ** (np.arange(0, rot_dim, 2)[: rot_dim // 2] / rot_dim))


def rotary_freqs_pixel(rot_dim: int, max_freq: float = 10.0) -> np.ndarray:
    """Frequency vector for 'pixel' rotary embeddings (coords in [-1, 1])."""
    return np.linspace(1.0, max_freq / 2.0, rot_dim // 2) * np.pi


def _angles(positions: np.ndarray, freqs: np.ndarray) -> np.ndarray:
    """Outer product position x freq, duplicated per rotation pair.

    Returns [..., 2 * len(freqs)] with layout [f0, f0, f1, f1, ...] so that
    adjacent channels share a rotation angle (interleaved-pair convention).
    """
    ang = np.einsum("...,f->...f", positions.astype(np.float64), freqs)
    return np.repeat(ang, 2, axis=-1)


def build_dalle_rotary(
    text_len: int,
    image_fmap_size: int,
    dim_head: int,
    theta: float = 10000.0,
    max_freq: float = 10.0,
    text_sentinel: float = 8192.0,
    pixel_sentinel: float = -10.0,
) -> jnp.ndarray:
    """Build the combined [seq_len + 1, 3 * 2*(rot_dim//2)] rotary angle table.

    `text_len` counts the <bos> token (reference: seq_len - img_seq_len + 1).
    Row layout: text rows first, then image rows in raster order.
    Channel layout: [text-1D block | image-row block | image-col block].
    """
    rot_dim = dim_head // 3
    img_seq_len = image_fmap_size * image_fmap_size

    lang = rotary_freqs_lang(rot_dim, theta)
    pixel = rotary_freqs_pixel(rot_dim, max_freq)

    # block 1: 1-D language rotary (text positions; images at far sentinel)
    text_block = np.concatenate(
        [
            _angles(np.arange(text_len), lang),
            _angles(np.full((img_seq_len,), text_sentinel), lang),
        ],
        axis=0,
    )

    # blocks 2+3: 2-D axial pixel rotary (texts at off-grid sentinel)
    coords = np.linspace(-1.0, 1.0, image_fmap_size)
    ax = _angles(coords, pixel)  # [fmap, d]
    row = np.broadcast_to(ax[:, None, :], (image_fmap_size, image_fmap_size, ax.shape[-1]))
    col = np.broadcast_to(ax[None, :, :], (image_fmap_size, image_fmap_size, ax.shape[-1]))
    img_axial = np.concatenate([row, col], axis=-1).reshape(img_seq_len, -1)

    text_sent = _angles(np.full((text_len,), pixel_sentinel), pixel)
    text_axial = np.concatenate([text_sent, text_sent], axis=-1)
    axial_block = np.concatenate([text_axial, img_axial], axis=0)

    table = np.concatenate([text_block, axial_block], axis=-1)
    return jnp.asarray(table, dtype=jnp.float32)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    """(x0, x1, x2, x3, ...) -> (-x1, x0, -x3, x2, ...) on the last axis."""
    x = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = x[..., 0], x[..., 1]
    return jnp.stack([-x2, x1], axis=-1).reshape(*x.shape[:-2], -1)


def apply_rotary(angles: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Rotate the first `angles.shape[-1]` channels of t; pass the rest through.

    angles: [..., n, d_rot] broadcastable against t[..., n, :d_rot].
    """
    d_rot = angles.shape[-1]
    angles = angles.astype(t.dtype)
    t_rot, t_pass = t[..., :d_rot], t[..., d_rot:]
    t_rot = t_rot * jnp.cos(angles) + _rotate_half(t_rot) * jnp.sin(angles)
    return jnp.concatenate([t_rot, t_pass], axis=-1)


# ------------------------------------------------- 1-D rotary, rotate-half
#
# The language-model path: one table per KIND of layer, channel i paired
# with channel i + dim/2 (the "rotate-half" convention of the published
# language models), q and k only. Angles, cosines and sines are float32
# whatever the model computes in: at position 8191 a bf16 angle is off by
# up to 32 radians (PERF.md, fault 1), which the DALL-E tables above keep
# only because their configurations state it.


def rotary_inv_freq(spec) -> np.ndarray:
    """[dim / 2] float32 inverse frequencies of a rotary `spec`:
    `{"type": "default", "dim", "theta"}`, or `"yarn"` with `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow` (YaRN,
    arXiv:2309.00071: the slow channels interpolated by `factor`, the fast
    ones left alone, a linear ramp between the two correction dims)."""
    dim, theta = int(spec["dim"]), float(spec["theta"])
    inv_freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if spec["type"] == "default":
        return inv_freq.astype(np.float32)
    if spec["type"] != "yarn":
        raise ValueError(f"unknown rotary type {spec['type']!r}")
    original = float(spec["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * np.log(original / (rotations * 2 * np.pi)) / (2 * np.log(theta))

    low = max(int(np.floor(correction_dim(float(spec["beta_fast"])))), 0)
    high = min(int(np.ceil(correction_dim(float(spec["beta_slow"])))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    interpolated = inv_freq / float(spec["factor"])
    return (interpolated * ramp + inv_freq * (1.0 - ramp)).astype(np.float32)


def rotary_cos_sin(positions, spec):
    """(cos, sin), each float32 [..., dim], of `positions` under `spec`;
    YaRN's `attention_factor` multiplies both."""
    inv_freq = jnp.asarray(rotary_inv_freq(spec))
    angles = jnp.asarray(positions, jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    scale = np.float32(spec.get("attention_factor", 1.0) if spec["type"] == "yarn" else 1.0)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rotary_half(cos: jnp.ndarray, sin: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Rotate every channel of t [..., n, dim] by float32 tables [n, dim],
    halves paired; the product is float32 and the result t's dtype. Tables
    narrower than t (a partial rotary) turn its first columns, halves of THOSE
    paired, and the rest passes."""
    rot = cos.shape[-1]
    if rot < t.shape[-1]:
        return jnp.concatenate([apply_rotary_half(cos, sin, t[..., :rot]), t[..., rot:]], axis=-1)
    x = t.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos + turned * sin).astype(t.dtype)
