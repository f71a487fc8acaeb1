"""Pallas TPU flash-decode: cached attention over a slot KV cache with
per-row live lengths.

The decode analog of `ops/pallas_attention.py`. During KV-cached decode the
dense path attends every query chunk against the ENTIRE fixed-shape cache
[B, H, max_len, D] — dead positions included, masked out in the softmax
epilogue — so every decode step pays max_len worth of K/V reads no matter
how short the live prefix is. Under continuous batching the waste compounds:
each slot row sits at its OWN position, and a freshly-admitted row drags the
full cache through the MXU for a prefix of a few hundred tokens.

Design (split-K over the key axis, cf. flash-decoding / "SparkAttention",
PAPERS.md):

  * grid (b, h, ki): the small query chunk (1..K tokens per slot row) stays
    resident in VMEM while [block_k, d] K/V tiles stream through; the
    online-softmax state (m, l, acc) carries across ki in fp32 VMEM scratch
    and the normalized output flushes on the last step — O(max_len) memory
    never materializes a [*, max_len] score row in HBM;
  * per-row liveness: `lengths[b]` (the row's cache index + the chunk size)
    arrives via scalar prefetch (SMEM), so K/V tiles fully above a row's
    live prefix are skipped ENTIRELY — the kernel predicates compute with
    `@pl.when`, and the DMA index map clamps dead steps to the row's last
    live tile (Pallas elides the copy when the block index repeats, the
    same trick as the causal skip in `ops/pallas_attention.py`), so a row
    at position p costs ceil(p/block_k) tiles of K/V traffic, not
    max_len/block_k;
  * within the live region, causality over the written prefix matches the
    dense cached path exactly: query row i (global position
    lengths[b] - n + i) attends to cache positions <= lengths[b] - n + i;
  * fp32 accumulation regardless of input dtype; no VJP (decode is
    inference-only — the training path keeps `flash_attention`'s
    recompute-based backward).

Dispatch lives in `models/attention.py` (`Attention._use_flash_decode`):
the dense cached path remains the fallback for pattern masks (static or
traced — a per-step row-sliced mask cannot drive the block skip) and for
small caches below `AUTO_FLASH_DECODE_MIN_LEN`. Interpret mode is selected
automatically off-TPU (same `_use_interpret` probe as the training kernel)
so CPU tests exercise the real kernel logic.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_pytorch_tpu.ops.pallas_attention import (
    NEG_INF,
    CompilerParams,
    _pad_to,
    _use_interpret,
)

#: minimum q-axis tile (fp32 sublane count) — single-token decode pads its
#: one query row up to this and slices the garbage rows back off
_MIN_BLOCK_Q = 8


def _last_live_block(length, block_k):
    """Index of the last K/V block holding a live position for a row of
    `length` live cache entries. Single source of truth for the kernel's
    liveness predicate AND the DMA-skip index map — they must stay in
    lockstep (a skipped copy for a step the kernel treats as live would
    compute on stale data silently)."""
    return jnp.maximum(length - 1, 0) // block_k


def _scale_rows(scale, block_k=None):
    """int8 scale sidecar [B|P, H, S] -> the kernel operand [B|P, H, 1, S]
    (padded along S to `block_k` when given). Mosaic wants the last two
    block dims divisible by (8, 128) or equal to the array's: a
    (1, 1, block_k) tile of a 3-D array has H in the sublane slot and is
    refused; (1, 1, 1, block_k) over the unit axis is legal and keeps the
    scales lane-dense in HBM (an [.., S, 1] layout would pad 1 -> 128)."""
    scale = scale.astype(jnp.float32)
    if block_k is not None:
        scale = _pad_to(scale, 2, block_k)
    return scale[:, :, None, :]


def _decode_kernel(
    lengths_ref, q_ref, *refs,
    sm_scale, block_k, n_real_q, nk_blocks, quantized=False, live_ref=None,
):
    """Grid (b, h, ki): the q chunk stays put over the inner ki steps while
    [block_k, d] K/V tiles stream through (auto double-buffered). Tiles
    fully above the row's live length never run — and never DMA (their
    index-map steps repeat the last live tile, so the copy is elided).

    `quantized=True` interleaves per-(position, head) fp32 scale refs
    ([1, block_k] tiles) after each int8 K/V ref and dequantizes IN KERNEL —
    the HBM read stays 1 byte/element; compute is fp32 as always.

    `live_ref` ([B, nk_blocks] int32 in SMEM, block-sparse mode) replaces
    the length-derived liveness predicate with a per-(row, tile) bitmap:
    a 0 entry skips the tile's compute here AND its DMA (the block-map
    scalar operand the sparse index maps read re-indexes the previous
    live tile, so Pallas elides the copy — the exact length-skip trick,
    generalized to holes). The bitmap arrives pre-ANDed with the length
    bound, so in-live-range causality still comes from `lengths_ref`."""
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if live_ref is None:
        live = ki <= _last_live_block(length, block_k)
    else:
        live = live_ref[b, ki] == 1

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [bq, d]
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            kb = kb * ks_ref[0, 0].reshape(block_k, 1)
            vb = vb * vs_ref[0, 0].reshape(block_k, 1)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # [bq, bk]
        bq = q.shape[0]
        col = ki * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        row = lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        # query row i sits at global position length - n + i; causal over
        # the written prefix (same mask the dense cached path builds in
        # models/attention.py) — this also masks the key padding, since
        # length <= n_real_k <= padded length
        s = jnp.where(col <= length - n_real_q + row, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32
        )

    @pl.when(ki == nk_blocks - 1)
    def _flush():
        # padded q rows (bq > n_real_q) DO accumulate — their causal bound
        # is wider than any real row's — but the caller slices them off;
        # the guard only protects the lengths == 0 corner (no live key at
        # all), which real callers never produce (lengths >= n >= 1)
        safe_l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Cached-decode attention with per-row live lengths and KV block skip.

    q: [B, H, n, D] — the current chunk's queries (n = 1 for single-token
       decode, larger for prefill chunks), already written into the cache;
    k, v: [B, H, S, D] — the fixed-shape slot cache AFTER the chunk write;
    lengths: [B] int — per-row live cache entries INCLUDING the chunk, i.e.
       the row's pre-chunk cache index + n. Query row i of batch row b
       attends to cache positions <= lengths[b] - n + i, exactly the mask
       the dense cached path applies.

    `k_scale`/`v_scale` ([B, H, S] fp32, both or neither) mark an int8
    cache: K/V tiles are dequantized inside the kernel (tile element *
    its position's scale) before the fp32 flash math — so the per-token
    HBM read is 1 byte/element and no fp copy of the cache ever
    materializes. The kernel takes them as [B, H, 1, S] in
    (1, 1, 1, block_k) tiles (`_scale_rows`) — the 3-D (1, 1, block_k)
    tile the TPU compiler refuses; tests/test_tpu_compile.py asks it.

    Matches `dense_attention(q, k, v, mask)` over that mask to fp32
    tolerance (pinned in tests/test_pallas_decode.py). Not differentiable
    by design — decode only.
    """
    b, h, n, d = q.shape
    s_len = k.shape[2]
    assert k.shape == v.shape == (b, h, s_len, d), (q.shape, k.shape, v.shape)
    assert lengths.shape == (b,), f"lengths {lengths.shape} != ({b},)"
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
    if quantized:
        assert k_scale.shape == v_scale.shape == (b, h, s_len), (
            k_scale.shape, (b, h, s_len),
        )
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    block_k = max(min(block_k, s_len), 1)
    qp = _pad_to(q, 2, _MIN_BLOCK_Q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    bq = qp.shape[2]
    nk_blocks = kp.shape[2] // block_k
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, s_len)

    kernel = functools.partial(
        _decode_kernel,
        sm_scale=scale,
        block_k=block_k,
        n_real_q=n,
        nk_blocks=nk_blocks,
        quantized=quantized,
    )
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, j, lens: (b_, h_, 0, 0))

    def k_idx(b_, h_, j, lens):
        # DMA skip: steps above the row's last live tile re-index that tile,
        # so Pallas elides their copies (repeat block index = no new DMA)
        return (b_, h_, jnp.minimum(j, _last_live_block(lens[b_], block_k)), 0)

    kspec = pl.BlockSpec((1, 1, block_k, d), k_idx)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    if quantized:
        sspec = pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b_, h_, j, lens: (
                b_, h_, 0, jnp.minimum(j, _last_live_block(lens[b_], block_k)),
            ),
        )
        ksp = _scale_rows(k_scale, block_k)
        vsp = _scale_rows(v_scale, block_k)
        in_specs = [qspec, kspec, sspec, kspec, sspec]
        operands = [qp, kp, ksp, vp, vsp]
    out = pl.pallas_call(
        kernel,
        name="decode_slots",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk_blocks),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interp,
    )(lengths, *operands)
    return out[:, :, :n, :]


# ------------------------------------------------- block-sparse tile skip
#
# Policy sparsity (axial / block-sparse attention layouts) generalizes the
# length skip: a row's dead KV tiles are not just the suffix above its live
# length but arbitrary HOLES the attention pattern never reads (an axial-row
# image query only attends its own feature-map row + the text prefix). The
# bitmap is per (batch row, KV tile), rides scalar prefetch next to the
# lengths, and drives BOTH the compute predicate and the DMA index map —
# so a skipped tile costs zero FLOPs and zero HBM traffic, and (since the
# int8 scale sidecars share the same index maps) zero scale reads too.
# An all-ones bitmap reduces the predicate and the index map to EXACTLY
# the length-skip forms above, which is the bit-identity pin the tests
# hold the sparse kernel to.


def _sparse_maps(lengths, block_bitmap, block_k, nk_blocks):
    """Per-(row, tile) liveness + DMA re-index maps for the sparse kernels.

    live[b, j]  = bitmap says read it AND tile j intersects the live prefix;
    bmap[b, j]  = j for live tiles, else the nearest live tile index <= j
                  (0 before the first live tile — that one copy is real but
                  its compute is predicated off). Consecutive dead steps
                  repeat an index, so Pallas elides their DMAs.

    Both are traced int32 — policy flips never recompile (the bitmap is
    DATA, not structure)."""
    j = lax.broadcasted_iota(jnp.int32, (lengths.shape[0], nk_blocks), 1)
    llb = _last_live_block(lengths, block_k)[:, None]
    live = (block_bitmap != 0) & (j <= llb)
    bmap = jnp.maximum(lax.cummax(jnp.where(live, j, -1), axis=1), 0)
    return live.astype(jnp.int32), bmap.astype(jnp.int32)


def _sparse_decode_kernel(lengths_ref, live_ref, bmap_ref, q_ref, *refs, **kw):
    """Online-softmax body with the bitmap predicate; the block-map ref is
    consumed by the K/V BlockSpec index maps, not the body."""
    del bmap_ref
    _decode_kernel(lengths_ref, q_ref, *refs, live_ref=live_ref, **kw)


def block_sparse_flash_decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    block_bitmap: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """`flash_decode_attention` with per-row per-KV-tile policy skipping.

    block_bitmap: [B, ceil(S/block_k)] int (nonzero = tile may be read) —
    tile j of row b covers cache positions [j*block_k, (j+1)*block_k).
    Within live tiles the causal-over-prefix mask still applies, so an
    all-ones bitmap is bit-identical to `flash_decode_attention` (same
    tile order, same predicates, same accumulation — pinned in tests).
    The bitmap is traced data: policy changes never trigger a compile.

    int8 caches pass `k_scale`/`v_scale` as usual; the scale sidecars ride
    the same block-map index maps, so a skipped tile skips its scale read.
    """
    b, h, n, d = q.shape
    s_len = k.shape[2]
    assert k.shape == v.shape == (b, h, s_len, d), (q.shape, k.shape, v.shape)
    assert lengths.shape == (b,), f"lengths {lengths.shape} != ({b},)"
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
    if quantized:
        assert k_scale.shape == v_scale.shape == (b, h, s_len), (
            k_scale.shape, (b, h, s_len),
        )
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    block_k = max(min(block_k, s_len), 1)
    qp = _pad_to(q, 2, _MIN_BLOCK_Q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    bq = qp.shape[2]
    nk_blocks = kp.shape[2] // block_k
    assert block_bitmap.shape == (b, nk_blocks), (
        f"block_bitmap {block_bitmap.shape} != ({b}, {nk_blocks}) "
        f"for S={s_len}, block_k={block_k}"
    )
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, s_len)
    live_map, block_map = _sparse_maps(
        lengths, block_bitmap, block_k, nk_blocks
    )

    kernel = functools.partial(
        _sparse_decode_kernel,
        sm_scale=scale,
        block_k=block_k,
        n_real_q=n,
        nk_blocks=nk_blocks,
        quantized=quantized,
    )
    qspec = pl.BlockSpec(
        (1, 1, bq, d), lambda b_, h_, j, lens, live, bmap: (b_, h_, 0, 0)
    )

    def k_idx(b_, h_, j, lens, live, bmap):
        # dead steps re-index the nearest preceding live tile -> copy elided
        return (b_, h_, bmap[b_, j], 0)

    kspec = pl.BlockSpec((1, 1, block_k, d), k_idx)
    in_specs = [qspec, kspec, kspec]
    operands = [qp, kp, vp]
    if quantized:
        sspec = pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b_, h_, j, lens, live, bmap: (b_, h_, 0, bmap[b_, j]),
        )
        ksp = _scale_rows(k_scale, block_k)
        vsp = _scale_rows(v_scale, block_k)
        in_specs = [qspec, kspec, sspec, kspec, sspec]
        operands = [qp, kp, ksp, vp, vsp]
    out = pl.pallas_call(
        kernel,
        name="decode_sparse",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, nk_blocks),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interp,
    )(lengths, live_map, block_map, *operands)
    return out[:, :, :n, :]


# ------------------------------------------------------------ paged KV cache
#
# The continuous engine's paged layout stores K/V as a pool of fixed-size
# pages [P, H, page_size, D] plus a per-row page table [B, n_pages] mapping
# logical block j of row b to a physical page (serving/paging.py owns the
# allocation; models/dalle.py the scatter/gather ops). Two decode-attention
# implementations sit behind `paged_decode_attention`:
#
#   "gather"  materialize each row's logical view with one gather and run
#             the EXACT `flash_decode_attention` kernel above. Same tile
#             boundaries, same online-softmax accumulation order — so the
#             paged engine is bit-for-bit identical to the slotted one
#             (the parity contract tests/test_paging.py pins). Costs one
#             transient contiguous copy of the virtual cache per dispatch.
#   "kernel"  the true paged kernel: the page table rides scalar prefetch
#             and the K/V index maps dereference it per grid step, so a row
#             at position p streams only its ceil(p/page_size) live pages
#             out of HBM — no contiguous copy ever materializes. Tile size
#             equals the page size, so its accumulation ORDER differs from
#             the slotted kernel's; it matches the gather oracle to fp32
#             tolerance (pinned), not bit-for-bit.
#
# Default is "gather" (bit-exactness is the serving stack's contract and
# CPU-hosted tests exercise it end to end); flip `PAGED_DECODE_IMPL` or set
# DALLE_PAGED_DECODE_IMPL=kernel to arm the bandwidth-optimal path on TPU.

PAGED_DECODE_IMPL = os.environ.get("DALLE_PAGED_DECODE_IMPL", "gather")


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray, vlen: int):
    """Contiguous per-row view of a paged K/V pool.

    pages: [P, H, page_size, D]; page_table: [B, n_pages] int32 physical
    page per logical block. Returns [B, H, vlen, D] — the first `vlen`
    positions of each row's logical sequence (positions no table entry was
    ever written for come from the garbage page; callers mask them).
    """
    b, n_pages = page_table.shape
    _, h, bs, d = pages.shape
    g = pages[page_table]  # [B, n_pages, H, bs, D]
    g = g.transpose(0, 2, 1, 3, 4).reshape(b, h, n_pages * bs, d)
    return g[:, :, :vlen, :]


def _paged_decode_kernel(lengths_ref, pt_ref, *refs, **kw):
    """Same online-softmax body as `_decode_kernel`; the page table ref is
    consumed by the K/V BlockSpec index maps, not the body."""
    del pt_ref
    _decode_kernel(lengths_ref, *refs, **kw)


def paged_flash_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Flash decode directly over the paged pool: grid step (b, h, j) DMAs
    physical page `page_table[b, j]`, and steps past the row's last live
    block re-index that block so Pallas elides the copy (the same dead-tile
    trick as the contiguous kernel). The causal-over-prefix mask is
    identical to `flash_decode_attention`'s.

    q: [B, H, n, D]; k_pages/v_pages: [P, H, page_size, D]; lengths: [B]
    live positions including the current chunk; page_table: [B, n_pages].
    Tile size == page_size (TPU wants page_size a multiple of 8 and D of
    128 off interpret mode). `k_scale`/`v_scale` ([P, H, page_size] fp32)
    mark an int8 pool — scale pages ride the SAME page-table indirection
    and dequant happens in kernel. fp32 accumulation; decode-only, no VJP.
    """
    b, h, n, d = q.shape
    p_total, hk, page_size, dk = k_pages.shape
    assert k_pages.shape == v_pages.shape and (hk, dk) == (h, d), (
        q.shape, k_pages.shape, v_pages.shape,
    )
    n_pages = page_table.shape[1]
    assert page_table.shape == (b, n_pages), (page_table.shape, b)
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
    if quantized:
        assert k_scale.shape == v_scale.shape == (p_total, h, page_size), (
            k_scale.shape, (p_total, h, page_size),
        )
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    qp = _pad_to(q, 2, _MIN_BLOCK_Q)
    bq = qp.shape[2]
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, n_pages * page_size)
    page_table = page_table.astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=scale,
        block_k=page_size,
        n_real_q=n,
        nk_blocks=n_pages,
        quantized=quantized,
    )
    qspec = pl.BlockSpec(
        (1, 1, bq, d), lambda b_, h_, j, lens, pt: (b_, h_, 0, 0)
    )

    def kv_idx(b_, h_, j, lens, pt):
        # dead steps re-index the row's last live page -> copy elided
        jc = jnp.minimum(j, _last_live_block(lens[b_], page_size))
        return (pt[b_, jc], h_, 0, 0)

    kvspec = pl.BlockSpec((1, 1, page_size, d), kv_idx)
    in_specs = [qspec, kvspec, kvspec]
    operands = [qp, k_pages, v_pages]
    if quantized:
        def sv_idx(b_, h_, j, lens, pt):
            jc = jnp.minimum(j, _last_live_block(lens[b_], page_size))
            return (pt[b_, jc], h_, 0, 0)

        svspec = pl.BlockSpec((1, 1, 1, page_size), sv_idx)
        in_specs = [qspec, kvspec, svspec, kvspec, svspec]
        operands = [
            qp, k_pages, _scale_rows(k_scale), v_pages, _scale_rows(v_scale),
        ]
    out = pl.pallas_call(
        kernel,
        name="decode_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, n_pages),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interp,
    )(lengths, page_table, *operands)
    return out[:, :, :n, :]


def _sparse_paged_decode_kernel(
    lengths_ref, pt_ref, live_ref, bmap_ref, q_ref, *refs, **kw
):
    """Paged sparse body: page table + block map feed the index maps."""
    del pt_ref, bmap_ref
    _decode_kernel(lengths_ref, q_ref, *refs, live_ref=live_ref, **kw)


def block_sparse_paged_flash_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    block_bitmap: jnp.ndarray,
    *,
    sm_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """`paged_flash_decode_attention` with policy tile skipping at PAGE
    granularity: block_bitmap is [B, n_pages] (one bit per page-table
    entry), and a dead page is never dereferenced — its grid step
    re-indexes the nearest preceding live page through the block map, so
    the physical-page DMA is elided along with the compute. An all-ones
    bitmap is bit-identical to `paged_flash_decode_attention`. int8 scale
    pages ride the same indirection and skip with their page."""
    b, h, n, d = q.shape
    p_total, hk, page_size, dk = k_pages.shape
    assert k_pages.shape == v_pages.shape and (hk, dk) == (h, d), (
        q.shape, k_pages.shape, v_pages.shape,
    )
    n_pages = page_table.shape[1]
    assert page_table.shape == (b, n_pages), (page_table.shape, b)
    assert block_bitmap.shape == (b, n_pages), (
        f"block_bitmap {block_bitmap.shape} != ({b}, {n_pages})"
    )
    quantized = k_scale is not None
    assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
    if quantized:
        assert k_scale.shape == v_scale.shape == (p_total, h, page_size), (
            k_scale.shape, (p_total, h, page_size),
        )
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    qp = _pad_to(q, 2, _MIN_BLOCK_Q)
    bq = qp.shape[2]
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, n_pages * page_size)
    page_table = page_table.astype(jnp.int32)
    live_map, block_map = _sparse_maps(
        lengths, block_bitmap, page_size, n_pages
    )

    kernel = functools.partial(
        _sparse_paged_decode_kernel,
        sm_scale=scale,
        block_k=page_size,
        n_real_q=n,
        nk_blocks=n_pages,
        quantized=quantized,
    )
    qspec = pl.BlockSpec(
        (1, 1, bq, d),
        lambda b_, h_, j, lens, pt, live, bmap: (b_, h_, 0, 0),
    )

    def kv_idx(b_, h_, j, lens, pt, live, bmap):
        # dead steps re-index the nearest preceding live PAGE -> copy elided
        return (pt[b_, bmap[b_, j]], h_, 0, 0)

    kvspec = pl.BlockSpec((1, 1, page_size, d), kv_idx)
    in_specs = [qspec, kvspec, kvspec]
    operands = [qp, k_pages, v_pages]
    if quantized:
        def sv_idx(b_, h_, j, lens, pt, live, bmap):
            return (pt[b_, bmap[b_, j]], h_, 0, 0)

        svspec = pl.BlockSpec((1, 1, 1, page_size), sv_idx)
        in_specs = [qspec, kvspec, svspec, kvspec, svspec]
        operands = [
            qp, k_pages, _scale_rows(k_scale), v_pages, _scale_rows(v_scale),
        ]
    out = pl.pallas_call(
        kernel,
        name="decode_sparse_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h, n_pages),
            in_specs=in_specs,
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, bq, d), q.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interp,
    )(lengths, page_table, live_map, block_map, *operands)
    return out[:, :, :n, :]


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    vlen: int,
    *,
    impl: Optional[str] = None,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    block_bitmap: Optional[jnp.ndarray] = None,
    sparse_block: Optional[int] = None,
) -> jnp.ndarray:
    """Flash-path dispatch for the paged cache — see the section comment
    above for the "gather" (bit-exact) vs "kernel" (bandwidth-optimal)
    trade. `vlen` is the virtual contiguous length the gather path crops
    to (the slotted cache's max_len, so tile boundaries match exactly).
    int8 pools pass their [P, H, page_size] scale pools: the gather path
    gathers int8 pages + scales and hands BOTH to the contiguous kernel
    (in-kernel dequant, identical math to the slotted quantized path),
    keeping the slotted-vs-paged parity contract on the quantized cache.

    `block_bitmap` ([B, ceil(vlen/sparse_block)], with `sparse_block` the
    policy's tile width) arms policy skipping: the gather path hands it to
    the contiguous sparse kernel at `sparse_block` granularity (same tile
    boundaries as the slotted engine, so paged-vs-slotted parity holds
    under sparsity too); the "kernel" path re-expands it to PAGE
    granularity (sparse_block must be a page_size multiple) so dead pages
    are never dereferenced through the table."""
    impl = PAGED_DECODE_IMPL if impl is None else impl
    if impl == "gather":
        k = paged_gather(k_pages, page_table, vlen)
        v = paged_gather(v_pages, page_table, vlen)
        kw = {}
        if k_scale is not None:
            kw = {
                "k_scale": paged_gather(
                    k_scale[..., None], page_table, vlen
                )[..., 0],
                "v_scale": paged_gather(
                    v_scale[..., None], page_table, vlen
                )[..., 0],
            }
        if block_bitmap is not None:
            assert sparse_block is not None, "sparse_block rides block_bitmap"
            return block_sparse_flash_decode_attention(
                q, k, v, lengths, block_bitmap,
                sm_scale=sm_scale, block_k=sparse_block, **kw,
            )
        return flash_decode_attention(q, k, v, lengths, sm_scale=sm_scale, **kw)
    assert impl == "kernel", f"unknown paged decode impl {impl!r}"
    if block_bitmap is not None:
        assert sparse_block is not None, "sparse_block rides block_bitmap"
        page_size = k_pages.shape[2]
        n_pages = page_table.shape[1]
        assert sparse_block % page_size == 0, (
            f"sparse_block {sparse_block} must be a multiple of "
            f"page_size {page_size} for the paged kernel"
        )
        bm = jnp.repeat(block_bitmap, sparse_block // page_size, axis=1)
        if bm.shape[1] < n_pages:
            # trailing pages beyond the policy's bitmap window: dead (the
            # live-length AND inside the kernel keeps this conservative)
            bm = jnp.pad(bm, ((0, 0), (0, n_pages - bm.shape[1])))
        else:
            bm = bm[:, :n_pages]
        return block_sparse_paged_flash_decode_attention(
            q, k_pages, v_pages, lengths, page_table, bm,
            sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
        )
    return paged_flash_decode_attention(
        q, k_pages, v_pages, lengths, page_table, sm_scale=sm_scale,
        k_scale=k_scale, v_scale=v_scale,
    )


# ------------------------------------------------- mesh-sharded dispatch
#
# A Pallas call is a single-device program: GSPMD cannot partition it, so
# a mesh-sharded serving engine must split the kernel EXPLICITLY. Heads
# are the natural cut (SNIPPETS.md [1]: shard_map-wrapped flash/paged
# attention with P(data, model, ...) specs): decode attention is
# head-independent, so each device runs the unmodified kernel over its
# own head shard and the concatenation over heads is exact — the sharded
# kernel is bit-for-bit the unsharded one, preserving the serving
# stack's decode-composition-invariance contract.


def sharded_flash_decode_attention(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    head_axis: str = "tp",
    sm_scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    block_bitmap: Optional[jnp.ndarray] = None,
    sparse_block: Optional[int] = None,
):
    """`flash_decode_attention` split over `head_axis` of `mesh` via
    shard_map. Heads that don't divide the axis fall back to the
    unsharded kernel — same drop-to-replicated posture as
    `serving_partition`'s divisibility rule. int8 caches hand their
    [B, H, S] scale leaves along — per-head scales split with the heads
    (reduction-free), so the sharded quantized kernel stays bit-identical
    to the unsharded quantized one. `block_bitmap`/`sparse_block` arm
    policy tile skipping: the bitmap is head-independent so it REPLICATES
    (P()) like the lengths and every head shard skips the same tiles."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def dispatch(q_, k_, v_, lens_, bm_=None, ks_=None, vs_=None):
        kw = {"sm_scale": sm_scale, "k_scale": ks_, "v_scale": vs_}
        if bm_ is not None:
            return block_sparse_flash_decode_attention(
                q_, k_, v_, lens_, bm_,
                block_k=128 if sparse_block is None else sparse_block, **kw,
            )
        return flash_decode_attention(q_, k_, v_, lens_, **kw)

    h = q.shape[1]
    # a mesh without the axis (custom caller-built meshes) falls back
    # unsharded rather than raising at trace time inside the chunk program
    axis_n = dict(mesh.shape).get(head_axis, 1)
    if axis_n == 1 or h % axis_n != 0:
        return dispatch(q, k, v, lengths, block_bitmap, k_scale, v_scale)
    spec = P(None, head_axis, None, None)
    args = [q, k, v, lengths]
    in_specs = [spec, spec, spec, P()]
    if block_bitmap is not None:
        args.append(block_bitmap)
        in_specs.append(P())
    if k_scale is not None:
        sspec = P(None, head_axis, None)
        args += [k_scale, v_scale]
        in_specs += [sspec, sspec]

    def call(q_, k_, v_, lens_, *rest):
        rest = list(rest)
        bm_ = rest.pop(0) if block_bitmap is not None else None
        ks_, vs_ = rest if rest else (None, None)
        return dispatch(q_, k_, v_, lens_, bm_, ks_, vs_)

    fn = shard_map(
        call,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec,
        check_vma=False,
    )
    return fn(*args)


def sharded_paged_decode_attention(
    mesh,
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    page_table: jnp.ndarray,
    vlen: int,
    *,
    head_axis: str = "tp",
    impl: Optional[str] = None,
    sm_scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    block_bitmap: Optional[jnp.ndarray] = None,
    sparse_block: Optional[int] = None,
):
    """`paged_decode_attention` split over `head_axis` of `mesh`: the page
    pool shards at its HEAD axis (axis 1 of [P, H, page_size, D]) — pages
    stay whole per device because the host page table addresses physical
    pages globally — and the table + lengths replicate, so every device
    dereferences the same logical->physical mapping over its own head
    shard. Both impls ("gather" and the per-page-DMA "kernel") run the
    unmodified single-device code per shard; the head concat is exact, so
    sharded paged decode is bit-identical to single-device paged decode.
    Never split the PAGE axis: a page-split pool silently reads other
    rows' pages through the global table (tracelint TL008 flags it).
    `block_bitmap`/`sparse_block` replicate (P()) like the page table —
    policy skipping is head-independent."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def dispatch(q_, kp_, vp_, lens_, pt_, bm_=None, ks_=None, vs_=None):
        return paged_decode_attention(
            q_, kp_, vp_, lens_, pt_, vlen, impl=impl, sm_scale=sm_scale,
            k_scale=ks_, v_scale=vs_,
            block_bitmap=bm_, sparse_block=sparse_block,
        )

    h = q.shape[1]
    axis_n = dict(mesh.shape).get(head_axis, 1)
    if axis_n == 1 or h % axis_n != 0:
        return dispatch(
            q, k_pages, v_pages, lengths, page_table,
            block_bitmap, k_scale, v_scale,
        )
    spec = P(None, head_axis, None, None)
    args = [q, k_pages, v_pages, lengths, page_table]
    in_specs = [spec, spec, spec, P(), P()]
    if block_bitmap is not None:
        args.append(block_bitmap)
        in_specs.append(P())
    if k_scale is not None:
        sspec = P(None, head_axis, None)
        args += [k_scale, v_scale]
        in_specs += [sspec, sspec]

    def call(q_, kp_, vp_, lens_, pt_, *rest):
        rest = list(rest)
        bm_ = rest.pop(0) if block_bitmap is not None else None
        ks_, vs_ = rest if rest else (None, None)
        return dispatch(q_, kp_, vp_, lens_, pt_, bm_, ks_, vs_)

    fn = shard_map(
        call,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec,
        check_vma=False,
    )
    return fn(*args)
