"""Pallas TPU flash attention with static-mask block sparsity.

This is the TPU-native replacement for the reference's DeepSpeed CUDA/Triton
block-sparse kernel (`/root/reference/dalle_pytorch/attention.py:339-398`,
built via `DS_BUILD_SPARSE_ATTN=1`, `install_deepspeed.sh`) and the
long-sequence fast path for every other attention pattern (full causal,
axial row/col, conv-like — `attention.py:39,103,225`), all of which are
static token masks in this framework (ops/masks.py).

Design:
  * classic flash attention: q blocks stay resident, k/v blocks stream
    through VMEM while an online-softmax accumulator (m, l, acc) builds the
    exact result — O(N) memory instead of O(N^2);
  * the static mask is analyzed host-side into a per-block occupancy layout;
    fully-empty (q-block, k-block) tiles are skipped entirely (`lax.cond`),
    so axial/conv/block-sparse patterns get real compute savings, and
    partially-occupied tiles apply the token-level mask streamed from the
    mask operand;
  * with no mask and `causal=True`, the k-loop bound is the block-triangle
    cut — no mask tensor ever materializes;
  * full custom-VJP: backward recomputes attention blockwise from the saved
    log-sum-exp (two kernels: dq over q blocks, dk/dv over k blocks), the
    same recompute-instead-of-store trade the reference's reversible layers
    make (`reversible.py:57-127`);
  * fp32 accumulation regardless of input dtype (bf16 inputs stay bf16 on
    the MXU operands).

Interpret mode (CPU) is selected automatically off-TPU so the full test
suite exercises these kernels without hardware.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

CompilerParams = pltpu.CompilerParams


def _use_interpret() -> bool:
    """Interpret on the CPU backend (the test suite's), compile anywhere
    else: a backend that cannot compile the kernel raises, it is never
    handed the emulator instead. Callers override per call with
    `interpret=`."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _causal_last_live_k(qi, block_q, block_k):
    """Last k-block index a causal q block `qi` attends to. Single source
    of truth for BOTH the kernels' liveness predicates and the DMA-skip
    index maps — they must stay in lockstep (a skip for a step the kernel
    treats as live would load stale data silently)."""
    return ((qi + 1) * block_q - 1) // block_k


def _causal_first_live_q(ki, block_k, block_q):
    """First q-block index that attends to causal k block `ki` (transposed
    twin of `_causal_last_live_k`)."""
    return (ki * block_k) // block_q


def mask_block_layout(mask: np.ndarray, block_q: int, block_k: int):
    """(padded token mask, [nq, nk] int32 occupancy layout) for a static mask.

    Every real query row must attend to at least one key: with a finite
    NEG_INF sentinel an all-masked row would softmax to a uniform average of
    its tile's values instead of the dense oracle's uniform-over-all-keys
    garbage — neither is meaningful, so we reject the mask outright.
    """
    mask = np.asarray(mask, dtype=bool)
    empty = ~mask.any(axis=1)
    if empty.any():
        raise ValueError(
            f"static attention mask has {int(empty.sum())} fully-masked query "
            f"row(s) (first: {int(np.argmax(empty))}); every query must be "
            "allowed to attend to at least one key"
        )
    nq = math.ceil(mask.shape[0] / block_q)
    nk = math.ceil(mask.shape[1] / block_k)
    padded = np.zeros((nq * block_q, nk * block_k), dtype=bool)
    padded[: mask.shape[0], : mask.shape[1]] = mask
    blocks = padded.reshape(nq, block_q, nk, block_k)
    layout = blocks.any(axis=(1, 3)).astype(np.int32)
    return padded, layout


# ------------------------------------------------------------------ forward


def _fwd_kernel(
    *refs,
    sm_scale: float,
    block_k: int,
    causal: bool,
    has_mask: bool,
    n_real_k: int,
    nk_blocks: int,
):
    """Grid (b, h, qi, ki): the q block stays put over the inner ki steps
    while [block_k, d] k/v tiles stream through (auto double-buffered), so
    VMEM holds one tile of each operand regardless of sequence length. The
    online-softmax state (m, l, acc) carries across ki in fp32 VMEM
    scratch and the normalized output flushes on the last step."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        layout_ref = mask_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    bq = q_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if causal and not has_mask:
        # block-triangle cut: k blocks strictly above the diagonal never run
        live = ki <= _causal_last_live_k(qi, bq, block_k)
    elif has_mask:
        live = layout_ref[qi, ki] != 0
    else:
        live = True

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [bq, d]
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)  # [bq, bk]
        col = ki * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        if causal and not has_mask:
            row = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            s = jnp.where(row >= col, s, NEG_INF)
        if has_mask:
            s = jnp.where(mask_ref[...], s, NEG_INF)
        if n_real_k % block_k != 0:  # mask key padding
            s = jnp.where(col < n_real_k, s, NEG_INF)
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
        safe_l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(safe_l)  # [bq, 1]


def _flash_forward(
    q, k, v, mask_pad, layout, *,
    sm_scale, block_q, block_k, causal, n_real_q, n_real_k, interpret,
):
    b, h, n_q, d = q.shape
    n_k = k.shape[2]
    nq_blocks = n_q // block_q
    nk_blocks = n_k // block_k
    has_mask = mask_pad is not None

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale,
        block_k=block_k,
        causal=causal,
        has_mask=has_mask,
        n_real_k=n_real_k,
        nk_blocks=nk_blocks,
    )
    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    if causal and not has_mask:
        # Causal DMA skip: k tiles strictly above the block diagonal are
        # dead (the kernel predicates compute with `live`), but a naive
        # j-index map still streams them in — ~2x K/V tile traffic at the
        # diagonal-heavy DALL-E lengths. Remapping every dead step to the
        # LAST live tile makes consecutive dead steps index the same
        # block, and Pallas elides the copy when the block index repeats,
        # so the dead region costs zero DMA. (min(j, ...) also keeps the
        # index in range: the clamp target never exceeds j itself.)
        k_idx = lambda b_, h_, i, j: (
            b_, h_, jnp.minimum(j, _causal_last_live_k(i, block_q, block_k)), 0
        )
    else:
        k_idx = lambda b_, h_, i, j: (b_, h_, j, 0)
    kspec = pl.BlockSpec((1, 1, block_k, d), k_idx)
    in_specs = [qspec, kspec, kspec]
    operands = [q, k, v]
    if has_mask:
        in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),  # layout, whole array
            *in_specs,
            pl.BlockSpec((block_q, block_k), lambda b_, h_, i, j: (i, j)),
        ]
        operands = [layout, q, k, v, mask_pad]

    # the name is also the kernel's innermost scope, and the chip names the
    # custom call after it (`%fwd_flash.3`): forward, dq and dkv are told
    # apart by name alone, and all three still end in `_flash`
    o, lse = pl.pallas_call(
        kernel,
        name="fwd_flash",
        grid=(b, h, nq_blocks, nk_blocks),
        in_specs=in_specs,
        out_specs=[
            qspec,
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, n_q, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, n_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            ),
        ),
        interpret=interpret,
    )(*operands)
    return o, lse


# ----------------------------------------------------------------- backward


def _dq_kernel(
    *refs, sm_scale, block_k, causal, has_mask, n_real_k, nk_blocks,
):
    """Grid (b, h, qi, ki): the q block stays put over the inner ki steps
    while [block_k, d] k/v tiles stream through — VMEM holds one tile of
    each operand regardless of sequence length (the previous revision gave
    every program instance the ENTIRE K/V, which scales VMEM with n_k).
    dq accumulates in an fp32 VMEM scratch across ki and flushes on the
    last step."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         mask_ref, dq_ref, acc_ref) = refs
    else:
        layout_ref = mask_ref = None
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs

    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bq = q_ref.shape[2]
    if causal and not has_mask:
        # k blocks strictly above the block triangle contribute nothing
        live = ki <= _causal_last_live_k(qi, bq, block_k)
    elif has_mask:
        live = layout_ref[qi, ki] != 0
    else:
        live = True

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * sm_scale
        col = ki * block_k + lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        if causal and not has_mask:
            row = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            s = jnp.where(row >= col, s, NEG_INF)
        if has_mask:
            s = jnp.where(mask_ref[...], s, NEG_INF)
        if n_real_k % block_k != 0:
            s = jnp.where(col < n_real_k, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        acc_ref[...] += jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk_blocks - 1)
    def _flush():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    *refs, sm_scale, block_q, causal, has_mask, n_real_q, n_real_k,
    block_k, nq_blocks,
):
    """Grid (b, h, ki, qi): the k/v blocks stay put over the inner qi steps
    while [block_q, d] q/do tiles stream through (bounded VMEM — see
    `_dq_kernel`). dk/dv accumulate in fp32 VMEM scratch across qi and
    flush on the last step."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         mask_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        layout_ref = mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs

    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    bk = k_ref.shape[2]
    if causal and not has_mask:
        # q blocks strictly below the k-block diagonal start never attend
        live = qi >= _causal_first_live_q(ki, bk, block_q)
    elif has_mask:
        live = layout_ref[qi, ki] != 0
    else:
        live = True

    @pl.when(live)
    def _attend():
        kb = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        vb = v_ref[0, 0].astype(jnp.float32)
        qb = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        dob = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # [bq, 1]
        delta = delta_ref[0, 0]
        col = ki * bk + lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * sm_scale
        if causal and not has_mask:
            row = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0
            )
            s = jnp.where(row >= col, s, NEG_INF)
        if has_mask:
            s = jnp.where(mask_ref[...], s, NEG_INF)
        if n_real_k % bk != 0:
            s = jnp.where(col < n_real_k, s, NEG_INF)
        if n_real_q % block_q != 0:  # padded q rows have garbage lse: drop them
            row = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0
            )
            s = jnp.where(row < n_real_q, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_acc[...] += jnp.dot(p.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc[...] += jnp.dot(ds.T, qb, preferred_element_type=jnp.float32)

    @pl.when(qi == nq_blocks - 1)
    def _flush():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(
    res, g, *, sm_scale, block_q, block_k, causal, n_real_q, n_real_k, interpret,
):
    q, k, v, o, lse, mask_pad, layout = res
    do = g
    b, h, n_q, d = q.shape
    n_k = k.shape[2]
    has_mask = mask_pad is not None

    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )

    nq_blocks = n_q // block_q
    nk_blocks = n_k // block_k

    # Both passes run a 4D grid with the reduction as the INNER dimension
    # and fp32 VMEM scratch carrying the accumulator across its steps; every
    # operand arrives as one [block, d] tile per step (auto double-buffered
    # by Pallas), so VMEM use is flat in sequence length — the previous
    # revision's whole-K/V ("kfull") BlockSpecs scaled VMEM with n_k and
    # became hostile at exactly the long sequences flash exists for.

    # dq: grid (b, h, qi, ki) — q-indexed tiles ignore ki, k-indexed use ki
    qspec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    if causal and not has_mask:
        # causal DMA skip (see _flash_forward): dead above-diagonal steps
        # re-index the last live k tile so Pallas elides their copies
        k_idx = lambda b_, h_, i, j: (
            b_, h_, jnp.minimum(j, _causal_last_live_k(i, block_q, block_k)), 0
        )
    else:
        k_idx = lambda b_, h_, i, j: (b_, h_, j, 0)
    kspec = pl.BlockSpec((1, 1, block_k, d), k_idx)
    rowspec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    dq_in = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    dq_ops = [q, k, v, do, lse, delta]
    if has_mask:
        dq_in = [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *dq_in,
            pl.BlockSpec((block_q, block_k), lambda b_, h_, i, j: (i, j)),
        ]
        dq_ops = [layout, *dq_ops, mask_pad]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal,
            has_mask=has_mask, n_real_k=n_real_k, nk_blocks=nk_blocks,
        ),
        name="dq_flash",
        grid=(b, h, nq_blocks, nk_blocks),
        in_specs=dq_in,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            ),
        ),
        interpret=interpret,
    )(*dq_ops)

    # dk/dv: grid (b, h, ki, qi) — k-indexed tiles ignore qi
    kspec2 = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    if causal and not has_mask:
        # causal DMA skip, transposed: for k block i the dead q tiles are
        # the PREFIX qi < first_live; clamp re-indexes them to the first
        # live tile so their copies are elided. The outer min keeps the
        # index in range when n_k > n_q (a fully-dead k row's first_live
        # would point past the last q block — the whole row is dead, so
        # any in-range tile serves; without the min the DMA reads out of
        # bounds)
        q_idx = lambda b_, h_, i, j: (
            b_, h_,
            jnp.minimum(
                jnp.maximum(j, _causal_first_live_q(i, block_k, block_q)),
                nq_blocks - 1,
            ),
            0,
        )
    else:
        q_idx = lambda b_, h_, i, j: (b_, h_, j, 0)
    qspec2 = pl.BlockSpec((1, 1, block_q, d), q_idx)
    rowspec2 = pl.BlockSpec((1, 1, block_q, 1), q_idx)
    dkv_in = [qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2]
    dkv_ops = [q, k, v, do, lse, delta]
    if has_mask:
        dkv_in = [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *dkv_in,
            pl.BlockSpec((block_q, block_k), lambda b_, h_, i, j: (j, i)),
        ]
        dkv_ops = [layout, *dkv_ops, mask_pad]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, block_q=block_q, causal=causal,
            has_mask=has_mask, n_real_q=n_real_q, n_real_k=n_real_k,
            block_k=block_k, nq_blocks=nq_blocks,
        ),
        name="dkv_flash",
        grid=(b, h, nk_blocks, nq_blocks),
        in_specs=dkv_in,
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            ),
        ),
        interpret=interpret,
    )(*dkv_ops)
    return dq, dk, dv


# -------------------------------------------------------------- public API


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[np.ndarray] = None,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention over [B, H, N, D] with an optional STATIC token mask.

    `mask` must be a host-side numpy bool array [Nq, Nk] (True = attend); it
    is analyzed into a block-occupancy layout so empty tiles are skipped.
    Every query row must have at least one attendable key (enforced —
    see `mask_block_layout`). When `mask` is None and `causal=True`,
    causality is enforced in-kernel with a block-triangle loop bound and no
    materialized mask. Differentiable (custom VJP, recompute-based backward).
    """
    assert q.ndim == 4, f"expected [B,H,N,D], got {q.shape}"
    n_q, n_k = q.shape[2], k.shape[2]
    d = q.shape[3]
    block_q = min(block_q, max(n_q, 1))
    block_k = min(block_k, max(n_k, 1))
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    if mask is not None:
        assert mask.shape == (n_q, n_k), f"mask {mask.shape} != {(n_q, n_k)}"
        mask_pad_np, layout_np = mask_block_layout(mask, block_q, block_k)
        mask_pad = jnp.asarray(mask_pad_np)
        layout = jnp.asarray(layout_np)
    else:
        mask_pad = layout = None

    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)

    static = dict(
        sm_scale=scale, block_q=block_q, block_k=block_k,
        causal=causal and mask is None, n_real_q=n_q, n_real_k=n_k,
        interpret=interp,
    )

    @jax.custom_vjp
    def _attn(q_, k_, v_):
        o, _ = _flash_forward(q_, k_, v_, mask_pad, layout, **static)
        return o

    def _attn_fwd(q_, k_, v_):
        o, lse = _flash_forward(q_, k_, v_, mask_pad, layout, **static)
        return o, (q_, k_, v_, o, lse, mask_pad, layout)

    def _attn_bwd(res, g):
        return _flash_backward(res, g, **static)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    out = _attn(qp, kp, vp)
    return out[:, :, :n_q, :]


def lib_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """jax's library TPU flash kernel (pallas.ops.tpu.flash_attention)
    behind the in-repo calling convention ([B, H, N, D], scale d^-0.5).

    Alternative backend to the in-repo `flash_attention` for plain
    causal/full attention (no static-mask block skipping — the library
    kernel has no occupancy layout). Exists so the on-chip A/B
    (`scripts/pallas_onchip.py`) can pick whichever is faster on real
    hardware; differentiable (the library defines its own custom VJP).

    CPU caveat: the interpret guard below covers only the forward trace;
    the library's custom-VJP backward traces its own pallas_calls at grad
    time, so CPU *training* with lib_flash must run the whole grad inside
    `pltpu.force_tpu_interpret_mode()` (tests do). On TPU none of this
    applies. This is a TPU-hardware option; `flash` is the portable one.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _lib,
    )

    scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale
    if _use_interpret():
        with pltpu.force_tpu_interpret_mode():
            return _lib(q, k, v, causal=causal, sm_scale=scale)
    return _lib(q, k, v, causal=causal, sm_scale=scale)
