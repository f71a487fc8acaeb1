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
  * fp32 scores, softmax state and accumulators regardless of input dtype;
    the MXU operands keep the dtype they arrive in (bf16 stays bf16);
  * tiles chosen from the shape (`choose_tiles`), the streamed operand's
    tiles walked by a loop inside the kernel, and each kernel emitted
    through a jitted function, so a program holds each body once.

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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# what `flash_attention`'s forward rule hands its backward, by the names a
# `jax.checkpoint` policy may keep them under (`save_only_these_names`):
# q, k, v as the kernel took them, its result and its log-sum-exp
RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")

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


def _window_first_live_k(qi, block_q, block_k, window):
    """First k-block index a q block `qi` attends to under a sliding window
    (key p is seen by query t iff t - p < window): the block of the first
    row's oldest key. Shared, like the causal pair above, by the kernels'
    loop bounds and the DMA-skip index maps."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _window_last_live_q(ki, block_k, block_q, window):
    """Last q-block index that attends to k block `ki` under a sliding
    window (transposed twin of `_window_first_live_k`); may lie past the
    last block, callers clip."""
    return ((ki + 1) * block_k - 1 + window - 1) // block_q


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


# ------------------------------------------------------------------- tiles

#: VMEM a call may plan for: v5e's scoped default is 16 MiB a kernel, and
#: Mosaic's own temporaries (relayouts, the spilled score tile) want room.
VMEM_BUDGET = 12 * 1024 * 1024
_LANES = 128  # a [rows, 1] block is laid out [rows, 128] in VMEM

#: the two layouts an operand may arrive in: `[B, H, N, D]`, a head a block
#: of the second axis, or `[B, N, H, D]`, the columns of a projection as it
#: writes them, a head (or a lane row of heads) a block of the last
HEAD_MAJOR, TOKEN_MAJOR = "head_major", "token_major"

#: kernel bodies built (traced and lowered to Mosaic) by this process, the
#: tiles each distinct call shape got and the layout each body indexes:
#: what the jitted emitters below keep at 3 or 4 for a whole train step
#: where every call site used to build its own (tests pin it the way they
#: pin `obs.scopes.remembered`).
kernel_bodies = 0
tiles_chosen: dict = {}
layouts_built: dict = {}


def forget() -> None:
    """Drop the counters, the tiles and the emitters' trace caches (tests)."""
    global kernel_bodies
    kernel_bodies = 0
    tiles_chosen.clear()
    layouts_built.clear()
    for emit in (_emit_fwd, _emit_dq, _emit_dkv):
        emit.clear_cache()


def _built(kind: str, q, n_k: int, block_q: int, block_k: int, heads) -> None:
    global kernel_bodies
    kernel_bodies += 1
    tiles_chosen[(kind, q.shape, n_k, q.dtype.name)] = (block_q, block_k)
    layout = HEAD_MAJOR if heads is None else TOKEN_MAJOR
    layouts_built[layout] = layouts_built.get(layout, 0) + 1


def heads_per_block(d: int, hq: int, hkv: int) -> Optional[int]:
    """How many heads one column block of a token-major operand holds, from
    the head size alone: a head of a multiple of 128 lanes is a block by
    itself; narrower heads share a 128-lane block (a pair at 64), since a
    block's last dimension is a multiple of 128 or the whole, and then the
    block's heads must be the same K/V heads' as query heads'. None where
    neither holds: such a call keeps the head-major layout."""
    if d % _LANES == 0:
        return 1
    pack = _LANES // d
    if _LANES % d == 0 and hq == hkv and hq % pack == 0:
        return pack
    return None


def _sides(n: int) -> list:
    """The sides a score tile may take along a length: the whole length up
    to one lane row, else the multiples of 128 that DIVIDE the length
    rounded up to 128 (128, 256, 640, 1280 at 1280, never 512, which would
    pad every operand to 1536; 128, 384 at 257, over a padded 384)."""
    if n <= _LANES:
        return [max(n, 1)]
    n_pad = -(-n // _LANES) * _LANES
    return [c for c in range(_LANES, n_pad + 1, _LANES) if n_pad % c == 0]


def _span(n: int, block: int, row_bytes: int) -> int:
    """How much of a streamed operand one grid step holds: the whole row
    where a third of the budget takes it twice (always, at DALL-E
    lengths), else the longest run of whole blocks that divides it."""
    steps = -(-n // block)
    fit = max(VMEM_BUDGET // 3 // (2 * row_bytes * block), 1)
    return block * max(s for s in range(1, steps + 1) if steps % s == 0 and s <= fit)


def _spans(n_q: int, n_k: int, block_q: int, block_k: int, d: int, itemsize: int):
    """(span_q, span_k): the q rows `dkv` and the k rows `fwd`/`dq` keep
    resident per grid step, walked tile by tile inside the kernel. `d`: a
    block's width, the head's own or the 128 lanes narrower heads share."""
    span_q = _span(n_q, block_q, 2 * d * itemsize + 2 * _LANES * 4)
    span_k = _span(n_k, block_k, 2 * d * itemsize)
    return span_q, span_k


def vmem_bytes(block_q: int, block_k: int, span_q: int, span_k: int, d: int,
               itemsize: int, masked: bool = False) -> int:
    """What the widest of the three kernels holds in VMEM at these tiles:
    every operand block twice (Pallas double-buffers), `[rows, 1]` blocks
    at their lane-padded size, a static mask's int8 block, that kernel's
    own fp32 scratch (the forward's m, l and accumulator, `dq`'s
    accumulator, or `dkv`'s two), and four score tiles (s, p, dp, ds) in
    fp32. Heads that share a block's lanes share its `[rows, heads]`
    statistics too, and take their score tiles one after the other: their
    plan is one head's at `d` = the block's width."""
    rows = lambda n: n * _LANES * 4  # lse, delta, m, l
    fwd_dq = (2 * (3 * block_q * d * itemsize + 2 * span_k * d * itemsize
                   + 2 * rows(block_q) + masked * block_q * span_k)
              + block_q * d * 4 + 2 * rows(block_q))
    dkv = (2 * (2 * span_q * d * itemsize + 4 * block_k * d * itemsize
                + 2 * rows(span_q) + masked * span_q * block_k)
           + 2 * block_k * d * 4)
    return max(fwd_dq, dkv) + 4 * block_q * block_k * 4


def choose_tiles(n_q: int, n_k: int, d: int, dtype, masked: bool = False) -> tuple:
    """(block_q, block_k) for a call, from its shape alone: evaluated at
    trace time, nothing timed, nothing read from the environment. `d` is
    the width of a block: the head size, or 128 where narrower heads
    share a token-major block.

    The largest score tile that fits `VMEM_BUDGET`; of two as large the
    squarer, then the one with more keys. Measured on the v5e at
    16 x 16 x 1280 x 64 bf16 (PERF.md, PR 26): every tile pays one serial
    round of softmax state (row max and sum across lanes, `m`, `l`, the
    rescaled accumulator) whatever its width, so few wide tiles beat many
    narrow ones even though a causal diagonal then crosses more dead
    pairs: 640 x 640 runs fwd + fwd + dq + dkv in 8.4 ms a layer,
    256 x 256 in 15.5, 128 x 128 in 27.4; token-major, a pair of heads a
    block (PR 34): 640 x 640 in 9.0, 256 x 640 in 11.0.
    """
    itemsize = jnp.dtype(dtype).itemsize
    fits = [
        (bq * bk, min(bq, bk), bk, bq)
        for bq in _sides(n_q) for bk in _sides(n_k)
        if vmem_bytes(bq, bk, *_spans(n_q, n_k, bq, bk, d, itemsize), d, itemsize,
                      masked) <= VMEM_BUDGET
    ]
    # nothing fits only where one 128 x 128 tile's operands do not (a head
    # thousands wide): take the narrowest and let the compiler say so
    *_, bk, bq = max(fits) if fits else (_sides(n_k)[0], _sides(n_q)[0])
    return bq, bk


# ---------------------------------------------------------------- kernels
#
# All three run a grid (b, h, resident block, span of the streamed operand)
# and walk the span's tiles in a `lax.fori_loop` INSIDE the kernel: a grid
# step costs ~0.35 us on the v5e whatever it does (25,600 of them were the
# whole 11 ms of a 128 x 128 call), a loop step a few cycles, and a causal
# loop simply stops at the diagonal. MXU operands keep the dtype they
# arrive in (bf16 stays bf16: one MXU pass, not the multi-pass f32
# product); scores, softmax state and accumulators are fp32, and `p`/`ds`
# are rounded to the operand dtype only where they enter a dot, as the
# dense path's weights x V does.
#
# One body serves both layouts. Head-major blocks are `[1, 1, rows, d]`,
# token-major ones `[1, rows, width]` (`pack` given): the grid's second
# axis then counts column blocks, and where `pack` heads share a block's
# 128 lanes (a pair at head size 64) each takes its turn: its scores are
# `(q, zero outside its lanes) @ k^T` over all the lanes, which costs the
# MXU what a 64-wide contraction does; `p @ v` comes out a block wide and a
# lane select keeps the head's own; `ds^T @ (q, zeroed)` lands in the
# head's lanes by itself. Softmax state is per head: column `s` of a
# `[rows, pack]` block.

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """fp32 product of two MXU operands. Float32 operands multiply at the
    precision the process is set to, as they always did; narrower ones
    take the MXU's one native pass (a process-wide `highest` has no
    meaning for bf16 operands, and Mosaic refuses it)."""
    precision = None if a.dtype == jnp.float32 else lax.Precision.DEFAULT
    return lax.dot_general(
        a, b, dims, precision=precision, preferred_element_type=jnp.float32
    )


def even_block(leaf: int, cap: int) -> int:
    """The cached positions a grid step of a streaming decode kernel takes
    (ops/grouped_decode.py, ops/latent_decode.py): the leaf in the fewest
    blocks of at most `cap`, as even as whole lane tiles (128 positions)
    allow. A step computes one block while the next is fetched, so a short
    last block's fetch beside a full block's products hides nothing, and a
    full block's fetch beside the short one's products is waited for: under a
    cap of 2,560, 16,960 positions are 7 blocks of 2,432 (the last 2,368), not
    6 of 2,560 and one of 1,600."""
    steps = -(-leaf // cap)
    return min(leaf, -(-leaf // (128 * steps)) * 128)


def _scores(q, kb, *, sm_scale, row0, col0, causal, mask, n_real_k, n_real_q=None,
            window=None):
    """fp32 [bq, bk] scores of one tile with everything that is not
    attended set to NEG_INF. `row0`/`col0`: the tile's first row and
    column in the whole (padded) score matrix."""
    bq, bk = q.shape[0], kb.shape[0]
    s = _dot(q, kb, _NT) * sm_scale
    if causal:
        rel = (lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
               - lax.broadcasted_iota(jnp.int32, (bq, bk), 1))
        live = rel >= col0 - row0
        if window is not None:  # causal AND at most window - 1 keys back
            live &= rel < window + col0 - row0
        s = jnp.where(live, s, NEG_INF)
    if mask is not None:  # the static mask's tile, int8
        s = jnp.where(mask.astype(jnp.int32) != 0, s, NEG_INF)
    if n_real_k % bk != 0:  # key padding
        col = col0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(col < n_real_k, s, NEG_INF)
    if n_real_q is not None and n_real_q % bq != 0:
        # padded q rows have garbage lse: drop them (dk/dv sum over rows)
        row = row0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(row < n_real_q, s, NEG_INF)
    return s


def _walk(body, *, block, steps, lo, hi, occupied=None, t0=None):
    """Run `body(tile, start)` over tiles `lo` to `hi - 1` of this grid
    step's span: `tile` the tile's index in the whole row, `start` its
    first row in the span. `occupied(tile)` reads a static mask's
    occupancy layout: every tile is visited and the empty ones skipped.
    `t0`: the span's first tile, where the last grid axis does not count
    spans alone (dkv over a group of query heads)."""
    if t0 is None:
        t0 = pl.program_id(3) * steps

    def step(j, carry):
        start = pl.multiple_of(j * block, block)
        if occupied is None:
            body(t0 + j, start)
        else:
            pl.when(occupied(t0 + j) != 0)(lambda: body(t0 + j, start))
        return carry

    lax.fori_loop(lo, hi, step, 0)


def _walk_k(body, *, qi, bq, block_k, steps, causal, layout_ref, window=None):
    """The forward's and dq's walk over the live k tiles of the span: a
    causal loop ends at the diagonal, a windowed one also starts at the
    window's far edge."""
    lo, hi = 0, steps
    if causal:
        k0 = pl.program_id(3) * steps
        hi = jnp.clip(_causal_last_live_k(qi, bq, block_k) + 1 - k0, 0, steps)
    if window is not None:
        lo = jnp.clip(_window_first_live_k(qi, bq, block_k, window) - k0, 0, steps)
    _walk(body, block=block_k, steps=steps, lo=lo, hi=hi,
          occupied=None if layout_ref is None else lambda kj: layout_ref[qi, kj])


def _block_heads(ref, pack):
    """(lead, own): the unit axes in front of a block's rows, and for each
    head of the block the `[1, width]` mask of the lanes it owns (None: the
    whole block is one head's)."""
    if pack is None:
        return (0, 0), [None]
    if pack == 1:
        return (0,), [None]
    d = ref.shape[-1] // pack
    lane = lax.broadcasted_iota(jnp.int32, (1, ref.shape[-1]), 1)
    return (0,), [(lane >= s * d) & (lane < (s + 1) * d) for s in range(pack)]


def _own(x, lanes):
    """`x` with zeros outside a head's lanes: an MXU operand of that head
    alone, whichever lanes the other operand fills."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _join(parts, own):
    """One array of each head's: head `s` supplies the lanes it owns
    (`[rows, 1]` parts are spread over them)."""
    out = parts[-1]
    for part, lanes in zip(parts[-2::-1], own[-2::-1]):
        out = jnp.where(lanes, part, out)
    return out


def _fwd_kernel(*refs, sm_scale, block_k, causal, has_mask, n_real_k, steps,
                window=None, pack=None):
    """q block resident, k/v span resident, online softmax over its tiles;
    (m, l, acc) carry across spans in fp32 scratch and the normalized
    output flushes on the last one."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        layout_ref = mask_ref = None
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    lead, own = _block_heads(q_ref, pack)
    qi = pl.program_id(2)
    bq = q_ref.shape[-2]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(kj, start):
        cols = pl.ds(start, block_k)
        kb, vb = k_ref[(*lead, cols, slice(None))], v_ref[(*lead, cols, slice(None))]
        corrs, pvs = [], []
        for s, lanes in enumerate(own):
            one = slice(s, s + 1)
            sc = _scores(
                _own(q_ref[lead], lanes), kb, sm_scale=sm_scale, row0=qi * bq,
                col0=kj * block_k, causal=causal,
                mask=mask_ref[:, cols] if has_mask else None,
                n_real_k=n_real_k, window=window,
            )
            m = m_ref[:, one]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m - m_new)
            m_ref[:, one] = m_new
            l_ref[:, one] = l_ref[:, one] * corr + jnp.sum(p, axis=-1, keepdims=True)
            corrs.append(corr)
            pvs.append(_dot(p.astype(vb.dtype), vb))
        acc_ref[...] = acc_ref[...] * _join(corrs, own) + _join(pvs, own)

    _walk_k(attend, qi=qi, bq=bq, block_k=block_k, steps=steps, causal=causal,
            layout_ref=layout_ref, window=window)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        safe_l = [jnp.maximum(l_ref[:, s:s + 1], 1e-30) for s in range(len(own))]
        o_ref[lead] = (acc_ref[...] / _join(safe_l, own)).astype(o_ref.dtype)
        for s, safe in enumerate(safe_l):  # [bq, 1] a head
            lse_ref[0, 0, :, s:s + 1] = m_ref[:, s:s + 1] + jnp.log(safe)


def _dq_kernel(*refs, sm_scale, block_k, causal, has_mask, n_real_k, steps,
               window=None, pack=None):
    """Same walk as the forward; dq accumulates in fp32 scratch."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         mask_ref, dq_ref, acc_ref) = refs
    else:
        layout_ref = mask_ref = None
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = refs
    lead, own = _block_heads(q_ref, pack)
    qi = pl.program_id(2)
    bq = q_ref.shape[-2]

    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(kj, start):
        cols = pl.ds(start, block_k)
        kb, vb = k_ref[(*lead, cols, slice(None))], v_ref[(*lead, cols, slice(None))]
        parts = []
        for s, lanes in enumerate(own):
            one = slice(s, s + 1)
            sc = _scores(
                _own(q_ref[lead], lanes), kb, sm_scale=sm_scale, row0=qi * bq,
                col0=kj * block_k, causal=causal,
                mask=mask_ref[:, cols] if has_mask else None,
                n_real_k=n_real_k, window=window,
            )
            p = jnp.exp(sc - lse_ref[0, 0, :, one])
            dp = _dot(_own(do_ref[lead], lanes), vb, _NT)
            ds = p * (dp - delta_ref[0, 0, :, one])  # sm_scale: once, at the flush
            parts.append(_dot(ds.astype(kb.dtype), kb))
        acc_ref[...] += _join(parts, own)

    _walk_k(attend, qi=qi, bq=bq, block_k=block_k, steps=steps, causal=causal,
            layout_ref=layout_ref, window=window)

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        dq_ref[lead] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale, block_q, causal, has_mask, n_real_q,
                n_real_k, steps, window=None, n_spans=None, pack=None):
    """Transposed walk: the k/v block is resident, the q/do/lse/delta span
    is resident, and the loop runs over the span's q tiles from the first
    one that attends to this k block (to the last, under a window). dk/dv
    accumulate in fp32 scratch. Where a group of query heads shares this
    K/V head (`n_spans` given), the last grid axis walks every span of
    every head of the group in turn and the sums run over all of them."""
    if has_mask:
        (layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         mask_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        layout_ref = mask_ref = None
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         dk_acc, dv_acc) = refs
    lead, own = _block_heads(k_ref, pack)
    ki = pl.program_id(2)
    bk = k_ref.shape[-2]
    if n_spans is None:
        q0, grouped = pl.program_id(3) * steps, {}
    else:  # the last axis counts (head of the group, span)
        q0 = (pl.program_id(3) % n_spans) * steps
        grouped = {"t0": q0}

    @pl.when(pl.program_id(3) == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def attend(qj, start):
        rows = pl.ds(start, block_q)
        for s, lanes in enumerate(own):
            one = slice(s, s + 1)
            qb = _own(q_ref[(*lead, rows, slice(None))], lanes)
            dob = _own(do_ref[(*lead, rows, slice(None))], lanes)
            sc = _scores(
                qb, k_ref[lead], sm_scale=sm_scale, row0=qj * block_q,
                col0=ki * bk, causal=causal,
                mask=mask_ref[rows, :] if has_mask else None, n_real_k=n_real_k,
                n_real_q=n_real_q, window=window,
            )
            p = jnp.exp(sc - lse_ref[0, 0, rows, one])
            dv_acc[...] += _dot(p.astype(dob.dtype), dob, _TN)
            dp = _dot(dob, v_ref[lead], _NT)
            ds = p * (dp - delta_ref[0, 0, rows, one])  # sm_scale: at the flush
            dk_acc[...] += _dot(ds.astype(qb.dtype), qb, _TN)

    lo, hi = 0, steps
    if causal:
        lo = jnp.clip(_causal_first_live_q(ki, bk, block_q) - q0, 0, steps)
    if window is not None:
        hi = jnp.clip(_window_last_live_q(ki, bk, block_q, window) + 1 - q0, 0, steps)
    _walk(attend, block=block_q, steps=steps, lo=lo, hi=hi, **grouped,
          occupied=None if layout_ref is None else lambda qj: layout_ref[qj, ki])

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        dk_ref[lead] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[lead] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------- emitters
#
# One jitted function per kernel, the statics as static arguments: JAX
# caches the trace by signature and lowers a jitted callee ONCE per
# program, so a 12-layer step holds each body once (forward, forward under
# remat, dq, dkv) and calls it 12 times, where every call site used to
# trace the body and lower it to Mosaic again (48 bodies, 2 to 3 s of
# every start, warm or cold). The callee's operations carry names relative
# to the call site's, so the instruction-to-component table
# (`obs/scopes.py`) still sees each call under its own layer and phase.
#
# `heads` = (query heads, K/V heads) says the operands are token-major,
# `[B, N, heads x d]` with the statistics `[B, blocks, N, pack]` (a column
# block's heads side by side); without it they are `[B, H, N, d]` and
# `[B, H, N, 1]`. The two differ in where a block's (batch, head, rows)
# land in the index tuple and in nothing else (`_spec`).

_PARALLEL = CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)
_STATICS = ("sm_scale", "block_q", "block_k", "causal", "n_real_q",
            "n_real_k", "interpret", "window", "heads")


def _windowed(window):
    """The kernel's `window=` where there is one: a call without hands its
    kernel the arguments it always had."""
    return {} if window is None else {"window": window}


def _dims(q_shape, k_shape, heads):
    """(b, query heads, K/V heads, n_q, n_k, d, pack) of a call from its
    operands' shapes; `pack` is None head-major, else the heads a column
    block holds."""
    if heads is None:
        b, hq, n_q, d = q_shape
        return b, hq, k_shape[1], n_q, k_shape[2], d, None
    hq, hkv = heads
    b, n_q, cols = q_shape
    d = cols // hq
    return b, hq, hkv, n_q, k_shape[1], d, heads_per_block(d, hq, hkv)


def _spec(rows, width, at, tokens=False):
    """A block of `rows` x `width` at the (batch, head or column block, row
    block) that `at(*grid indices)` names: `[1, 1, rows, width]` of a
    head-major operand (and of the statistics, in either layout),
    `[1, rows, width]` of a token-major one, whose heads are column
    blocks."""
    if not tokens:
        return pl.BlockSpec((1, 1, rows, width), lambda *g: (*at(*g), 0))

    def rows_first(*g):
        b_, h_, r = at(*g)
        return b_, r, h_

    return pl.BlockSpec((1, rows, width), rows_first)


def _k_span(span_k, block_q, causal, group=1, window=None):
    """Where the k/v span of grid step (i, j) lies. Causal: spans wholly
    above the diagonal are dead (the loop runs no tile of them), and
    re-indexing them to the last live span makes consecutive dead steps
    name the same block, whose copy Pallas then elides; under a window the
    spans wholly before it are dead the same way. The kernels' loop bounds
    and this map share `_causal_last_live_k` and `_window_first_live_k`:
    they must stay in lockstep. Query head `h_` reads K/V head
    `h_ // group`."""
    head = (lambda h_: h_) if group == 1 else (lambda h_: h_ // group)
    if causal and window is not None:
        return lambda b_, h_, i, j: (
            b_, head(h_), jnp.clip(j, _window_first_live_k(i, block_q, span_k, window),
                                   _causal_last_live_k(i, block_q, span_k)))
    if causal:
        return lambda b_, h_, i, j: (
            b_, head(h_), jnp.minimum(j, _causal_last_live_k(i, block_q, span_k)))
    return lambda b_, h_, i, j: (b_, head(h_), j)


def _resident(b_, h_, i, j):
    """The block that the grid's third axis walks."""
    return b_, h_, i


def _with_mask(in_specs, operands, layout, mask_pad, mask_spec):
    """Masked calls: the occupancy layout rides whole in SMEM in front,
    the token mask's tile behind."""
    # tracelint: disable=TL001 -- an optional operand's None-ness is static (a pytree with no leaves)
    if mask_pad is None:
        return in_specs, operands
    return (
        [pl.BlockSpec(memory_space=pltpu.SMEM), *in_specs, mask_spec],
        [layout, *operands, mask_pad],
    )


def _packed(pack):
    """The kernel's `pack=` for token-major operands, as `_windowed`."""
    return {} if pack is None else {"pack": pack}


@functools.partial(jax.jit, static_argnames=_STATICS)
def _emit_fwd(q, k, v, mask_pad, layout, *, sm_scale, block_q, block_k,
              causal, n_real_q, n_real_k, interpret, window=None, heads=None):
    b, h, hkv, n_q, n_k, d, pack = _dims(q.shape, k.shape, heads)
    lanes = pack or 1  # heads a block holds; its width is theirs together
    _, span_k = _spans(n_q, n_k, block_q, block_k, lanes * d, q.dtype.itemsize)
    _built("fwd", q, n_k, block_q, block_k, heads)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal,
        has_mask=mask_pad is not None, n_real_k=n_real_k,
        steps=span_k // block_k, **_windowed(window), **_packed(pack),
    )
    tokens = heads is not None
    qspec = _spec(block_q, lanes * d, _resident, tokens)
    kspec = _spec(span_k, lanes * d, _k_span(span_k, block_q, causal, h // hkv, window), tokens)
    in_specs, operands = _with_mask(
        [qspec, kspec, kspec], [q, k, v],
        layout, mask_pad, pl.BlockSpec((block_q, span_k), lambda b_, h_, i, j: (i, j)),
    )
    # the name is also the kernel's innermost scope, and the chip names the
    # custom call after it (`%fwd_flash.3`): forward, dq and dkv are told
    # apart by name alone, and all three still end in `_flash`
    return pl.pallas_call(
        kernel,
        name="fwd_flash",
        grid=(b, h // lanes, n_q // block_q, n_k // span_k),
        in_specs=in_specs,
        out_specs=[qspec, _spec(block_q, lanes, _resident)],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h // lanes, n_q, lanes), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
            pltpu.VMEM((block_q, lanes * d), jnp.float32),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _emit_dq(q, k, v, do, lse, delta, mask_pad, layout, *, sm_scale, block_q,
             block_k, causal, n_real_q, n_real_k, interpret, window=None, heads=None):
    b, h, hkv, n_q, n_k, d, pack = _dims(q.shape, k.shape, heads)
    lanes = pack or 1
    _, span_k = _spans(n_q, n_k, block_q, block_k, lanes * d, q.dtype.itemsize)
    _built("dq", q, n_k, block_q, block_k, heads)
    kernel = functools.partial(
        _dq_kernel, sm_scale=sm_scale, block_k=block_k, causal=causal,
        has_mask=mask_pad is not None, n_real_k=n_real_k,
        steps=span_k // block_k, **_windowed(window), **_packed(pack),
    )
    tokens = heads is not None
    qspec = _spec(block_q, lanes * d, _resident, tokens)
    rowspec = _spec(block_q, lanes, _resident)
    kspec = _spec(span_k, lanes * d, _k_span(span_k, block_q, causal, h // hkv, window), tokens)
    in_specs, operands = _with_mask(
        [qspec, kspec, kspec, qspec, rowspec, rowspec],
        [q, k, v, do, lse, delta], layout, mask_pad,
        pl.BlockSpec((block_q, span_k), lambda b_, h_, i, j: (i, j)),
    )
    return pl.pallas_call(
        kernel,
        name="dq_flash",
        grid=(b, h // lanes, n_q // block_q, n_k // span_k),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, lanes * d), jnp.float32)],
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=_STATICS)
def _emit_dkv(q, k, v, do, lse, delta, mask_pad, layout, *, sm_scale, block_q,
              block_k, causal, n_real_q, n_real_k, interpret, window=None, heads=None):
    b, h, hkv, n_q, n_k, d, pack = _dims(q.shape, k.shape, heads)
    lanes = pack or 1
    group = h // hkv  # query heads that share one K/V head
    span_q, _ = _spans(n_q, n_k, block_q, block_k, lanes * d, q.dtype.itemsize)
    n_spans = n_q // span_q
    _built("dkv", q, n_k, block_q, block_k, heads)
    kernel = functools.partial(
        _dkv_kernel, sm_scale=sm_scale, block_q=block_q, causal=causal,
        has_mask=mask_pad is not None, n_real_q=n_real_q, n_real_k=n_real_k,
        steps=span_q // block_q, **_windowed(window),
        **({} if group == 1 else {"n_spans": n_spans}), **_packed(pack),
    )

    def live_span(i, j):
        """Causal DMA skip, transposed: for k block i the dead q spans are
        the PREFIX before the first live one (and, under a window, the
        suffix after the last); the clamp re-indexes them to a live one so
        their copies are elided. The outer min keeps the index in range
        when n_k > n_q (a fully-dead k row's first live span would lie
        past the last one: the whole row is dead, so any in-range span
        serves; without the min the DMA reads out of bounds)."""
        if not causal:
            return j
        j = jnp.maximum(j, _causal_first_live_q(i, block_k, span_q))
        if window is not None:
            j = jnp.minimum(j, _window_last_live_q(i, block_k, span_q, window))
        return jnp.minimum(j, n_spans - 1)

    if group == 1:
        q_at = lambda b_, h_, i, j: (b_, h_, live_span(i, j))
    else:  # the last axis walks the group's heads, each head's spans in turn
        q_at = lambda b_, h_, i, j: (
            b_, h_ * group + j // n_spans, live_span(i, j % n_spans))
    tokens = heads is not None
    qspec = _spec(span_q, lanes * d, q_at, tokens)
    rowspec = _spec(span_q, lanes, q_at)
    kspec = _spec(block_k, lanes * d, _resident, tokens)
    in_specs, operands = _with_mask(
        [qspec, kspec, kspec, qspec, rowspec, rowspec],
        [q, k, v, do, lse, delta], layout, mask_pad,
        pl.BlockSpec((span_q, block_k), (lambda b_, h_, i, j: (j, i)) if group == 1
                     else (lambda b_, h_, i, j: (j % n_spans, i))),
    )
    return pl.pallas_call(
        kernel,
        name="dkv_flash",
        grid=(b, hkv // lanes, n_k // block_k, group * n_spans),
        in_specs=in_specs,
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, lanes * d), jnp.float32),
            pltpu.VMEM((block_k, lanes * d), jnp.float32),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(*operands)


# -------------------------------------------------------------- public API


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[np.ndarray] = None,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    layout: str = HEAD_MAJOR,
) -> jnp.ndarray:
    """Flash attention over q [B, Hq, N, D], k/v [B, Hkv, N, D] with an
    optional STATIC token mask. Hq is a multiple of Hkv: query head j reads
    K/V head `j // (Hq // Hkv)`, and dk/dv sum over the group.

    `layout="token_major"`: q [B, N, Hq, D], k/v [B, N, Hkv, D], the
    columns of a fused projection as it writes them, and the result
    [B, N, Hq, D], the columns the output projection contracts over; no
    operand, result or gradient is transposed on the way. The same kernels
    then take a head as a column block (`heads_per_block(D, Hq, Hkv)` must
    not be None: a head size that is a multiple of 128, or a divisor of it
    with as many K/V heads as query heads).

    `mask` must be a host-side numpy bool array [Nq, Nk] (True = attend); it
    is analyzed into a block-occupancy layout so empty tiles are skipped.
    Every query row must have at least one attendable key (enforced —
    see `mask_block_layout`). When `mask` is None and `causal=True`,
    causality is enforced in-kernel with a block-triangle loop bound and no
    materialized mask. Differentiable (custom VJP, recompute-based backward).

    The forward rule hands the backward q, k, v as the kernel took them, its
    result and its log-sum-exp, each under its name of `RESIDUAL_NAMES`
    (`jax.ad_checkpoint.checkpoint_name`): a `jax.checkpoint` policy that
    saves those names (`save_only_these_names`; the trunk's `remat_policy`
    `"flash_residuals"`, `"layer_residuals"`) keeps them across remat, and the
    backward then runs neither this forward kernel nor what made q, k and v
    a second time. Outside a checkpoint, and under a policy that does not
    know them, a name is the identity and lowers to nothing.

    `window` (causal, unmasked calls only): query t sees key p iff
    `0 <= t - p < window`. It is a second loop bound beside the causal one,
    in all three kernels and in the DMA skip, and the tiles that straddle
    either edge are masked from their positions; no mask is materialized.

    `block_q`/`block_k` are the sides of the score tile; left out, they are
    chosen from the shape (`choose_tiles`). Lengths a tile does not divide
    are zero-padded up to it and the padding masked.
    """
    assert q.ndim == 4, f"expected [B,H,N,D] or [B,N,H,D], got {q.shape}"
    tokens = layout == TOKEN_MAJOR
    assert tokens or layout == HEAD_MAJOR, layout
    heads_at, rows_at = (2, 1) if tokens else (1, 2)
    hq, hkv = q.shape[heads_at], k.shape[heads_at]
    assert hq % hkv == 0 and hkv == v.shape[heads_at], (
        f"{hq} query heads cannot share {hkv} K/V heads")
    n_q, n_k = q.shape[rows_at], k.shape[rows_at]
    if window is not None:
        assert causal and mask is None and n_q == n_k, (
            "a window is a bound of the causal, unmasked, self-attention walk")
    d = q.shape[3]
    pack = heads_per_block(d, hq, hkv) if tokens else None
    assert pack is not None or not tokens, (
        f"no column block holds whole heads of {d} ({hq} over {hkv}): use head_major")
    chosen = choose_tiles(n_q, n_k, d * (pack or 1), q.dtype, masked=mask is not None)
    block_q = chosen[0] if block_q is None else min(block_q, max(n_q, 1))
    block_k = chosen[1] if block_k is None else min(block_k, max(n_k, 1))
    scale = d**-0.5 if sm_scale is None else sm_scale
    interp = _use_interpret() if interpret is None else interpret

    # host arrays, handed to the emitters as they are: a device constant
    # made here would belong to whatever trace is open (a `jax.checkpoint`
    # body, say) and leak from it through the VJP's closures
    mask_pad = occupancy = None
    if mask is not None:
        assert mask.shape == (n_q, n_k), f"mask {mask.shape} != {(n_q, n_k)}"
        mask_pad, occupancy = mask_block_layout(mask, block_q, block_k)
        mask_pad = mask_pad.astype(np.int8)  # a bool block is held as int32

    static = dict(
        sm_scale=float(scale), block_q=block_q, block_k=block_k,
        causal=causal and mask is None, n_real_q=n_q, n_real_k=n_k,
        interpret=bool(interp), **_windowed(window),
        **({"heads": (hq, hkv)} if tokens else {}),
    )

    def row_sums(do, o):
        """delta [B, H, N, 1] (token-major: [B, blocks, N, pack], a
        block's heads side by side, as the forward writes lse)."""
        prod = do.astype(jnp.float32) * o.astype(jnp.float32)
        if not tokens:
            return jnp.sum(prod, axis=-1, keepdims=True)
        # a head's columns summed where they lie; only the sums, two numbers
        # a row and block, are laid out anew
        per_head = jnp.sum(prod.reshape(*prod.shape[:2], hq, d), axis=-1)
        return per_head.reshape(*prod.shape[:2], hq // pack, pack).transpose(0, 2, 1, 3)

    @jax.custom_vjp
    def _attn(q_, k_, v_):
        return _emit_fwd(q_, k_, v_, mask_pad, occupancy, **static)[0]

    def _attn_fwd(q_, k_, v_):
        o, lse = _emit_fwd(q_, k_, v_, mask_pad, occupancy, **static)
        # named HERE, on the values the rule hands the backward: a name on
        # the primal result outside would mark another variable
        q_, k_, v_, o, lse = (checkpoint_name(t, name) for t, name in
                              zip((q_, k_, v_, o, lse), RESIDUAL_NAMES))
        return o, (q_, k_, v_, o, lse)

    def _attn_bwd(res, do):
        q_, k_, v_, o, lse = res
        delta = row_sums(do, o)
        dq = _emit_dq(q_, k_, v_, do, lse, delta, mask_pad, occupancy, **static)
        dk, dv = _emit_dkv(q_, k_, v_, do, lse, delta, mask_pad, occupancy, **static)
        return dq, dk, dv

    _attn.defvjp(_attn_fwd, _attn_bwd)
    if tokens:  # a head's columns side by side: the projection's own layout
        q, k, v = (t.reshape(*t.shape[:2], -1) for t in (q, k, v))
    out = _attn(_pad_to(q, rows_at, block_q), _pad_to(k, rows_at, block_k),
                _pad_to(v, rows_at, block_k))
    if tokens:
        return out[:, :n_q].reshape(out.shape[0], n_q, hq, d)
    return out[:, :, :n_q, :]

