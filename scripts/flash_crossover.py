"""Dense↔flash crossover measurement: sets AUTO_FLASH_MIN_SEQ and
AUTO_FLASH_DECODE_MIN_LEN from data instead of folklore.

Methodology (the same hardware-free instrument as `scripts/hbm_model.py`,
whose r4 ladder the live TPU bench later validated): AOT-compile the REAL
dense attention program per sequence length on the CPU backend (same HLO
structure as TPU), read `compiled.cost_analysis()` FLOPs/bytes, and place
both kernels on the v5e roofline (197 TFLOP/s, ~819 GB/s):

  * dense: measured op-level bytes include the [B, H, N, N] fp32 score
    chain the fused MXU epilogue cannot eliminate once it spills VMEM;
  * flash: analytic tile traffic, EXACT from the kernel's BlockSpecs
    (q/o streamed once per q block; k/v once per LIVE (qi, ki) tile under
    the causal DMA skip — `_causal_last_live_k` is imported, not re-derived)
    plus the same measured matmul FLOPs halved by the causal block cut.

The prefill/training crossover is the first N where the dense program goes
BANDWIDTH-bound (bytes/BW > flops/peak): below it both kernels are
compute-bound and dense's tighter fusion wins (the r4 on-chip finding:
dense == fully-levered flash wall time at 1280 under dispatch overhead);
above it dense pays score traffic that flash simply does not have.

The decode crossover compares one cached step's K/V reads: dense always
reads the whole [B, H, max_len, D] cache; flash-decode reads
ceil(live/block_k) tiles (expected live ~ max_len/2 over an image) plus a
per-kernel overhead charge. Emits one JSON line per seq and a final
recommendation line. This is a roofline MODEL over compiled-program cost
analysis, computed on the CPU — not a chip measurement; the on-chip
wall-clock A/B (`scripts/pallas_onchip.py`) is the final decider.

`--sparse` runs the BLOCK-SPARSE decode sweep instead: for the flagship axial-row layout it reduces the static
pattern to per-row KV-tile bitmaps at several tile widths (the same
`ops/masks.py:mask_to_block_bitmap` reduction the serving policy ships at
runtime) and models, per width, the expected tiles read/skipped over a
full image decode plus the roofline step time with a per-tile grid charge.
The tension it quantifies: thin tiles skip more (a tile one live position
touches is read whole) but pay more grid steps; wide tiles amortise grid
overhead but smear the pattern. The sweep is what justifies
`DECODE_SPARSE_BLOCK = 128` in models/attention.py.

Usage: JAX_PLATFORMS=cpu python scripts/flash_crossover.py [--sparse]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# shared with the live serving-side accounting (obs/vitals.py:
# ProgramCostTable) so offline and live rooflines cannot drift
from dalle_pytorch_tpu.obs.vitals import (  # noqa: E402
    V5E_HBM_BPS, V5E_PEAK_FLOPS, extract_cost,
)
#: in-program Mosaic kernel overhead per pallas_call (grid setup; NOT a
#: host dispatch — the kernel runs inside the jitted step)
KERNEL_OVERHEAD_S = 5e-6

# serving/training flagship geometry: heads 16, head dim 64
BATCH, HEADS, DIM_HEAD = 4, 16, 64
BLOCK = 128
SEQS = (256, 384, 512, 640, 768, 1024, 1280, 1536, 2048, 4096)

# flagship text/image split: 256 text tokens + <bos>, fmap 32 -> 1024
# image tokens, decode cache max_len 1281
TEXT_SEQ, FMAP = 256, 32
#: per-grid-step charge inside the Mosaic kernel (DMA issue + bookkeeping
#: per (head, kv-tile) step) — the cost thin tiles multiply
TILE_STEP_OVERHEAD_S = 1e-7
SPARSE_BLOCKS = (32, 64, 128, 256, 512)


def measured_dense(seq, dtype):
    """cost_analysis FLOPs/bytes of the compiled dense causal attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.ops.attention_core import dense_attention

    mask = jnp.asarray(np.tril(np.ones((seq, seq), dtype=bool))[None, None])
    q = jnp.zeros((BATCH, HEADS, seq, DIM_HEAD), dtype)

    compiled = (
        jax.jit(lambda q_, k_, v_: dense_attention(q_, k_, v_, mask=mask))
        .lower(q, q, q)
        .compile()
    )
    cost = extract_cost(compiled)
    return float(cost["flops"]), float(cost["bytes accessed"])


def flash_tile_bytes(seq, itemsize):
    """Exact causal-skip K/V tile traffic of the flash forward at this seq
    (q/o once per q block; k/v once per live (qi, ki) tile)."""
    from dalle_pytorch_tpu.ops.pallas_attention import _causal_last_live_k

    nq = -(-seq // BLOCK)
    live_tiles = sum(
        min(_causal_last_live_k(qi, BLOCK, BLOCK), nq - 1) + 1
        for qi in range(nq)
    )
    per_head = (
        2 * seq * DIM_HEAD  # q in, o out
        + 2 * live_tiles * BLOCK * DIM_HEAD  # k + v tiles
    ) * itemsize + seq * 4  # lse row, fp32
    return BATCH * HEADS * per_head


def decode_step_times(max_len, itemsize):
    """(dense_s, flash_s) roofline time of ONE cached decode step's
    attention reads at expected live length max_len/2 (bandwidth-bound:
    q is a single token)."""
    kv = 2 * BATCH * HEADS * max_len * DIM_HEAD * itemsize
    dense_s = kv / V5E_HBM_BPS
    live = max_len / 2
    tiles = -(-live // BLOCK)
    kv_flash = 2 * BATCH * HEADS * tiles * BLOCK * DIM_HEAD * itemsize
    flash_s = kv_flash / V5E_HBM_BPS + KERNEL_OVERHEAD_S
    return dense_s, flash_s


def sparse_sweep():
    """Tile-width sweep for the block-sparse flash-decode kernel.

    Pure host numpy over the REAL static layout (`_build_static_mask` +
    `mask_to_block_bitmap` — the exact reduction the serving policy ships),
    so the live/dead tile counts are the truth, not a model; only the time
    axis is a roofline. Per block width, averaged over every image decode
    position p (cache length text_len + p + 1):

      * tiles_read / tiles_skipped among causally in-range tiles — i.e.
        the policy's savings ON TOP of the PR 4 length skip, the same
        accounting as the fleet's kv_tiles_* counters;
      * roofline step time: live K/V tile bytes over HBM BW, plus the
        per-tile grid charge times in-range tiles (dead tiles still cost
        a grid step: the kernel skips their DMA and compute, not their
        index-map evaluation) and the per-kernel overhead.
    """
    import numpy as np

    from dalle_pytorch_tpu.models.transformer import _build_static_mask
    from dalle_pytorch_tpu.ops.masks import mask_to_block_bitmap

    itemsize = 2  # bf16 KV cache
    total = TEXT_SEQ + FMAP * FMAP
    max_len = total + 1
    text_len = TEXT_SEQ + 1
    image_seq = FMAP * FMAP
    mask = np.asarray(_build_static_mask("axial_row", total, FMAP, 0))
    if mask.shape[0] < max_len:
        pad = max_len - mask.shape[0]
        mask = np.pad(mask, ((0, pad), (0, pad)), constant_values=True)
    mask = mask[:max_len, :max_len]

    lens = text_len + np.arange(image_seq) + 1  # cache length at step p
    rows_out = []
    for blk in SPARSE_BLOCKS:
        nb = -(-max_len // blk)
        bitmap = mask_to_block_bitmap(
            mask, blk, n_blocks=nb, always_live=text_len
        )[text_len:][:image_seq]
        llb = (lens - 1) // blk
        in_range = np.arange(nb)[None, :] <= llb[:, None]
        live = bitmap & in_range
        read = live.sum(axis=1).astype(float)
        in_r = in_range.sum(axis=1).astype(float)
        live_frac = float(read.sum() / in_r.sum())
        # one decode step's K/V traffic (all heads; q is a single token)
        kv_read = 2 * BATCH * HEADS * read.mean() * blk * DIM_HEAD * itemsize
        kv_len = 2 * BATCH * HEADS * in_r.mean() * blk * DIM_HEAD * itemsize
        step_s = (
            kv_read / V5E_HBM_BPS
            + HEADS * in_r.mean() * TILE_STEP_OVERHEAD_S
            + KERNEL_OVERHEAD_S
        )
        len_skip_s = (
            kv_len / V5E_HBM_BPS
            + HEADS * in_r.mean() * TILE_STEP_OVERHEAD_S
            + KERNEL_OVERHEAD_S
        )
        rows_out.append(
            {
                "probe": "sparse_block_sweep",
                "pattern": "axial_row",
                "block": blk,
                "n_blocks": nb,
                "live_tile_frac": round(live_frac, 4),
                "tiles_read_mean": round(float(read.mean()), 2),
                "tiles_skipped_mean": round(float((in_r - read).mean()), 2),
                "kv_bytes_read_mean": int(kv_read),
                "kv_bytes_saved_mean": int(kv_len - kv_read),
                "decode_step_us": round(step_s * 1e6, 2),
                "decode_lengthskip_us": round(len_skip_s * 1e6, 2),
            }
        )
        print(json.dumps(rows_out[-1]), flush=True)
    best_saved = max(r["kv_bytes_saved_mean"] for r in rows_out)
    by_block = {r["block"]: r for r in rows_out}
    print(
        json.dumps(
            {
                "probe": "sparse_block_recommendation",
                "decode_sparse_block": 128,
                "savings_captured_vs_best": round(
                    by_block[128]["kv_bytes_saved_mean"] / best_saved, 4
                ),
                "basis": "128 matches flash_decode_attention's default "
                "block_k (all-ones bitmap keeps bit-identity with the "
                "dense-causal flash path) and sits at the roofline knee: "
                "thinner tiles save more bytes but the per-tile grid "
                "charge eats the win (32-wide models SLOWER than "
                "length-skip-only at 128); wider tiles smear the "
                "pattern and forfeit most of the skip",
            }
        ),
        flush=True,
    )


def main():
    import jax
    import jax.numpy as jnp

    dtype = jnp.bfloat16
    itemsize = 2
    prefill_cross = None
    decode_cross = None
    for seq in SEQS:
        flops, dense_bytes = measured_dense(seq, dtype)
        t_dense = max(flops / V5E_PEAK_FLOPS, dense_bytes / V5E_HBM_BPS)
        dense_bw_bound = dense_bytes / V5E_HBM_BPS > flops / V5E_PEAK_FLOPS
        fbytes = flash_tile_bytes(seq, itemsize)
        # causal block cut halves the matmul work; epilogue FLOPs are noise
        t_flash = max(
            (flops / 2) / V5E_PEAK_FLOPS, fbytes / V5E_HBM_BPS
        ) + KERNEL_OVERHEAD_S
        d_dense, d_flash = decode_step_times(seq, itemsize)
        row = {
            "probe": "flash_crossover",
            "seq": seq,
            "dense_flops": flops,
            "dense_bytes": dense_bytes,
            "flash_bytes": fbytes,
            "dense_roofline_us": round(t_dense * 1e6, 1),
            "flash_roofline_us": round(t_flash * 1e6, 1),
            "dense_bw_bound": dense_bw_bound,
            "decode_dense_us": round(d_dense * 1e6, 2),
            "decode_flash_us": round(d_flash * 1e6, 2),
            "device": jax.devices()[0].platform,
        }
        print(json.dumps(row), flush=True)
        if prefill_cross is None and dense_bw_bound and t_flash < t_dense:
            prefill_cross = seq
        if decode_cross is None and d_flash < d_dense:
            decode_cross = seq
    # Op-level counting cannot resolve the LOW end of the prefill bracket:
    # below ~1k tokens XLA's epilogue fusion may keep (part of) the score
    # chain out of HBM, so "dense is BW-bound from `prefill_cross` on" is a
    # lower bound, not a crossover. The r4 hardware anchor (flash == dense
    # wall at 1280 even under dispatch overhead; the r3 HBM analysis says
    # flash wins there outright) caps the bracket from above. Recommend the
    # largest bench-grid point that still auto-selects flash for the
    # flagship 1280: every estimate agrees there, and the unreliable
    # sub-1k region stays dense until the on-chip A/B rules on it.
    recommended_prefill = 1024
    print(
        json.dumps(
            {
                "probe": "flash_crossover_recommendation",
                "prefill_bracket_low_seq": prefill_cross,
                "prefill_hardware_anchor_seq": 1280,
                "auto_flash_min_seq": recommended_prefill,
                "auto_flash_decode_min_len": decode_cross,
                "basis": "v5e roofline over measured dense cost_analysis; "
                "on-chip wall-clock A/B remains the final decider",
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    if "--sparse" in sys.argv[1:]:
        sparse_sweep()
    else:
        main()
