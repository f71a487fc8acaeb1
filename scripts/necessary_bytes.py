#!/usr/bin/env python
"""Analytic NECESSARY-HBM-traffic model for the flagship train step.

The gap from the one recorded on-chip MFU (7.7%, TPU_RESULTS.jsonl) to
the >=45% target needs a roofline. The hardware half is
scripts/perf_probe.py on the chip; this script supplies the model half: a lower-bound estimate of the HBM bytes a WELL-FUSED XLA
program must move per step, as opposed to `cost_analysis()`'s op-level
operand counting (which charges every elementwise op its full operands
— 886 GB/step at the same levers (flash+policy+fused CE); 1.34 TB for
the dense full-remat baseline — and therefore wildly overcounts what
the fused program actually streams).

Counting rules (bf16 activations/params, fp32 master adds x2 where
noted):
  * every tensor the autodiff must SAVE (remat policy
    dots_with_no_batch_dims_saveable: matmul outputs) is written once in
    the forward and read once in the backward;
  * the residual stream is read+written once per block per direction
    (fused with the adjacent matmuls beyond that);
  * flash attention streams Q/K/V/O once per pass plus the saved lse —
    score tensors never touch HBM (that is the point of flash; the
    causal DMA-skip removes the dead-tile re-reads);
  * fused CE streams the hidden states and the head weight once per
    chunk pass (logits are never materialized);
  * params: read fwd + read bwd + grad write + Adam moments read/write
    (fp32) + fp32 master read/write.

The result is a LOWER bound (perfect fusion, no spills); the true
program sits between this and the op-level count. Prints one JSON line
and a small table.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

# flagship geometry + v5e roofline constants: one source of truth with
# the op-level model (hbm_model re-exports the roofline anchors from
# dalle_pytorch_tpu.obs.vitals — the same numbers the live serving MFU
# gauges use; importing it pulls jax, but backends initialize lazily so
# this stays side-effect-free)
from hbm_model import (  # noqa: E402
    BATCH, DEPTH, DIM, DIM_HEAD, HEADS, SEQ, V5E_HBM_BPS, V5E_PEAK_FLOPS,
    VOCAB,
)

B, S, D, L = BATCH, SEQ, DIM, DEPTH
DH = DIM_HEAD
V = VOCAB
FF_MULT = 4
BF16, F32 = 2, 4

GB = 1e9


def gb(x):
    return x / GB


def main():
    bsd = B * S * D * BF16

    # ---- per-layer saved activations (dots policy: matmul outputs) ----
    qkv_out = 3 * bsd            # to_qkv output
    attn_o = bsd                 # flash O (saved for backward)
    lse = B * HEADS * S * 1 * F32
    attn_proj = bsd              # out-projection output
    ff_in = 2 * FF_MULT * bsd    # GEGLU up-projection (2 branches)
    ff_out = bsd                 # down-projection output
    saved_per_layer = qkv_out + attn_o + lse + attn_proj + ff_in + ff_out
    # each saved tensor: 1 write (fwd) + 1 read (bwd)
    saved_traffic = 2 * saved_per_layer * L

    # ---- flash attention streaming (fwd + dq + dkv passes) ----
    # per pass Q, K, V each read once; O written (fwd) / dO read + dq/dkv
    # written (bwd). 3 passes stream ~4 x [B,H,S,DH] tensors each.
    bhsd = B * HEADS * S * DH * BF16
    flash_traffic = L * (4 * bhsd + 2 * (4 * bhsd))

    # ---- residual stream (read + write per block per direction) ----
    resid_traffic = L * 2 * (2 * bsd) * 2  # 2 blocks/layer, fwd+bwd

    # ---- embeddings + logits head (fused CE, chunked) ----
    emb_traffic = 2 * bsd  # token+pos gather out fwd, grad scatter bwd
    head_w = D * V * BF16
    # fwd chunk pass + recompute in bwd + dW grad write + dh read/write
    ce_traffic = 2 * (bsd + head_w) + head_w * 2 + 2 * bsd

    # ---- params + optimizer ----
    n_params = (
        L * (3 * D * D + D * D + 2 * FF_MULT * D * D + FF_MULT * D * D)
        + V * D + D * V
    )
    p_bf16 = n_params * BF16
    p_f32 = n_params * F32
    #   read fwd + read bwd (recompute streams) + grad write (fp32)
    # + adam m,v read+write (fp32) + master read+write (fp32)
    param_traffic = 2 * p_bf16 + p_f32 + 4 * p_f32 + 2 * p_f32

    total = (
        saved_traffic + flash_traffic + resid_traffic
        + emb_traffic + ce_traffic + param_traffic
    )

    # device-time model (33.1e12 = the policy-remat step FLOPs measured
    # by hbm_model.py's cost-analysis table, round 4)
    flops = 33.1e12
    t_mxu = flops / V5E_PEAK_FLOPS
    t_hbm = total / V5E_HBM_BPS
    bound = max(t_mxu, t_hbm)
    mfu_ceiling = t_mxu / bound

    rows = [
        ("saved activations (dots policy) x12", saved_traffic),
        ("flash Q/K/V/O streams x12 (3 passes)", flash_traffic),
        ("residual stream x12", resid_traffic),
        ("embeddings", emb_traffic),
        ("fused-CE head (chunked)", ce_traffic),
        ("params + Adam (fp32 moments/master)", param_traffic),
    ]
    print(f"{'component':44s} {'GB/step':>8s}")
    for name, b in rows:
        print(f"{name:44s} {gb(b):8.1f}")
    print(f"{'TOTAL necessary (lower bound)':44s} {gb(total):8.1f}")
    print()
    print(
        f"t_mxu {t_mxu*1e3:.0f} ms vs t_hbm {t_hbm*1e3:.0f} ms -> "
        f"{'COMPUTE' if t_mxu >= t_hbm else 'BANDWIDTH'}-bound; "
        f"MFU ceiling {mfu_ceiling*100:.0f}%"
    )
    print(json.dumps({
        "metric": "necessary_bytes_model",
        "value": round(gb(total), 1),
        "unit": "GB/step",
        "vs_baseline": None,
        "t_mxu_ms": round(t_mxu * 1e3, 1),
        "t_hbm_ms": round(t_hbm * 1e3, 1),
        "mfu_ceiling": round(mfu_ceiling, 3),
        "oplevel_gb": 886,  # hbm_model.py op-level count for contrast
    }))


if __name__ == "__main__":
    main()
