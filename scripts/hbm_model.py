"""Predicted-HBM ladder: XLA cost analysis of the bench configurations.

The working hypothesis (TPU_RESULTS.jsonl's round-4 rows; to be tested
against ledger rows) is that the flagship step is HBM-bandwidth-bound, so the *bytes accessed* of the compiled program is
the best hardware-free predictor of which configuration wins. This script
AOT-compiles the real train step (CPU backend — same HLO structure as
TPU for everything except the Pallas flash kernel) at FULL flagship
depth, reads `compiled.cost_analysis()`, and prints one JSON line per
config with FLOPs, bytes, arithmetic intensity, and the
bandwidth-implied MFU ceiling on a v5e (197 TFLOP/s peak, ~819 GB/s HBM).

IMPORTANT measurement caveat: XLA cost analysis counts `lax.scan` /
while-loop bodies ONCE, not x trip-count, so any config containing a
loop (scan executor, vocab-chunked fused CE, grad accumulation)
undercompares. Only loop-free configurations are compiled here; the
flash and fused-CE levers are applied as clearly-labeled analytic
adjustments with stated assumptions:
  * flash: per-layer [B, H, N, N] bf16 score traffic (4 passes/step with
    selective remat: fwd write+read, bwd recompute write+read) replaced
    by linear q/k/v/o+lse traffic;
  * fused CE: two fp32 [B, N, V] logits materializations (fwd + bwd
    softmax-minus-onehot) replaced by chunked transients that never
    leave VMEM.

Usage: python scripts/hbm_model.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# shared with the live serving-side accounting (obs/vitals.py:
# ProgramCostTable) so offline and live rooflines cannot drift
from dalle_pytorch_tpu.obs.vitals import (  # noqa: E402
    V5E_HBM_BPS, V5E_PEAK_FLOPS, extract_cost,
)

DIM, DEPTH, HEADS, DIM_HEAD = 1024, 12, 16, 64
TEXT_SEQ, FMAP, BATCH = 256, 32, 16
SEQ = TEXT_SEQ + FMAP * FMAP
VOCAB = 10000 + TEXT_SEQ + 8192  # model.total_tokens at this geometry


def build_step(mode, remat_policy):
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.training import (
        TrainState, make_optimizer, make_dalle_train_step,
    )

    model = DALLE(
        dim=DIM, depth=DEPTH, heads=HEADS, dim_head=DIM_HEAD,
        num_image_tokens=8192, image_fmap_size=FMAP,
        num_text_tokens=10000, text_seq_len=TEXT_SEQ,
        shift_tokens=True, rotary_emb=True, attn_impl="dense",
        reversible=True, reversible_impl="remat", remat_policy=remat_policy,
        fused_ce=False, executor="unrolled", dtype=jnp.bfloat16,
    )
    text = jnp.ones((BATCH, TEXT_SEQ), jnp.int32)
    tokens = jnp.zeros((BATCH, FMAP * FMAP), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), text, tokens)[
        "params"
    ]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    state = TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_optimizer(3e-4, clip_grad_norm=0.5),
    )
    step = make_dalle_train_step(model, mode=mode)
    return step, state, {"text": text, "image_tokens": tokens}


def emit(row):
    print(json.dumps(row), flush=True)
    return row


def ceiling(flops, nbytes):
    ai = flops / max(nbytes, 1.0)
    return ai, min(1.0, ai * V5E_HBM_BPS / V5E_PEAK_FLOPS)


def analyze(name, mode, remat_policy):
    import jax

    t0 = time.time()
    step, state, batch = build_step(mode, remat_policy)
    compiled = jax.jit(step, donate_argnums=0).lower(
        state, batch, jax.random.PRNGKey(1)
    ).compile()
    cost = extract_cost(compiled)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    ai, mfu = ceiling(flops, nbytes)
    return emit({
        "config": name,
        "mode": mode,
        "flops_per_step_T": round(flops / 1e12, 2),
        "gbytes_per_step": round(nbytes / 1e9, 1),
        "flop_per_byte": round(ai, 1),
        "bw_implied_mfu_ceiling": round(mfu, 3),
        "compile_s": round(time.time() - t0, 1),
        "measured": "xla_cost_analysis",
    })


def adjust(row, name, delta_bytes, note):
    """Analytic lever on top of a compiled row: bytes shift, FLOPs kept."""
    flops = row["flops_per_step_T"] * 1e12
    nbytes = row["gbytes_per_step"] * 1e9 + delta_bytes
    ai, mfu = ceiling(flops, nbytes)
    return emit({
        "config": name,
        "mode": row["mode"],
        "flops_per_step_T": row["flops_per_step_T"],
        "gbytes_per_step": round(nbytes / 1e9, 1),
        "flop_per_byte": round(ai, 1),
        "bw_implied_mfu_ceiling": round(mfu, 3),
        "measured": "analytic_on_" + row["config"],
        "note": note,
    })


def measure_attention_chain():
    """Per-layer op-level bytes of the dense score chain (fwd+bwd), same
    metric as the full-step rows — the part flash keeps in VMEM."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.ops.attention_core import dense_attention
    import numpy as np

    q = jnp.zeros((BATCH, HEADS, SEQ, DIM_HEAD), jnp.bfloat16)
    mask = jnp.asarray(np.tril(np.ones((SEQ, SEQ), bool)))[None, None]

    def f(q, k, v):
        return dense_attention(q, k, v, mask=mask).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q).compile()
    total = float(extract_cost(compiled).get("bytes accessed", 0.0))
    # flash's true per-layer traffic for the same math: q/k/v in, o out
    # (fwd), q/k/v/o/do in, dq/dk/dv out (bwd) + lse/delta rows
    linear = 12 * BATCH * HEADS * SEQ * DIM_HEAD * 2 + 3 * BATCH * HEADS * SEQ * 4
    emit({
        "component": "dense_score_chain_per_layer",
        "gbytes_fwd_bwd": round(total / 1e9, 1),
        "flash_linear_gbytes": round(linear / 1e9, 2),
        "measured": "xla_cost_analysis",
    })
    return total, linear


def decode_step_floor(batch=4):
    """Bandwidth floor for the generate north star: cost-analyze ONE
    cached decode step (loop-free) and multiply by the image length."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE, init_decode_cache

    model = DALLE(
        dim=DIM, depth=DEPTH, heads=HEADS, dim_head=DIM_HEAD,
        num_image_tokens=8192, image_fmap_size=FMAP,
        num_text_tokens=10000, text_seq_len=TEXT_SEQ,
        shift_tokens=True, rotary_emb=True, dtype=jnp.bfloat16,
    )
    text = jnp.ones((batch, TEXT_SEQ), jnp.int32)
    tokens = jnp.zeros((batch, FMAP * FMAP), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), text, tokens)[
        "params"
    ]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    cache = init_decode_cache(model, batch)

    def step(params, tok, pos, cache):
        return model.apply(
            {"params": params}, tok, pos, cache,
            method=DALLE.decode_image_step,
        )

    compiled = jax.jit(step).lower(
        params, jnp.zeros((batch,), jnp.int32), jnp.zeros((), jnp.int32),
        cache,
    ).compile()
    nbytes = float(extract_cost(compiled).get("bytes accessed", 0.0))
    n_img = FMAP * FMAP
    floor_s = n_img * nbytes / V5E_HBM_BPS
    emit({
        "component": "cached_decode_step",
        "batch": batch,
        "gbytes_per_step": round(nbytes / 1e9, 2),
        "p50_bw_floor_s": round(floor_s, 2),
        "note": f"x{n_img} sequential steps; op-level bytes (overcounts "
                "fused traffic), params+cache re-read every step",
        "measured": "xla_cost_analysis",
    })


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    decode_step_floor()

    # loop-free compiled rows (forward_forward runs two inline applies)
    analyze("dense_remat_full", "forward_only", None)
    pol = analyze("dense_policy", "forward_only",
                  "dots_with_no_batch_dims_saveable")
    ff = analyze("ff_dense_policy", "forward_forward",
                 "dots_with_no_batch_dims_saveable")

    # measured flash lever: the dense score chain's op-level bytes per
    # layer (same metric as the rows above) collapse to linear traffic
    attn_total, attn_linear = measure_attention_chain()
    flash_delta = -DEPTH * (attn_total - attn_linear)
    # fused-CE lever: fwd + bwd fp32 [B, N, V] logits materializations
    # plus the softmax chain over them (~2 more passes), all -> chunked
    logits_fp32 = BATCH * SEQ * VOCAB * 4
    fused_delta = -4 * logits_fp32

    pol_flash = adjust(
        pol, "dense_policy+flash", flash_delta,
        "measured score-chain bytes -> flash linear traffic, x12 layers",
    )
    adjust(
        pol_flash, "policy+flash+fusedce", fused_delta,
        "also drop ~4 fp32 [B,N,V] logits passes (chunked CE)",
    )
    adjust(
        ff, "ff_policy+flash+2xfusedce",
        2 * flash_delta + 2 * fused_delta,
        "both objectives fused (round-4 inverse fused CE) + flash",
    )


if __name__ == "__main__":
    main()
