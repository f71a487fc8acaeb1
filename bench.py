"""Benchmark: DALLE training throughput (image-tokens/sec/chip) + MFU.

Runs the flagship train step (dim 1024 / depth 12, OpenAI-dVAE geometry:
256 text + 1024 image tokens, bf16 compute) on the available accelerator
and prints ONE JSON line. The reference publishes no numbers — its only
runtime metric is `sample_per_sec`
(`/root/reference/train_dalle.py:578-581`) — so `vs_baseline` is reported
against the ≥45%-MFU design target from BASELINE.json (value 1.0 ==
exactly hitting the target scaled to this chip count). A run that finds
no accelerator fails (non-zero exit); nothing falls back to the CPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "dalle_train_image_tokens_per_sec_per_chip"
UNIT = "img-tok/s/chip"


# FLOPs/peak accounting lives in dalle_pytorch_tpu.utils.flops; imported
# lazily so the guard parent process stays light (no jax/flax import
# before forking the child).


def peak_flops_per_chip() -> float:
    import jax

    from dalle_pytorch_tpu.utils.flops import peak_flops_per_chip as _peak

    return _peak(jax.devices()[0].device_kind)


def main():
    import jax

    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.training import (
        TrainState,
        make_dalle_train_step,
        make_multi_step,
        make_optimizer,
    )
    from dalle_pytorch_tpu.utils.flops import transformer_train_flops

    # BASELINE.json ladder config: DALLE dim=1024 depth=12 with OpenAI-dVAE
    # geometry (f/8: 32x32 = 1024 image tokens, seq 1280). Env overrides for
    # A/B runs: BENCH_BATCH, BENCH_FMAP, BENCH_ATTN (dense|flash|auto),
    # BENCH_REMAT (per-layer rematerialization; without it the bf16
    # [B,1280,4096] GEGLU activations of all 12 layers stay live through the
    # backward and batch 16 blows 16G HBM — the round-2 failure mode),
    # BENCH_ACCUM (gradient accumulation: global batch stays BENCH_BATCH,
    # split into BENCH_ACCUM scanned microbatches).
    dim, depth, heads, dim_head = 1024, 12, 16, 64
    text_seq = 256
    fmap = int(os.environ.get("BENCH_FMAP", "32"))
    batch = int(os.environ.get("BENCH_BATCH", "16"))
    accum = int(os.environ.get("BENCH_ACCUM", "1"))
    remat = os.environ.get("BENCH_REMAT", "1") == "1"
    # jax.checkpoint policy for the remat executor; "none" = full recompute
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "none")
    remat_policy = None if remat_policy == "none" else remat_policy
    attn_impl = os.environ.get("BENCH_ATTN", "auto")
    # comma list, e.g. "full,axial_row,axial_col,conv_like" — cycled over
    # layers like the reference's attn_types; masked types run dense with
    # per-layer pattern masks (scan executor scans them over depth)
    attn_types = os.environ.get("BENCH_ATTN_TYPES")
    attn_types = tuple(attn_types.split(",")) if attn_types else None
    fused_ce = os.environ.get("BENCH_FUSED_CE", "0") == "1"
    # "scan" compiles ONE layer body instead of `depth` copies — ~12x
    # smaller program
    executor = os.environ.get("BENCH_EXECUTOR", "unrolled")
    # BENCH_SCAN_STEPS=S runs S optimizer steps per dispatch via
    # make_multi_step (host-loop elimination): scanning amortizes one
    # dispatch over S real steps.
    scan_steps = int(os.environ.get("BENCH_SCAN_STEPS", "1"))
    image_seq = fmap * fmap
    seq = text_seq + image_seq

    model = DALLE(
        dim=dim, depth=depth, heads=heads, dim_head=dim_head,
        num_image_tokens=8192, image_fmap_size=fmap,
        num_text_tokens=10000, text_seq_len=text_seq,
        shift_tokens=True, rotary_emb=True, attn_impl=attn_impl,
        attn_types=attn_types,
        reversible=remat, reversible_impl="remat", remat_policy=remat_policy,
        fused_ce=fused_ce, executor=executor,
        dtype=jnp.bfloat16,
    )
    text = jnp.ones((batch, text_seq), jnp.int32)
    tokens = jnp.zeros((batch, image_seq), jnp.int32)
    # jit the init: eager init dispatches each op separately
    params = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_optimizer(3e-4, clip_grad_norm=0.5),
    )
    step_fn = make_dalle_train_step(model, grad_accum=accum)
    if scan_steps > 1:
        step = jax.jit(make_multi_step(step_fn, scan_steps), donate_argnums=0)
    else:
        step = jax.jit(step_fn, donate_argnums=0)
    batch_dict = {"text": text, "image_tokens": tokens}
    if scan_steps > 1:
        # token ids only — the [S, B, seq] int32 window is ~a few MB
        batch_dict = jax.tree.map(
            lambda x: jnp.repeat(x[None], scan_steps, 0), batch_dict
        )
    rng = jax.random.PRNGKey(1)

    def call(state, b, r):
        if scan_steps > 1:
            return step(state, b, jax.random.split(r, scan_steps))
        return step(state, b, r)

    # warmup / compile (float() forces completion; see timing note below)
    state, metrics = call(state, batch_dict, rng)
    float(metrics["loss"])

    n_steps = int(os.environ.get("BENCH_STEPS", "20"))
    # keep the dispatch count whole; the metric divides by the true count
    n_dispatches = max(1, n_steps // scan_steps)
    n_steps = n_dispatches * scan_steps
    # BENCH_INPUT=host: feed every step through the real input machinery —
    # per-step host batch assembly (numpy tokenize-shaped work + device_put)
    # overlapped via the Prefetcher — and report the measured input-bound
    # fraction alongside throughput.
    input_mode = os.environ.get("BENCH_INPUT", "synthetic")
    prefetcher = None
    if input_mode == "host":
        import numpy as np

        from dalle_pytorch_tpu.data.prefetch import Prefetcher

        host_rng = np.random.RandomState(0)

        def host_batches():
            # batch GENERATION stays inside the pipeline so the measured
            # wait fraction includes real host-side assembly work, not just
            # the transfer; with multi-stepping one yielded item is a whole
            # [scan_steps, ...] window (one transfer per dispatch)
            for _ in range(n_dispatches):
                window = [
                    {
                        "text": host_rng.randint(1, 9000, (batch, text_seq)),
                        "image_tokens": host_rng.randint(
                            0, 8192, (batch, image_seq)
                        ),
                    }
                    for _ in range(scan_steps)
                ]
                yield window if scan_steps > 1 else window[0]

        def assemble(b):
            if scan_steps > 1:
                from dalle_pytorch_tpu.training import stack_batches

                b = stack_batches(b)
            return {
                "text": jax.device_put(b["text"].astype(np.int32)),
                "image_tokens": jax.device_put(b["image_tokens"].astype(np.int32)),
            }

        prefetcher = Prefetcher(host_batches(), transform=assemble, depth=2)

    t0 = time.perf_counter()
    done_steps = 0
    if prefetcher is not None:
        for dev_batch in prefetcher:
            rng, r = jax.random.split(rng)
            state, metrics = call(state, dev_batch, r)
            done_steps += scan_steps
        assert done_steps == n_steps, (done_steps, n_steps)
    else:
        for _ in range(n_dispatches):
            rng, r = jax.random.split(rng)
            state, metrics = call(state, batch_dict, r)
    # JAX returns before the device finishes: time up to completion
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    steps_per_sec = n_steps / dt
    img_tok_per_sec_chip = steps_per_sec * batch * image_seq / n_chips
    vocab = model.total_tokens  # logits width; keeps the FLOPs numerator in sync
    flops_per_step = transformer_train_flops(
        dim, depth, heads, dim_head, seq, vocab=vocab
    ) * batch
    mfu = flops_per_step * steps_per_sec / (peak_flops_per_chip() * n_chips)

    out = {
        "metric": METRIC,
        "value": round(img_tok_per_sec_chip, 1),
        "unit": UNIT,
        "ok": True,
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "samples_per_sec": round(steps_per_sec * batch, 2),
        "platform": jax.devices()[0].platform,
        "device": jax.devices()[0].device_kind,
        "n_chips": n_chips,
        "config": (
            f"dim{dim}-depth{depth}-seq{seq}-gbs{batch}-accum{accum}-{attn_impl}"
            f"{'-types=' + ','.join(attn_types) if attn_types else ''}"
            f"-remat{int(remat)}{'-' + remat_policy if remat_policy else ''}"
            f"{'-fusedce' if fused_ce else ''}"
            f"{'-scan' if executor == 'scan' else ''}"
            f"{'-steps' + str(scan_steps) if scan_steps > 1 else ''}-bf16"
        ),
    }
    if prefetcher is not None:
        out["input_mode"] = "host"
        out["input_wait_frac"] = round(prefetcher.wait_fraction, 4)
    print(json.dumps(out))


def _microbatch_of(env) -> "int | None":
    """Live microbatch implied by an env dict; None when invalid (accum
    must evenly divide the global batch for `_microbatch`'s reshape)."""
    try:
        b = int(env.get("BENCH_BATCH", "16"))
        a = int(env.get("BENCH_ACCUM", "1"))
    except ValueError:
        return None
    if a <= 0 or b <= 0 or b % a:
        return None
    return b // a


# Named configurations of the flagship step (env defaults; explicit env
# wins). BENCH_PROFILE picks ONE — a profile that fails is a failed bench,
# there is no falling through to a configuration that happens to run.
PROFILES = {
    "scan+flash+dots_policy+fused_ce+steps8": {
        "BENCH_EXECUTOR": "scan",
        "BENCH_ATTN": "flash",
        "BENCH_REMAT_POLICY": "dots_with_no_batch_dims_saveable",
        "BENCH_FUSED_CE": "1",
        "BENCH_SCAN_STEPS": "8",
        "BENCH_STEPS": "32",
    },
    "scan+flash+dots_policy+fused_ce": {
        "BENCH_EXECUTOR": "scan",
        "BENCH_ATTN": "flash",
        "BENCH_REMAT_POLICY": "dots_with_no_batch_dims_saveable",
        "BENCH_FUSED_CE": "1",
    },
    "flash+dots_policy+fused_ce": {
        "BENCH_ATTN": "flash",
        "BENCH_REMAT_POLICY": "dots_with_no_batch_dims_saveable",
        "BENCH_FUSED_CE": "1",
    },
    "dense+dots_policy+fused_ce": {
        "BENCH_ATTN": "dense",
        "BENCH_REMAT_POLICY": "dots_with_no_batch_dims_saveable",
        "BENCH_FUSED_CE": "1",
    },
    "baseline_dense_remat": {},
}
DEFAULT_PROFILE = "scan+flash+dots_policy+fused_ce+steps8"


if __name__ == "__main__":
    if "--child" in sys.argv:
        main()
    else:
        from bench_common import run_guarded

        profile = os.environ.get("BENCH_PROFILE", DEFAULT_PROFILE)
        if profile not in PROFILES:
            sys.exit(f"unknown BENCH_PROFILE {profile!r}; one of {sorted(PROFILES)}")
        run_guarded(
            METRIC,
            UNIT,
            __file__,
            child_timeout=1800.0,
            # halve-microbatch-on-OOM ladder: BENCH_BATCH is the global
            # batch (BENCH_ACCUM scan-splits it), so the metric stays
            # comparable at batch 16 while the live microbatch shrinks.
            oom_ladder=[
                {"BENCH_ACCUM": "2"},
                {"BENCH_ACCUM": "4"},
                {"BENCH_ACCUM": "8"},
            ],
            microbatch_of=_microbatch_of,
            profile=(profile, PROFILES[profile]),
        )
