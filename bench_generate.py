"""Benchmark: p50 latency of KV-cached image generation.

The BASELINE.json inference north star: `generate.py` producing 256x256
samples (OpenAI-dVAE geometry: 1024 image tokens autoregressively decoded
through the scan-based KV cache). Prints ONE JSON line with the p50
end-to-end latency for one batch of samples (transformer decode only; VAE
pixel decode is a single extra forward and is reported separately).

Env overrides: GEN_BATCH (default 4), GEN_FMAP (32), GEN_RUNS (5),
GEN_COND_SCALE (1.0), GEN_PHASES=1 adds a per-phase breakdown (prefill
program vs the 1024-step decode scan vs dVAE pixel decode) so the p50 can
be attacked where the time actually is.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "generate_p50_latency_batch"
UNIT = "s"


def main():
    import jax

    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE, generate_images_cached

    batch = int(os.environ.get("GEN_BATCH", "4"))
    fmap = int(os.environ.get("GEN_FMAP", "32"))
    runs = int(os.environ.get("GEN_RUNS", "5"))
    cond_scale = float(os.environ.get("GEN_COND_SCALE", "1.0"))
    # "scan" decodes natively on the depth-stacked layout: one compiled
    # layer body, the smallest decode program
    executor = os.environ.get("GEN_EXECUTOR", "unrolled")
    text_seq = 256

    model = DALLE(
        dim=1024, depth=12, heads=16, dim_head=64,
        num_image_tokens=8192, image_fmap_size=fmap,
        num_text_tokens=10000, text_seq_len=text_seq,
        shift_tokens=True, rotary_emb=True, executor=executor,
        dtype=jnp.bfloat16,
    )
    text = jnp.ones((batch, text_seq), jnp.int32)
    tokens = jnp.zeros((batch, fmap * fmap), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)

    def north_star_dvae():
        # the framework's 256px/8192-token DiscreteVAE geometry, shared by
        # the GEN_FUSED sampler and the GEN_PHASES vae-decode probe so the
        # two env-gated paths can never benchmark different models
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        v = DiscreteVAE(
            image_size=8 * fmap, num_layers=3, num_tokens=8192,
            codebook_dim=512, hidden_dim=64,
        )
        vp = jax.jit(v.init)(
            jax.random.PRNGKey(3), jnp.zeros((1, 8 * fmap, 8 * fmap, 3))
        )["params"]
        return v, vp

    fused_vae = None
    if os.environ.get("GEN_FUSED"):
        # end-to-end-pixels p50: dVAE pixel decode fused into the sampler
        # program (tokens AND pixels from one dispatch — the generate.py
        # production path for DiscreteVAE checkpoints)
        fused_vae, fused_vparams = north_star_dvae()

    def sample(rng):
        if fused_vae is not None:
            _, px = generate_images_cached(
                model, params, rng, text, cond_scale=cond_scale,
                vae=fused_vae, vae_params=fused_vparams,
            )
            return px
        return generate_images_cached(
            model, params, rng, text, cond_scale=cond_scale
        )

    # warmup / compile; the int() read-back waits for the decode to finish
    out = sample(jax.random.PRNGKey(1))
    int(jnp.asarray(out).ravel()[0])

    times = []
    for i in range(runs):
        t0 = time.perf_counter()
        out = sample(jax.random.PRNGKey(2 + i))
        int(jnp.asarray(out).ravel()[0])
        times.append(time.perf_counter() - t0)
    times.sort()
    p50 = times[len(times) // 2]

    phases = None
    if os.environ.get("GEN_PHASES") and fused_vae is not None:
        raise SystemExit(
            "GEN_PHASES with GEN_FUSED would fold the fused vae decode "
            "into decode_scan_s/per_token_ms (double-counted vs the "
            "separate vae_decode_s row) — run the phase breakdown on the "
            "unfused sampler"
        )
    if os.environ.get("GEN_PHASES"):
        # Phase split: time the prefill-only program separately; the decode
        # scan is (total - prefill) — no third compile needed. Each phase
        # is its own dispatch; the SPLIT (which phase dominates) is what
        # this measures. dVAE pixel decode (the one extra forward
        # `generate.py` runs after sampling) is timed on the framework's
        # 256px/8192-token DiscreteVAE north-star geometry.
        from dalle_pytorch_tpu.models.dalle import DALLE as _D, init_decode_cache

        @jax.jit
        def prefill(variables, t):
            return model.apply(
                variables, t, init_decode_cache(model, t.shape[0]),
                method=_D.decode_prefill,
            )

        # mirror the e2e path's classifier-free-guidance batch doubling
        # (generate_images_cached stacks a null-text stream when
        # cond_scale != 1), else the split under-measures prefill
        ptext = (
            jnp.concatenate([text, jnp.zeros_like(text)], axis=0)
            if cond_scale != 1.0 else text
        )
        row, _cache = prefill(params, ptext)
        float(jnp.asarray(row).ravel()[0].astype(jnp.float32))  # compile
        pf_times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            row, _cache = prefill(params, ptext)
            float(jnp.asarray(row).ravel()[0].astype(jnp.float32))
            pf_times.append(time.perf_counter() - t0)
        pf_times.sort()
        pf50 = pf_times[len(pf_times) // 2]

        vae, vparams = north_star_dvae()
        toks0 = jnp.zeros((batch, fmap * fmap), jnp.int32)
        vdec = jax.jit(
            lambda p, t: vae.apply({"params": p}, t, method=type(vae).decode)
        )
        float(jnp.asarray(vdec(vparams, toks0)).ravel()[0])  # compile
        vd_times = []
        for _ in range(runs):
            t0 = time.perf_counter()
            float(jnp.asarray(vdec(vparams, toks0)).ravel()[0])
            vd_times.append(time.perf_counter() - t0)
        vd_times.sort()
        vd50 = vd_times[len(vd_times) // 2]

        phases = {
            "prefill_s": round(pf50, 3),
            "decode_scan_s": round(p50 - pf50, 3),
            "per_token_ms": round((p50 - pf50) / (fmap * fmap) * 1e3, 3),
            "vae_decode_s": round(vd50, 3),
        }

    out = {
        "metric": METRIC,
        "value": round(p50, 3),
        "unit": UNIT,
        "ok": True,
        "vs_baseline": None,  # reference publishes no latency numbers
        "batch": batch,
        "image_tokens": fmap * fmap,
        "tokens_per_sec": round(batch * fmap * fmap / p50, 1),
        "platform": jax.devices()[0].platform,
        "device": jax.devices()[0].device_kind,
        "config": f"dim1024-depth12-fmap{fmap}-bs{batch}"
                  f"-cond{cond_scale}-bf16-cached"
                  f"{'-scan' if executor == 'scan' else ''}"
                  f"{'-fusedpx' if fused_vae is not None else ''}",
    }
    if phases is not None:
        out["phases"] = phases
    print(json.dumps(out))


if __name__ == "__main__":
    if "--child" in sys.argv:
        main()
    else:
        from bench_common import run_guarded

        run_guarded(
            METRIC,
            UNIT,
            __file__,
            child_timeout=1300.0,
        )
