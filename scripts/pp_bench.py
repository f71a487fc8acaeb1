#!/usr/bin/env python
"""Pipeline-parallel trunk cost check: pipelined vs plain scan trunk.

pp has engine-level parity tests; this is its cost check. This bench times a DALLE training step (value_and_grad through the
full model) with the trunk run two ways:

  plain : the scan executor's lax.scan-over-depth trunk
  pp    : make_pipeline_trunk over a PP_N-stage 'pp' mesh with PP_MICRO
          microbatches (parallel/gpipe.py GPipe schedule)

On ONE chip (PP_N=1) the difference is the pure cost of the schedule
machinery (shard_map + microbatch scan + ppermute plumbing) — the number
that says whether pp=1 degenerates gracefully. On the 8-device CPU mesh
(PP_N=4/8) it measures schedule overhead including bubble
(PP_MICRO/(PP_MICRO+PP_N-1) ideal efficiency).

Env: PP_N (stages, default 1), PP_MICRO (default 4), PP_BATCH (8),
PP_FMAP (16), PP_DIM (512), PP_DEPTH (8), PP_RUNS (3), PP_TEXT (64).
Defaults are sized to run everywhere; the TPU matrix row pins the
flagship geometry. Prints ONE JSON line.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    import jax

    from dalle_pytorch_tpu.utils.compile_cache import enable_xla_cache

    enable_xla_cache()  # before the first compile
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.models.transformer import (
        Transformer,
        make_pipeline_trunk,
    )
    from dalle_pytorch_tpu.parallel.gpipe import make_pp_mesh

    pp_n = int(os.environ.get("PP_N", "1"))
    n_micro = int(os.environ.get("PP_MICRO", "4"))
    batch = int(os.environ.get("PP_BATCH", "8"))
    fmap = int(os.environ.get("PP_FMAP", "16"))
    dim = int(os.environ.get("PP_DIM", "512"))
    depth = int(os.environ.get("PP_DEPTH", "8"))
    runs = int(os.environ.get("PP_RUNS", "3"))
    text_seq = int(os.environ.get("PP_TEXT", "64"))

    model = DALLE(
        dim=dim, depth=depth, heads=max(dim // 64, 1), dim_head=64,
        num_image_tokens=8192, image_fmap_size=fmap,
        num_text_tokens=10000, text_seq_len=text_seq,
        shift_tokens=True, rotary_emb=True, executor="scan",
        dtype=jnp.bfloat16,
    )
    text = jnp.ones((batch, text_seq), jnp.int32)
    toks = jnp.zeros((batch, fmap * fmap), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), text, toks)["params"]

    mesh = make_pp_mesh(pp_n)
    pipelined = make_pipeline_trunk(
        Transformer(**model.transformer_kwargs()), mesh, n_micro=n_micro
    )

    def loss_plain(p):
        loss, _ = model.apply({"params": p}, text, toks, return_loss=True)
        return loss

    def loss_pp(p):
        trunk = lambda h: pipelined(p["transformer"], h)
        loss, _ = model.apply(
            {"params": p}, text, toks, return_loss=True, trunk_fn=trunk
        )
        return loss

    def timed(fn):
        g = jax.jit(jax.value_and_grad(fn))
        l, grads = g(params)  # compile
        float(l)
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            l, grads = g(params)
            float(l)  # value read-back: the step has finished
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2], float(l)

    t_plain, l_plain = timed(loss_plain)
    t_pp, l_pp = timed(loss_pp)

    out = {
        "metric": "pp_trunk_step_overhead",
        "value": round(t_pp / t_plain, 3),
        "unit": "x_plain",
        "ok": abs(l_pp - l_plain) < 1e-2 * max(1.0, abs(l_plain)),
        "vs_baseline": None,  # reference has no pipeline parallelism
        "plain_s": round(t_plain, 4),
        "pp_s": round(t_pp, 4),
        "pp": pp_n,
        "n_micro": n_micro,
        "ideal_bubble_eff": round(n_micro / (n_micro + pp_n - 1), 3),
        "loss_delta": round(abs(l_pp - l_plain), 6),
        "platform": jax.devices()[0].platform,
        "device": jax.devices()[0].device_kind,
        "config": f"dim{dim}-depth{depth}-fmap{fmap}-bs{batch}-bf16",
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
