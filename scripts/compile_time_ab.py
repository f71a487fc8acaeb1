"""Compile-time A/B: unrolled vs scan executor at flagship geometry.

The scan executor exists to shrink the compiled program (~depth× fewer
layer bodies in the HLO). This measures trace+lower and XLA-compile wall
time for the full flagship train step on the CPU backend (compile cost is
a property of program structure, not the executing backend) plus the HLO
text size as a proxy for program size.

Run: python scripts/compile_time_ab.py          (one JSON line per row)
Env: AB_BATCH (default 4), AB_DEPTH (12), AB_EXECUTORS (unrolled,scan)
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

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.training import (
        TrainState, make_optimizer, make_dalle_train_step,
    )

    batch = int(os.environ.get("AB_BATCH", "4"))
    depth = int(os.environ.get("AB_DEPTH", "12"))
    execs = os.environ.get("AB_EXECUTORS", "unrolled,scan").split(",")

    for executor in execs:
        model = DALLE(
            dim=1024, depth=depth, heads=16, dim_head=64,
            num_image_tokens=8192, image_fmap_size=32,
            num_text_tokens=10000, text_seq_len=256,
            shift_tokens=True, rotary_emb=True, attn_impl="dense",
            reversible=True, reversible_impl="remat",
            remat_policy="dots_with_no_batch_dims_saveable",
            fused_ce=True, executor=executor, dtype=jnp.bfloat16,
        )
        text = jnp.ones((batch, 256), jnp.int32)
        tokens = jnp.zeros((batch, 1024), jnp.int32)
        t0 = time.perf_counter()
        params = jax.eval_shape(
            lambda: jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)
        )["params"]
        params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
        init_s = time.perf_counter() - t0

        state = TrainState.create(
            apply_fn=model.apply, params=params,
            tx=make_optimizer(3e-4, clip_grad_norm=0.5),
        )
        step = jax.jit(make_dalle_train_step(model), donate_argnums=0)
        batch_dict = {"text": text, "image_tokens": tokens}
        rng = jax.random.PRNGKey(1)

        t0 = time.perf_counter()
        lowered = step.lower(state, batch_dict, rng)
        lower_s = time.perf_counter() - t0
        hlo_chars = len(lowered.as_text())
        t0 = time.perf_counter()
        lowered.compile()
        compile_s = time.perf_counter() - t0
        print(json.dumps({
            "probe": "compile_ab", "executor": executor, "depth": depth,
            "batch": batch,
            "trace_lower_s": round(lower_s, 1),
            "xla_compile_s": round(compile_s, 1),
            "hlo_mb": round(hlo_chars / 1e6, 1),
            "param_init_s": round(init_s, 1),
        }), flush=True)


if __name__ == "__main__":
    main()
