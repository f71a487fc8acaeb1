"""On-chip Pallas flash-attention validation: parity + dense-vs-flash A/B.

The test suite runs the Pallas kernels in interpret mode (and
tests/test_tpu_compile.py only compiles them). This script must run on
the real TPU; it

  1. checks the compiled kernel's numerics against the dense oracle at the
     flagship and long-context geometries (fwd AND grad),
  2. times dense vs flash (fwd+bwd) at seq 1280 / 2048 / 4096 with the
     loop-inside-jit pattern (one dispatch, K iterations, scalar readback),
  3. prints one JSON line per row.

Run: python scripts/pallas_onchip.py            (on the chip)
     JAX_PLATFORMS=cpu python scripts/...       (interpret smoke)
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

K = int(os.environ.get("PROBE_K", "8"))
SEQS = [int(s) for s in os.environ.get("PROBE_SEQS", "1280,2048,4096").split(",")]
BATCH = int(os.environ.get("PROBE_BATCH", "4"))
HEADS = int(os.environ.get("PROBE_HEADS", "16"))
DIM_HEAD = int(os.environ.get("PROBE_DIM_HEAD", "64"))


def main():
    import jax

    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dalle_pytorch_tpu.ops.attention_core import dense_attention
    from dalle_pytorch_tpu.ops.pallas_attention import (
        _use_interpret,
        flash_attention,
    )

    dev = jax.devices()[0].device_kind
    interpret = _use_interpret()
    print(
        json.dumps(
            {"probe": "env", "device": dev, "interpret_mode": interpret}
        ),
        flush=True,
    )

    def qkv(seq, key=0):
        ks = jax.random.split(jax.random.PRNGKey(key), 3)
        shape = (BATCH, HEADS, seq, DIM_HEAD)
        return tuple(
            jax.random.normal(k, shape, jnp.bfloat16) * 0.5 for k in ks
        )

    # ---- 1. compiled parity vs dense oracle (fwd + grad) ----
    for seq in SEQS[:2]:  # parity at the two smaller geometries
        q, k, v = qkv(seq)
        causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]

        out_f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
            q, k, v
        )
        out_d = jax.jit(lambda q, k, v: dense_attention(q, k, v, mask=causal))(
            q, k, v
        )
        err = float(
            jnp.max(jnp.abs(out_f.astype(jnp.float32) - out_d.astype(jnp.float32)))
        )

        def loss_f(q):
            return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

        def loss_d(q):
            return dense_attention(q, k, v, mask=causal).astype(jnp.float32).sum()

        gf = jax.jit(jax.grad(loss_f))(q)
        gd = jax.jit(jax.grad(loss_d))(q)
        gerr = float(
            jnp.max(jnp.abs(gf.astype(jnp.float32) - gd.astype(jnp.float32)))
        )
        rec = {
            "probe": "parity",
            "seq": seq,
            "max_abs_err_fwd": round(err, 5),
            "max_abs_err_grad_q": round(gerr, 5),
            "ok": bool(err < 2e-2 and gerr < 2e-1),
        }
        print(json.dumps(rec), flush=True)

    # ---- 2. dense vs flash timing (fwd+bwd), loop-inside-jit ----
    def timed_grad(attn_fn, seq):
        q, k, v = qkv(seq)

        def loss(q):
            return attn_fn(q, k, v).astype(jnp.float32).mean()

        g = jax.grad(loss)

        @jax.jit
        def loop(q):
            def body(_, q):
                return q - 1e-3 * g(q).astype(q.dtype)

            return lax.fori_loop(0, K, body, q)

        out = loop(q)
        _ = float(jnp.asarray(out).ravel()[0])
        t0 = time.perf_counter()
        out = loop(q)
        _ = float(jnp.asarray(out).ravel()[0])
        return (time.perf_counter() - t0) / K

    # ---- 2b. flash block-size sweep at the flagship seq (tuning data;
    # PROBE_BLOCKS="bq:bk,..." to override) ----
    blocks = [
        tuple(int(x) for x in spec.split(":"))
        for spec in os.environ.get(
            "PROBE_BLOCKS", "128:128,256:128,128:256,256:256,512:128"
        ).split(",")
    ]
    for bq, bk in blocks:
        row = {"probe": "block_sweep", "seq": SEQS[0], "bq": bq, "bk": bk}
        try:
            row["flash_ms"] = round(
                timed_grad(
                    lambda q, k, v: flash_attention(
                        q, k, v, causal=True, block_q=bq, block_k=bk
                    ),
                    SEQS[0],
                )
                * 1e3,
                2,
            )
        except Exception as e:
            row["flash_ms"] = None
            row["error"] = type(e).__name__
        print(json.dumps(row), flush=True)

    # ---- 2c. jax library TPU flash kernel (pallas.ops.tpu.flash_attention)
    # as a second baseline: if it beats the in-repo kernel on-chip, adopt
    # it behind attn_impl. Skipped silently off-TPU (it is TPU-only).
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as lib_flash,
        )

        for seq in SEQS:
            row = {"probe": "lib_flash", "seq": seq, "batch": BATCH}
            try:
                row["lib_flash_ms"] = round(
                    timed_grad(
                        lambda q, k, v: lib_flash(q, k, v, causal=True), seq
                    )
                    * 1e3,
                    2,
                )
            except Exception as e:
                row["lib_flash_ms"] = None
                row["error"] = type(e).__name__
            print(json.dumps(row), flush=True)
    except ImportError:
        pass

    # ---- 2d. jax splash-attention kernel (the MaxText production kernel)
    # — fwd+bwd timing on real hardware only: its backward miscompiles in
    # CPU interpret mode (jax 0.9 interpret-machinery bug), so there is no
    # off-chip smoke for it; a model-level attn_impl would follow only if
    # this row beats flash/lib_flash on-chip.
    if not interpret:
        try:
            from jax.experimental.pallas.ops.tpu.splash_attention import (
                splash_attention_kernel as sk,
                splash_attention_mask as sm,
            )

            for seq in SEQS:
                row = {"probe": "splash", "seq": seq, "batch": BATCH}
                kernel = sk.make_splash_mha(
                    sm.MultiHeadMask([sm.CausalMask((seq, seq))] * HEADS),
                    head_shards=1,
                    q_seq_shards=1,
                )
                scale = DIM_HEAD**-0.5
                fn = jax.vmap(lambda q, k, v: kernel(q * scale, k, v))
                try:
                    row["splash_ms"] = round(
                        timed_grad(lambda q, k, v: fn(q, k, v), seq) * 1e3, 2
                    )
                except Exception as e:
                    row["splash_ms"] = None
                    row["error"] = type(e).__name__
                print(json.dumps(row), flush=True)
        except ImportError:
            pass

    for seq in SEQS:
        causal = jnp.tril(jnp.ones((seq, seq), bool))[None, None]
        row = {"probe": "ab", "seq": seq, "batch": BATCH}
        try:
            row["dense_ms"] = round(
                timed_grad(
                    lambda q, k, v: dense_attention(q, k, v, mask=causal), seq
                )
                * 1e3,
                2,
            )
        except Exception as e:  # dense OOMs first at long seq
            row["dense_ms"] = None
            row["dense_error"] = type(e).__name__
        try:
            row["flash_ms"] = round(
                timed_grad(
                    lambda q, k, v: flash_attention(q, k, v, causal=True), seq
                )
                * 1e3,
                2,
            )
        except Exception as e:
            row["flash_ms"] = None
            row["flash_error"] = type(e).__name__
        if row.get("dense_ms") and row.get("flash_ms"):
            row["flash_speedup"] = round(row["dense_ms"] / row["flash_ms"], 2)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
