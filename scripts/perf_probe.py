"""Perf triage probe: where does the flagship train step's time go?

Prints one JSON line per experiment.

Every probe runs its op K times INSIDE one jitted program (lax.fori_loop /
lax.scan with data dependence between iterations), makes exactly one
dispatch, and ends with a scalar read-back, so wall time / K ≈ device time
per op with the one dispatch amortized over the whole loop.

Probes:
  peak    — chained bf16 8192^3 matmuls: achievable MXU FLOP/s ceiling
  hbm     — chained elementwise pass over a 1 GiB array: achievable HBM
            read+write bandwidth (the roofline's other axis)
  attn    — one dense attention layer fwd+bwd at flagship geometry
  ff      — one GEGLU FF block fwd+bwd at flagship geometry
  logits  — logits head (18448 vocab) + CE fwd+bwd
  step    — full flagship train step (remat on), scanned K times
  step_noremat — same, remat off, microbatch 8
  fwd     — flagship forward+loss only

Usage: python scripts/perf_probe.py [probe ...]   (default: all)
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

K = int(os.environ.get("PROBE_K", "8"))


def run_probe(name, build, flops_per_iter, emit, k=K):
    """build() -> (jitted_fn, args); jitted_fn must run the op `k` times
    internally and return something reducible to a scalar."""
    import jax
    import jax.numpy as jnp

    fn, args = build()
    out = fn(*args)
    _ = float(jnp.asarray(out).ravel()[0])  # compile + warm, forced
    t0 = time.perf_counter()
    out = fn(*args)
    _ = float(jnp.asarray(out).ravel()[0])
    secs = (time.perf_counter() - t0) / k
    rec = {"probe": name, "ms_per_iter": round(secs * 1e3, 2), "k": k}
    if flops_per_iter:
        rec["tflops_per_sec"] = round(flops_per_iter / secs / 1e12, 1)
    emit(rec)


def main():
    import jax

    import jax.numpy as jnp
    from jax import lax

    only = set(sys.argv[1:]) or None
    dev = jax.devices()[0].device_kind

    def emit(rec):
        rec["device"] = dev
        print(json.dumps(rec), flush=True)

    def want(name):
        return only is None or name in only

    # flagship geometry by default; PROBE_DIM/PROBE_DEPTH/PROBE_FMAP shrink
    # it for CPU smoke runs of the probe script itself
    dim = int(os.environ.get("PROBE_DIM", "1024"))
    depth = int(os.environ.get("PROBE_DEPTH", "12"))
    heads, dim_head = 16, dim // 16
    text_seq = int(os.environ.get("PROBE_TEXT_SEQ", "256"))
    fmap = int(os.environ.get("PROBE_FMAP", "32"))
    image_seq = fmap * fmap
    seq = text_seq + image_seq
    batch = int(os.environ.get("PROBE_BATCH", "16"))
    inner = heads * dim_head

    if want("peak"):
        n = 8192

        def build():
            a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
            b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)

            @jax.jit
            def loop(a, b):
                def body(_, x):
                    y = x @ b
                    return y * lax.rsqrt(jnp.float32(n)).astype(y.dtype)

                return lax.fori_loop(0, K, body, a)

            return loop, (a, b)

        run_probe("peak_matmul_bf16_8192", build, 2 * n**3, emit)

    if want("hbm"):
        # streaming read+write of a 1 GiB bf16 buffer; XLA can't fuse the
        # iterations away because each depends on the previous value.
        # Reported as GB/s = 2 * size / t (one read + one write per pass).
        elems = int(os.environ.get("PROBE_HBM_ELEMS", str(512 * 1024 * 1024)))

        def build():
            x = jnp.ones((elems,), jnp.bfloat16)

            @jax.jit
            def loop(x):
                def body(i, x):
                    # bf16-representable, sign-alternating perturbation: the
                    # value genuinely changes every iteration, so no legal
                    # simplifier pass can elide the dependence chain.
                    delta = jnp.where(i % 2 == 0, jnp.bfloat16(0.25),
                                      jnp.bfloat16(-0.25))
                    return x + delta

                return lax.fori_loop(0, K, body, x)

            return loop, (x,)

        def emit_bw(rec):
            secs = rec["ms_per_iter"] / 1e3
            rec = dict(rec)
            rec["gbytes_per_sec"] = round(2 * elems * 2 / secs / 1e9, 1)
            rec["buffer_gib"] = round(elems * 2 / 2**30, 2)
            emit(rec)

        run_probe("hbm_stream_bw", build, None, emit_bw)

    def grad_loop_probe(name, module, x_shape, flops):
        """K chained fwd+bwd of `module` inside one jit: x <- x - 1e-3*dx."""

        def build():
            x = jax.random.normal(jax.random.PRNGKey(0), x_shape, jnp.bfloat16)
            params = module.init(jax.random.PRNGKey(1), x)

            def loss(p, x):
                out = module.apply(p, x)
                if isinstance(out, tuple):  # Attention returns (out, cache)
                    out = out[0]
                return out.astype(jnp.float32).mean()

            g = jax.grad(loss, argnums=1)

            @jax.jit
            def loop(params, x):
                def body(_, x):
                    return x - 1e-3 * g(params, x).astype(x.dtype)

                return lax.fori_loop(0, K, body, x)

            return loop, (params, x)

        run_probe(name, build, flops, emit)

    if want("attn"):
        from dalle_pytorch_tpu.models.attention import Attention

        attn = Attention(
            dim=dim, heads=heads, dim_head=dim_head, causal=True, seq_len=seq,
            dtype=jnp.bfloat16,
        )
        fl = 3 * batch * (
            2 * seq * dim * 3 * inner
            + 2 * seq * seq * inner * 2
            + 2 * seq * inner * dim
        )
        grad_loop_probe("attn_layer_grad", attn, (batch, seq, dim), fl)

    if want("ff"):
        from dalle_pytorch_tpu.models.transformer import FeedForward

        ff = FeedForward(dim=dim, mult=4, dtype=jnp.bfloat16)
        fl = 3 * batch * (2 * seq * dim * 4 * dim * 2 + 2 * seq * dim * 4 * dim)
        grad_loop_probe("ff_block_grad", ff, (batch, seq, dim), fl)

    if want("logits"):
        total_tokens = 10000 + text_seq + 8192

        def build():
            w = (
                jax.random.normal(
                    jax.random.PRNGKey(0), (dim, total_tokens), jnp.bfloat16
                )
                * 0.02
            )
            h = jax.random.normal(
                jax.random.PRNGKey(1), (batch, seq, dim), jnp.bfloat16
            )
            labels = jnp.zeros((batch, seq), jnp.int32)

            def loss(w, h):
                logits = (h @ w).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits)
                return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()

            g = jax.grad(loss)

            @jax.jit
            def loop(w, h):
                def body(_, w):
                    return w - 1e-3 * g(w, h).astype(w.dtype)

                return lax.fori_loop(0, K, body, w)

            return loop, (w, h)

        run_probe(
            "logits_head_grad", build, 3 * 2 * batch * seq * dim * total_tokens, emit
        )

    def flagship_flops(b):
        from dalle_pytorch_tpu.utils.flops import transformer_train_flops

        return transformer_train_flops(
            dim, depth, heads, dim_head, seq, vocab=10000 + text_seq + 8192
        ) * b

    if want("step") or want("step_noremat") or want("fwd"):
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training import (
            TrainState,
            make_optimizer,
            make_dalle_train_step,
        )

        def make_model(remat, attn_impl="auto"):
            return DALLE(
                dim=dim, depth=depth, heads=heads, dim_head=dim_head,
                num_image_tokens=8192, image_fmap_size=fmap,
                num_text_tokens=10000, text_seq_len=text_seq,
                shift_tokens=True, rotary_emb=True, attn_impl=attn_impl,
                reversible=remat, reversible_impl="remat",
                dtype=jnp.bfloat16,
            )

        attn_impl = os.environ.get("PROBE_ATTN", "auto")

        for name, remat, b in (
            ("step", True, batch),
            ("step_noremat", False, int(os.environ.get("PROBE_NOREMAT_BATCH", "8"))),
        ):
            if not want(name):
                continue

            def build(remat=remat, b=b):
                model = make_model(remat, attn_impl)
                text = jnp.ones((b, text_seq), jnp.int32)
                tokens = jnp.zeros((b, image_seq), jnp.int32)
                params = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)[
                    "params"
                ]
                state = TrainState.create(
                    apply_fn=model.apply, params=params,
                    tx=make_optimizer(3e-4, clip_grad_norm=0.5),
                )
                step = make_dalle_train_step(model)
                batch_dict = {"text": text, "image_tokens": tokens}

                @jax.jit
                def loop(state, batch_dict, rng):
                    def body(carry, r):
                        st, _ = carry
                        st, metrics = step(st, batch_dict, r)
                        return (st, metrics["loss"]), None

                    (st, loss), _ = lax.scan(
                        body,
                        (state, jnp.float32(0)),
                        jax.random.split(rng, K),
                    )
                    return loss

                return loop, (state, batch_dict, jax.random.PRNGKey(1))

            run_probe(f"{name}_b{b}_{attn_impl}", build, flagship_flops(b), emit)

        if want("fwd"):

            def build():
                model = make_model(False, attn_impl)
                text = jnp.ones((batch, text_seq), jnp.int32)
                tokens = jnp.zeros((batch, image_seq), jnp.int32)
                variables = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)

                @jax.jit
                def loop(variables, text, tokens):
                    def body(_, acc):
                        # tie the inputs to the carry (always +0, but data-
                        # dependent) so loop-invariant code motion can't
                        # hoist the forward out of the loop
                        t = text + (acc == jnp.inf).astype(jnp.int32)
                        loss, _ = model.apply(
                            variables, t, tokens, return_loss=True,
                            deterministic=True,
                        )
                        return acc + loss

                    return lax.fori_loop(0, K, body, jnp.float32(0))

                return loop, (variables, text, tokens)

            run_probe(f"fwd_b{batch}_{attn_impl}", build, flagship_flops(batch) / 3, emit)


if __name__ == "__main__":
    main()
