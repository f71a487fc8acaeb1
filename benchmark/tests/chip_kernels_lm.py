"""On the chip, before the cell: the language-model path's kernels alone at
the cell's shapes against dense float32, one layer of each kind against the
reference, and the grouped product timed beside the candidate it was chosen over.

    chiprun -- python3 benchmark/tests/chip_kernels_lm.py            # the cell's shapes
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_kernels_lm.py --tiny   # rehearsal

Prints one `[tag] {json}` line per reading; exits 1 if a comparison is off.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np


def say(tag, **kw):
    print(f"[{tag}] " + json.dumps(kw, default=float), flush=True)


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps, out


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def dense_attention(q, k, v, window, block):
    """float32 masked dense attention, a batch row and a block of query rows
    at a time; q [B, H, N, D], k, v [B, Hkv, N, D]."""
    n, group = q.shape[2], q.shape[1] // k.shape[1]

    def row(args):
        qr, kr, vr = (t.astype(jnp.float32) for t in args)
        kr, vr = jnp.repeat(kr, group, 0), jnp.repeat(vr, group, 0)

        @jax.checkpoint
        def rows(a):
            qi, start = a
            t = start + jnp.arange(block)[:, None]
            p = jnp.arange(n)[None, :]
            live = (p <= t) & ((t - p < window) if window else True)
            s = jnp.einsum("hid,hjd->hij", qi, kr) * q.shape[-1] ** -0.5
            return jnp.einsum("hij,hjd->hid", jax.nn.softmax(jnp.where(live, s, -1e30), -1), vr)

        qb = qr.reshape(qr.shape[0], -1, block, qr.shape[-1]).transpose(1, 0, 2, 3)
        out = jax.lax.map(rows, (qb, jnp.arange(qb.shape[0]) * block))
        return out.transpose(1, 0, 2, 3).reshape(qr.shape)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, (q, k, v))


def flash(args, ok):
    from dalle_pytorch_tpu.ops.pallas_attention import flash_attention

    b, h, hkv, n, d = (1, 4, 2, 64, 16) if args.tiny else (4, 32, 4, 8192, 128)
    block = 32 if args.tiny else 1024
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v, w = (jax.random.normal(kk, (b, hh, n, d), jnp.float32).astype(jnp.bfloat16)
                  for kk, hh in zip(ks, (h, hkv, hkv, h)))
    for window in (8 if args.tiny else 1024, None):
        fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, window=window))
        grad = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, window=window).astype(jnp.float32)
                                    * w.astype(jnp.float32)), (0, 1, 2)))
        want_fwd = jax.jit(lambda q, k, v: dense_attention(q, k, v, window, block))
        want_grad = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(dense_attention(q, k, v, window, block)
                                    * w.astype(jnp.float32)), (0, 1, 2)))
        t_f, o = timed(fwd, q, k, v)
        t_g, g = timed(grad, q, k, v)
        errs = {"o": rel(o, want_fwd(q, k, v))}
        errs.update({n_: rel(a, b_) for n_, a, b_ in zip(("dq", "dk", "dv"), g, want_grad(q, k, v))})
        say("flash", window=window, shape=[b, h, hkv, n, d], fwd_ms=1e3 * t_f,
            fwd_bwd_ms=1e3 * t_g, rel_err=errs)
        ok &= all(e < 2e-2 for e in errs.values())  # bf16 operands against float32
    return ok


def grouped(args, ok):
    from dalle_pytorch_tpu.ops.grouped_matmul import grouped_matmul

    rows, dim, width, groups = (96, 16, 8, 4) if args.tiny else (131072, 2304, 896, 16)
    rng = np.random.default_rng(args.seed)
    share = rng.dirichlet(np.full(groups, 2.0))
    share[1] = 0.0  # an empty group
    sizes = np.floor(share / share.sum() * rows / 2).astype(np.int32)  # half the buffer empty
    ks = jax.random.split(jax.random.PRNGKey(args.seed + 1), 3)
    for k_dim, n_dim in ((dim, width), (width, dim)):
        lhs = jax.random.normal(ks[0], (rows, k_dim), jnp.float32).astype(jnp.bfloat16)
        rhs = jax.random.normal(ks[1], (groups, k_dim, n_dim), jnp.float32) / np.sqrt(k_dim)
        gs = jnp.asarray(sizes)
        w = jax.random.normal(ks[2], (rows, n_dim), jnp.float32).astype(jnp.bfloat16)
        w = w * (jnp.arange(rows) < int(sizes.sum()))[:, None]  # as the layer's cotangent is
        owner = np.repeat(np.arange(groups + 1), np.append(sizes, rows - sizes.sum()))

        def loop(lhs, rhs):
            out = jnp.zeros((rows, n_dim), jnp.float32)
            for g in range(groups):
                mine = jnp.asarray(owner == g)[:, None]
                out += jnp.where(mine, lhs.astype(jnp.float32), 0) @ rhs[g]
            return out

        fwd = jax.jit(grouped_matmul)
        grad = jax.jit(jax.grad(lambda l, r: jnp.sum(
            grouped_matmul(l, r, gs).astype(jnp.float32) * w.astype(jnp.float32)), (0, 1)))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(loop)(lhs, rhs)
            want_g = jax.jit(jax.grad(lambda l, r: jnp.sum(loop(l, r) * w.astype(jnp.float32)),
                                      (0, 1)))(lhs, rhs)
        t_f, out = timed(fwd, lhs, rhs, gs)
        t_g, g = timed(grad, lhs, rhs)
        live = int(sizes.sum())  # rows past the groups are not specified
        errs = {"out": rel(np.asarray(out, np.float32)[:live], np.asarray(want)[:live]),
                "dlhs": rel(np.asarray(g[0], np.float32)[:live], np.asarray(want_g[0])[:live]),
                "drhs": rel(g[1], want_g[1])}
        flops = 2.0 * live * k_dim * n_dim
        say("gmm_pallas", k=k_dim, n=n_dim, rows_present=int(sizes.sum()), buffer=rows,
            fwd_ms=1e3 * t_f, fwd_bwd_ms=1e3 * t_g, fwd_tflops=flops / t_f / 1e12,
            rel_err=errs)
        ok &= all(e < 2e-2 for e in errs.values())
        # the candidate that lost: XLA's own grouped kernel, forward only
        ragged = jax.jit(lambda l, r, s: jax.lax.ragged_dot(
            l, r.astype(l.dtype), s, precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=l.dtype))
        t_r, out_r = timed(ragged, lhs, rhs, gs)
        say("gmm_ragged_dot", k=k_dim, n=n_dim, fwd_ms=1e3 * t_r, fwd_tflops=flops / t_r / 1e12,
            rel_err=rel(np.asarray(out_r, np.float32)[:live], np.asarray(want)[:live]))
    return ok


def layers(args, ok):
    """One layer of each kind, float32 program against the reference."""
    from benchmark import build_lm, harness
    from benchmark.reference import mellum_ref
    from dalle_pytorch_tpu.models.lm import CausalLM

    name = "_tiny-mellum" if args.tiny else "mellum2-12b-ep4"
    n = 32 if args.tiny else 8192
    for kind in ("sliding_attention", "full_attention"):
        cfg = dict(harness.load("configs", name), num_hidden_layers=1)
        cfg["layer_types"] = [kind]
        cfg["mlp_layer_types"] = ["sparse"]
        mdl = CausalLM.from_config(cfg, n, dtype="float32", moe_buffer_rows=8 * n)
        variables = build_lm.seeded_variables(cfg, mdl, args.seed)
        tokens = jax.random.randint(jax.random.PRNGKey(args.seed), (1, n), 0, cfg["vocab_size"])
        with jax.default_matmul_precision("highest"):
            got = jax.jit(mdl.apply)(variables, tokens)
            want = jax.jit(lambda p, t: mellum_ref.logits_fn(p, cfg, t))(
                mellum_ref.init_params(cfg, args.seed), tokens)
        err = rel(got, want)
        say("layer", kind=kind, rel_err=err)
        ok &= err < 1e-4
    return ok


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--parts", default="flash,grouped,layers")
    args = p.parse_args()
    say("device", platform=jax.devices()[0].platform, kind=jax.devices()[0].device_kind)
    ok = True
    for part in args.parts.split(","):
        ok = {"flash": flash, "grouped": grouped, "layers": layers}[part](args, ok)
    say("done", ok=bool(ok))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
