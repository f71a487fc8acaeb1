"""On the chip, before the cell: the gated delta rule's token step alone at
the cell's shapes against the float32 recurrence, its two candidates timed
(the Pallas kernel `delta_step` at several head blocks, and XLA's fusion of
the same equations, which lives here alone), each with its share of the
roofline; and one period (linear, linear, linear, full) at the published
widths through the program's prefill and cached token steps against the
reference, with the fp8 control beside it.

    chiprun -- python3 benchmark/tests/chip_kernels_olmo.py            # the cell's shapes
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_kernels_olmo.py --tiny   # rehearsal

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


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def timed_in_place(fn, state, *args, reps=20):
    """Seconds a call of `fn(state, *args) -> (o, state)`, the state donated
    and carried from call to call as a token loop carries it."""
    o, state = fn(state, *args)
    jax.block_until_ready(state)
    t = time.perf_counter()
    for _ in range(reps):
        o, state = fn(state, *args)
    jax.block_until_ready((o, state))
    return (time.perf_counter() - t) / reps


def xla_step(state, q, k, v, alpha, beta):
    """XLA's fusion of the token step's equations on states [B, H, d_k, d_v]:
    the candidate the kernel was chosen over (PERF.md, PR 33). Products as
    multiply-and-sum, so that they stay float32 on the vector unit."""
    a = alpha[..., None]
    sk = jnp.sum(state * k[..., None], axis=2)
    sq = jnp.sum(state * q[..., None], axis=2)
    u = beta[..., None] * (v - a * sk)
    new = a[..., None] * state + k[..., None] * u[:, :, None, :]
    return a * sq + jnp.sum(k * q, -1, keepdims=True) * u, new


def xla_step_packed(state, q, k, v, alpha, beta):
    """The same on the cache's own leaf [B, d_k, H x d_v]."""
    b, h, dv = v.shape
    wide = lambda t: jnp.repeat(t, dv, axis=-1)  # [B, H] -> [B, H x d_v]
    kk = jnp.repeat(k.transpose(0, 2, 1), dv, axis=-1)  # [B, d_k, H x d_v]
    qq = jnp.repeat(q.transpose(0, 2, 1), dv, axis=-1)
    sk, sq = jnp.sum(state * kk, axis=1), jnp.sum(state * qq, axis=1)
    u = wide(beta) * (v.reshape(b, h * dv) - wide(alpha) * sk)
    new = wide(alpha)[:, None] * state + kk * u[:, None]
    o = wide(alpha) * sq + wide(jnp.sum(k * q, -1)) * u
    return o.reshape(b, h, dv), new


def step_alone(tiny: bool, rows: int) -> bool:
    from benchmark.trace import costs, costs_olmo
    from dalle_pytorch_tpu.models import decode_cache
    from dalle_pytorch_tpu.ops import delta_step as ds

    B, H, dk, dv = (3, 4, 8, 24) if tiny else (rows, 30, 96, 192)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (B, H, dk, dv), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[1], (B, H, dk))) * dk**-0.5
    k = unit(jax.random.normal(ks[2], (B, H, dk)))
    v = jax.random.normal(ks[3], (B, H, dv))
    alpha = jax.random.uniform(ks[4], (B, H), minval=0.8, maxval=1.0)
    beta = 2.0 * jax.random.uniform(ks[5], (B, H))
    want_o, want_s = ds.delta_step_reference(state, q, k, v, alpha, beta)
    ops, nbytes = costs_olmo.delta_step(B, H, dk, dv)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = costs.least_seconds(ops, nbytes, peak)[0]
    ok = True
    packed = decode_cache.pack_state(state)
    unpack = lambda s: s.reshape(B, dk, H, dv).transpose(0, 2, 1, 3)
    for block in ((2, 4) if tiny else (2, 6, 10, 30)):
        fn = jax.jit(lambda s, *a, block=block: ds.delta_step(s, *a, block=block),
                     donate_argnums=(0,))
        o, s = fn(packed + 0, q, k, v, alpha, beta)
        err = max(rel(o, want_o), rel(unpack(s), want_s))
        ok &= err < 1e-5
        seconds = timed_in_place(fn, packed + 0, q, k, v, alpha, beta)
        say("delta_kernel", rows=B, block=block, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)
    for name, fn, s0, back in (("heads", xla_step, state, lambda s: s),
                               ("packed", xla_step_packed, packed, unpack)):
        fn = jax.jit(fn, donate_argnums=(0,))
        o, s = fn(s0 + 0, q, k, v, alpha, beta)
        err = max(rel(o, want_o), rel(back(s), want_s))
        ok &= err < 1e-5
        seconds = timed_in_place(fn, s0 + 0, q, k, v, alpha, beta)
        say("delta_xla", rows=B, layout=name, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)
    return ok


def period_against_reference(tiny: bool, rows: int) -> bool:
    """One period at the published widths: the program's prefill and cached
    token steps against the reference's uncached forward."""
    from benchmark import build_olmo, harness
    from benchmark.reference import olmo_hybrid_ref as ref
    from dalle_pytorch_tpu.models import decode_cache
    from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

    name = "_tiny-olmo" if tiny else "olmo-hybrid-7b-pp2"
    cfg = dict(harness.load("configs", name), num_hidden_layers=4)
    n, steps, rows = (70, 8, 2) if tiny else (512, 8, 2)
    mdl = CausalLM.from_config(cfg, n + steps)
    variables = build_olmo.seeded_variables(cfg, mdl, 3)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg["vocab_size"], (rows, n + steps)), jnp.int32)
    t = time.perf_counter()
    cache, _ = prefill_cached(mdl, variables, tokens[:, :n], mdl.init_cache(rows))
    jax.block_until_ready(cache)
    say("prefill", seconds=time.perf_counter() - t, rows=rows, tokens=n)
    _, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, tokens[:, n:], steps, filter_thres=1.0,
        logit_rows=rows, start=n)
    got = np.asarray(logits).transpose(1, 0, 2)
    state = np.asarray(decode_cache.running_state(cache, 0, ref.dims(cfg)["lin_heads"]))
    say("counters", **{k: float(x) for k, x in counts.items()})
    del variables, cache
    want = ref.forward(cfg, 3, tokens, start=n)
    low = ref.forward(cfg, 3, tokens, start=n, quant="fp8")
    half = ref.forward(cfg, 3, tokens, start=n, state_round="bfloat16")
    gap = lambda x: float(np.max(np.linalg.norm(x - want["logits"], axis=-1)
                                 / np.linalg.norm(want["logits"], axis=-1)))
    say("period", logit_gap=gap(got), control_logit_gap=gap(low["logits"]),
        state_gap=rel(state, want["state"]), control_state_gap=rel(low["state"], want["state"]),
        bf16_state_gap=rel(half["state"], want["state"]),
        bf16_state_logit_gap=gap(half["logits"]))
    return gap(got) < (1e-3 if tiny else 0.5 * gap(low["logits"]))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--rows", type=int, default=48, help="rows of the step alone (the cell's)")
    p.add_argument("--only", default="step,period")
    args = p.parse_args()
    say("device", platform=jax.devices()[0].platform, kind=jax.devices()[0].device_kind)
    parts = {"step": step_alone, "period": period_against_reference}
    ok = True
    for name in args.only.split(","):
        good = parts[name](args.tiny, args.rows)
        say("part", name=name, ok=bool(good))
        ok &= good
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
