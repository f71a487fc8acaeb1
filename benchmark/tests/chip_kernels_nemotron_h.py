"""On the chip, before the cell: a Mamba-2 layer's token step alone at the
cell's shapes against the float32 recurrence, its two candidates timed (the
Pallas kernel `ssm_step` at several blocks of groups, and XLA's fusion of the
same equations, which lives here alone), each with its share of the roofline;
and one period (`MEMEM*EME`, the nine layers) at the published widths through
the program's prefill and per-row cached token steps against the reference,
rows of two lengths in one cache, with both controls beside it (the reference
in fp8; the state rounded to bfloat16 at every token).

    chiprun -- python3 benchmark/tests/chip_kernels_nemotron_h.py            # the cell's shapes
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_kernels_nemotron_h.py --tiny   # rehearsal

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
    """Seconds a call of `fn(state, *args) -> (y, state)`, the state donated
    and carried from call to call as a token loop carries it."""
    y, state = fn(state, *args)
    jax.block_until_ready(state)
    t = time.perf_counter()
    for _ in range(reps):
        y, state = fn(state, *args)
    jax.block_until_ready((y, state))
    return (time.perf_counter() - t) / reps


def xla_step(state, x, dt, a, b, c, d):
    """XLA's fusion of the token step's equations on states [B, H, N, P]: the
    candidate the kernel is timed beside. Products as multiply-and-sum, so
    that they stay float32 on the vector unit."""
    per_head = lambda t: jnp.repeat(t, x.shape[1] // t.shape[1], axis=1)  # [B, H, N]
    new = (jnp.exp(dt * a)[..., None, None] * state
           + per_head(b)[..., :, None] * (dt[..., None] * x)[..., None, :])
    return jnp.sum(new * per_head(c)[..., :, None], axis=2) + d[:, None] * x, new


def xla_step_packed(state, x, dt, a, b, c, d):
    """The same on the cache's own leaf [B, N, H x P]."""
    from dalle_pytorch_tpu.ops.ssm_step import ssm_step_operands

    rows, heads, p = x.shape
    width = heads * p // b.shape[1]
    decay, dtx, skip = ssm_step_operands(x, dt, a, d)
    wide = lambda t: jnp.repeat(t.transpose(0, 2, 1), width, axis=-1)  # [B, N, H x P]
    new = state * decay[:, None] + wide(b) * dtx[:, None]
    return (jnp.sum(new * wide(c), axis=1) + skip).reshape(rows, heads, p), new


def step_alone(tiny: bool, rows: int) -> bool:
    from benchmark.trace import costs, costs_nemotron_h
    from dalle_pytorch_tpu.models import decode_cache
    from dalle_pytorch_tpu.ops import ssm_step as ss

    B, H, P, G, N = (3, 8, 8, 2, 16) if tiny else (rows, 64, 64, 8, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(ks[0], (B, H, N, P), jnp.float32)
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.random.uniform(ks[2], (B, H), minval=0.001, maxval=0.1)
    b, c = jax.random.normal(ks[3], (B, G, N)), jax.random.normal(ks[4], (B, G, N))
    a = -jax.random.uniform(ks[5], (H,), minval=1.0, maxval=16.0)
    d = 1.0 + 0.1 * jax.random.normal(ks[6], (H,))
    want_y, want_s = ss.ssm_step_reference(state, x, dt, a, b, c, d)
    ops, nbytes = costs_nemotron_h.ssm_step(B, H, P, N, G, 1)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = costs.least_seconds(ops, nbytes, peak)[0]
    ok = True
    packed = decode_cache.pack_state(state)
    unpack = lambda s: s.reshape(B, N, H, P).transpose(0, 2, 1, 3)
    for block in ((1, 2) if tiny else (1, 2, 4, 8)):
        def kernel(s, x, dt, b, c, block=block):
            y, s = ss.ssm_step(s, *ss.ssm_step_operands(x, dt, a, d), b, c, block=block)
            return y.reshape(x.shape), s

        fn = jax.jit(kernel, donate_argnums=(0,))
        try:
            y, s = fn(packed + 0, x, dt, b, c)
        except Exception as e:  # a block Mosaic refuses (its VMEM): said, not fatal
            say("ssm_kernel", rows=B, block=block, refused=str(e)[:200])
            continue
        err = max(rel(y, want_y), rel(unpack(s), want_s))
        ok &= err < 1e-5
        seconds = timed_in_place(fn, packed + 0, x, dt, b, c)
        say("ssm_kernel", rows=B, block=block, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)
    for name, step, s0, back in (("heads", xla_step, state, lambda s: s),
                                 ("packed", xla_step_packed, packed, unpack)):
        fn = jax.jit(lambda s, x, dt, b, c, step=step: step(s, x, dt, a, b, c, d),
                     donate_argnums=(0,))
        y, s = fn(s0 + 0, x, dt, b, c)
        err = max(rel(y, want_y), rel(back(s), want_s))
        ok &= err < 1e-5
        seconds = timed_in_place(fn, s0 + 0, x, dt, b, c)
        say("ssm_xla", rows=B, layout=name, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)
    return ok


def period_against_reference(tiny: bool, rows: int) -> bool:
    """The nine layers at the published widths: the program's prefill and
    per-row cached token steps, rows of two lengths in one cache, against the
    reference's uncached forward and its two controls."""
    from benchmark import build_nemotron_h, harness
    from benchmark.reference import nemotron_h_ref as ref
    from dalle_pytorch_tpu.models import decode_cache
    from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

    cfg = harness.load("configs", "_tiny-nemotron-h" if tiny else "nemotron3-nano-30b-ep2")
    lengths, steps = ((70, 37), 8) if tiny else ((1024, 384), 16)
    mdl = CausalLM.from_config(cfg, max(lengths) + steps,
                               moe_buffer_rows=max(lengths) * cfg["num_experts_per_tok"])
    variables = build_nemotron_h.seeded_variables(cfg, mdl, 3)
    rng = np.random.default_rng(0)
    seqs = [jnp.asarray(rng.integers(0, cfg["vocab_size"], (1, n + steps)), jnp.int32)
            for n in lengths]
    cache = mdl.init_cache(len(lengths))
    t = time.perf_counter()
    dropped = 0
    for r, (seq, n) in enumerate(zip(seqs, lengths)):
        cache, counts = prefill_cached(mdl, variables, seq[:, :n], cache, r)
        dropped += int(np.sum(counts["moe_dropped"]))
    jax.block_until_ready(cache)
    say("prefill", seconds=time.perf_counter() - t, tokens=list(lengths), moe_dropped=dropped)
    forced = jnp.concatenate([seq[:, n:] for seq, n in zip(seqs, lengths)])
    _, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, forced, steps, filter_thres=1.0,
        logit_rows=len(lengths), start=jnp.asarray(lengths, jnp.int32))
    got = np.asarray(logits["logits"])[:, :, 0].transpose(1, 0, 2)  # [rows, steps, V]
    state = np.asarray(decode_cache.running_state(cache, 0, ref.dims(cfg)["ssm_heads"]))
    say("counters", **{k: float(np.sum(x)) for k, x in counts.items()})
    del variables, cache
    run = lambda **how: ref.forward(cfg, 3, seqs, start=list(lengths), **how)
    want, low, half = run(), run(quant="fp8"), run(state_round="bfloat16")
    stack = lambda out, k: np.concatenate(out[k])
    gaps = lambda x: (np.linalg.norm(x - stack(want, "logits"), axis=-1)
                      / np.linalg.norm(stack(want, "logits"), axis=-1))
    gap, mid = (lambda x: float(np.max(gaps(x)))), (lambda x: float(np.median(gaps(x))))
    flips = lambda out: float(np.mean(stack(out, "choices") != stack(want, "choices")))
    say("period", logit_gap=gap(got), logit_gap_median=mid(got),
        control_logit_gap=gap(stack(low, "logits")),
        control_logit_gap_median=mid(stack(low, "logits")),
        state_gap=rel(state, stack(want, "state")),
        control_state_gap=rel(stack(low, "state"), stack(want, "state")),
        bf16_state_gap=rel(stack(half, "state"), stack(want, "state")),
        bf16_state_logit_gap=gap(stack(half, "logits")),
        bf16_state_logit_gap_median=mid(stack(half, "logits")),
        control_choices_off=flips(low), bf16_state_choices_off=flips(half))
    # a router's near-tie that flips moves one step's logits wholly (the worst
    # step says that, whoever is judged): the MEDIAN step is what precision moves
    # the state: the program's inputs to the recurrence are bf16 activations
    # (the pre-norm's output), so it reads near the bf16-state control's and far
    # under the fp8 control's
    ok = dropped == 0 and rel(state, stack(want, "state")) < 0.5 * rel(
        stack(low, "state"), stack(want, "state"))
    return ok and mid(got) < (1e-3 if tiny else 0.5 * mid(stack(low, "logits")))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--rows", type=int, default=192, help="rows of the step alone (the cell's)")
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
