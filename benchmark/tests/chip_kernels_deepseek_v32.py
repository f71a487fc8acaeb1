"""On the chip, before the cell: learned sparse attention's three parts alone
at the cell's shapes (16 rows x 32,768 live of 33,792 cached positions, 2,048
selected), each against dense float32 and with XLA's own form timed beside it:
the index score kernel, the selection (threshold by counting + compaction to
indices) beside `lax.top_k`, the sparse attend (the fetch of the selected
positions and the attend over them, timed apart and together); and the DENSE
`decode_latent` over the same live positions, which is what the selection has
to beat.

    chiprun -- python3 benchmark/tests/chip_kernels_deepseek_v32.py          # the cell's shapes
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_kernels_deepseek_v32.py --tiny   # rehearsal

Prints one `[tag] {json}` line per reading; exits 1 if a comparison is off.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp
import numpy as np

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9


def say(tag, **kw):
    print(f"[{tag}] " + json.dumps(kw, default=float), flush=True)


def timed(fn, *args, reps=10):
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


def shapes(tiny: bool):
    """(rows, cached, live, selected, heads, kv_rank, rope, index heads, index dim, dtype)."""
    if tiny:
        return 3, 56, 50, 8, 4, 16, 8, 4, 16, jnp.float32
    return 16, 33792, 32768, 2048, 128, 512, 64, 64, 128, jnp.bfloat16


def index_alone(tiny: bool):
    from dalle_pytorch_tpu.ops import index_score as ix

    B, L, live, _, _, _, _, Hi, Di, dt = shapes(tiny)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hi, Di), dt)
    w = jax.random.normal(ks[1], (B, Hi), jnp.float32) * (Hi * Di) ** -0.5
    keys = jax.random.normal(ks[2], (B, L, Di), dt)
    lengths = jnp.full((B,), live, jnp.int32)

    @jax.jit
    def dense(q, w, keys):
        with jax.default_matmul_precision("highest"):
            f = lambda t: t.astype(jnp.float32)
            s = jnp.einsum("bhd,bld->bhl", f(q), f(keys))
            return jnp.einsum("bh,bhl->bl", w, jax.nn.relu(s))

    want = np.asarray(dense(q, w, keys))[:, :live]
    ops = B * live * Hi * (2.0 * Di + 2)
    nbytes = B * live * (Di * keys.dtype.itemsize + 4.0)
    least = max(ops / PEAK_FLOPS, nbytes / PEAK_BYTES)
    ok = True
    for block in ((16, 32) if tiny else (1024, 2048, 4096, 8192)):
        fn = functools.partial(ix._emit, block=block, interpret=ix._use_interpret())
        seconds, got = timed(fn, q, w, keys, lengths)
        got = np.asarray(got)
        err = rel(got[:, :live], want)
        ok &= err < (1e-5 if tiny else 2e-2) and bool((got[:, live:] <= -1e29).all())
        say("index_kernel", block=block, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)

    @jax.jit
    def xla(q, w, keys):
        s = jnp.einsum("bhd,bld->bhl", q, keys, preferred_element_type=jnp.float32)
        return jnp.einsum("bh,bhl->bl", w, jax.nn.relu(s))

    seconds, got = timed(xla, q, w, keys)
    say("index_xla", ms=1e3 * seconds, rel_err=rel(np.asarray(got)[:, :live], want),
        roofline_pct=100 * least / seconds)
    scores = jax.jit(ix.index_scores)(q, w, keys, lengths)
    return ok, scores, lengths


def selection_alone(tiny: bool, scores, lengths):
    from dalle_pytorch_tpu.ops import index_select as sel

    k = shapes(tiny)[3]

    @jax.jit
    def select(scores, lengths):
        mask, count = sel.selected_mask(scores, lengths, k)
        return sel.selected_indices(mask, k), count, jnp.sum(mask, axis=-1)

    seconds, (picked, count, total) = timed(select, scores, lengths)
    top = jax.jit(lambda s: jax.lax.top_k(s, k)[1])
    top_seconds, best = timed(top, scores)
    picked, best = np.asarray(picked), np.asarray(best)
    same = all(set(p) == set(b) for p, b in zip(picked, best))
    ok = (same and bool((np.asarray(count) == k).all()) and bool((np.asarray(total) == k).all())
          and bool((np.diff(picked, axis=-1) > 0).all()))
    parts = {}
    for name, fn in (("threshold_mask", lambda s, n: sel.selected_mask(s, n, k)[0]),
                     ("compaction", lambda s, n: sel.selected_indices(s > 0, k))):
        parts[name + "_ms"] = 1e3 * timed(jax.jit(fn), scores, lengths)[0]
    say("selection", ms=1e3 * seconds, lax_top_k_ms=1e3 * top_seconds, same_sets=same, **parts)
    return ok, jnp.asarray(picked), jnp.asarray(count)


def attend_alone(tiny: bool, picked, count):
    from dalle_pytorch_tpu.ops import latent_decode as ld, sparse_latent_decode as sp

    B, L, live, k, H, R, dr, _, _, dt = shapes(tiny)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q_c = jax.random.normal(ks[0], (B, H, R), dt)
    q_r = jax.random.normal(ks[1], (B, H, dr), dt)
    latent = jax.random.normal(ks[2], (B, L, R), dt)
    rope = jax.random.normal(ks[3], (B, dr, L), dt)
    scale = (R / 4 + dr) ** -0.5  # scores of unit-normal operands: keep the softmax soft

    @jax.jit
    def dense(q_c, q_r, latent, rope, seen):
        with jax.default_matmul_precision("highest"):
            f = lambda t: t.astype(jnp.float32)
            s = (jnp.einsum("bhr,blr->bhl", f(q_c), f(latent))
                 + jnp.einsum("bhd,bdl->bhl", f(q_r), f(rope))) * scale
            p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1)
            return jnp.einsum("bhl,blr->bhr", p, f(latent))

    rows = jnp.arange(B)[:, None]
    chosen = jnp.zeros((B, L), bool).at[rows, picked].set(True)
    want = dense(q_c, q_r, latent, rope, chosen)
    tol = 1e-5 if tiny else 2e-2
    ops = 2.0 * B * H * (2 * R + dr) * k
    nbytes = B * k * (R + dr) * latent.dtype.itemsize
    least = max(ops / PEAK_FLOPS, nbytes / PEAK_BYTES)
    whole = jax.jit(lambda *a: sp.sparse_latent_decode_attention(*a, sm_scale=scale))
    seconds, got = timed(whole, q_c, q_r, latent, rope, picked, count)
    ok = rel(got, want) < tol
    fetch = jax.jit(sp.fetch_selected)
    fetch_seconds, (some, turned) = timed(fetch, latent, rope, picked)
    part = {
        "fetch_latent_ms": 1e3 * timed(jax.jit(lambda l, i: jnp.take_along_axis(
            l, i[:, :, None], axis=1, mode="promise_in_bounds")), latent, picked)[0],
        "fetch_rope_ms": 1e3 * timed(jax.jit(lambda r, i: jnp.take_along_axis(
            r, i[:, None, :], axis=2, mode="promise_in_bounds")), rope, picked)[0],
    }
    attend = jax.jit(lambda *a: ld.latent_decode_attention(*a, sm_scale=scale))
    attend_seconds = timed(attend, q_c, q_r, some, turned, count)[0]

    @jax.jit
    def xla(q_c, q_r, some, turned):  # XLA's two products over what was fetched
        s = (jnp.einsum("bhr,bkr->bhk", q_c, some, preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bdk->bhk", q_r, turned, preferred_element_type=jnp.float32)) * scale
        return jnp.einsum("bhk,bkr->bhr", jax.nn.softmax(s, -1).astype(some.dtype), some,
                          preferred_element_type=jnp.float32).astype(q_c.dtype)

    xla_seconds, other = timed(xla, q_c, q_r, some, turned)
    say("sparse_attend", ms=1e3 * seconds, rel_err=rel(got, want), fetch_ms=1e3 * fetch_seconds,
        attend_ms=1e3 * attend_seconds, attend_xla_ms=1e3 * xla_seconds,
        attend_xla_rel_err=rel(other, want), roofline_pct=100 * least / seconds, **part)
    # what the selection has to beat: the dense kernel over every live position
    everything = jnp.broadcast_to(jnp.arange(L) < live, (B, L))
    dense_seconds, full = timed(attend, q_c, q_r, latent, rope, jnp.full((B,), live, jnp.int32))
    ok &= rel(full, dense(q_c, q_r, latent, rope, everything)) < tol
    dense_least = max(2.0 * B * H * (2 * R + dr) * live / PEAK_FLOPS,
                      B * live * (R + dr) * latent.dtype.itemsize / PEAK_BYTES)
    say("dense_latent", ms=1e3 * dense_seconds, positions=live,
        roofline_pct=100 * dense_least / dense_seconds)
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    d = jax.devices()[0]
    say("device", platform=d.platform, kind=d.device_kind)
    if not args.tiny and d.platform != "tpu":
        print("the cell's shapes are measured on the chip; --tiny rehearses", file=sys.stderr)
        return 2
    ok_index, scores, lengths = index_alone(args.tiny)
    ok_select, picked, count = selection_alone(args.tiny, scores, lengths)
    ok_attend = attend_alone(args.tiny, picked, count)
    say("ok", index=bool(ok_index), selection=bool(ok_select), attend=bool(ok_attend))
    return 0 if ok_index and ok_select and ok_attend else 1


if __name__ == "__main__":
    sys.exit(main())
