"""On the chip, before the cell: the latent decode attention alone at the
cell's shapes against dense float32, its two candidates timed (the Pallas
kernel at several block widths and XLA's two products); the grouped product
at a token step's sizes, timed at two row tiles; and one layer of each kind
(dense, routed) through the program's prefill and cached token steps against
the reference, with the fp8 control beside it.

    chiprun -- python3 benchmark/tests/chip_kernels_pangu.py            # the cell's shapes
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_kernels_pangu.py --tiny   # rehearsal

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


def attention_alone(tiny: bool) -> bool:
    from dalle_pytorch_tpu.ops import latent_decode as ld

    B, H, R, dr, L, lo = (3, 4, 16, 8, 50, 17) if tiny else (64, 128, 512, 64, 8480, 8192)
    dt = jnp.float32 if tiny else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q_c = jax.random.normal(ks[0], (B, H, R), dt)
    q_r = jax.random.normal(ks[1], (B, H, dr), dt)
    latent = jax.random.normal(ks[2], (B, L, R), dt)
    rope = jax.random.normal(ks[3], (B, dr, L), dt)
    # rows of different lengths, none a multiple of a block
    lengths = jnp.asarray(lo + (np.arange(B) * 37) % (L - lo), jnp.int32)
    scale = (R / 4 + dr) ** -0.5  # scores of unit-normal operands: keep the softmax soft

    @jax.jit
    def dense(q_c, q_r, latent, rope, lengths):
        with jax.default_matmul_precision("highest"):
            f = lambda t: t.astype(jnp.float32)
            s = (jnp.einsum("bhr,blr->bhl", f(q_c), f(latent))
                 + jnp.einsum("bhd,bdl->bhl", f(q_r), f(rope))) * scale
            live = jnp.arange(L)[None, None] < lengths[:, None, None]
            p = jax.nn.softmax(jnp.where(live, s, -1e30), -1)
            return jnp.einsum("bhl,blr->bhr", p, f(latent))

    want = dense(q_c, q_r, latent, rope, lengths)
    ok = True
    flops = 2.0 * H * (2 * R + dr) * float(jnp.sum(lengths))
    nbytes = (R + dr) * float(jnp.sum(lengths)) * latent.dtype.itemsize
    least = max(flops / 197e12, nbytes / 819e9)
    blocks = (16, 32) if tiny else (512, 1024, 2048)
    for block in blocks:
        fn = jax.jit(lambda *a, block=block: ld.latent_decode_attention(*a, sm_scale=scale, block=block))
        seconds, got = timed(fn, q_c, q_r, latent, rope, lengths)
        err = rel(got, want)
        ok &= err < (1e-5 if tiny else 2e-2)
        say("latent_kernel", block=block, ms=1e3 * seconds, rel_err=err,
            roofline_pct=100 * least / seconds)
    # the candidate the kernel was chosen over (PERF.md, PR 31), kept here
    # alone: XLA's two batched products around a float32 softmax
    @jax.jit
    def xla(q_c, q_r, latent, rope, lengths):
        s = (jnp.einsum("bhr,blr->bhl", q_c, latent, preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bdl->bhl", q_r, rope, preferred_element_type=jnp.float32)) * scale
        live = jnp.arange(L)[None, None] < lengths[:, None, None]
        p = jax.nn.softmax(jnp.where(live, s, -1e30), -1)
        return jnp.einsum("bhl,blr->bhr", p.astype(latent.dtype), latent,
                          preferred_element_type=jnp.float32).astype(q_c.dtype)

    seconds, got = timed(xla, q_c, q_r, latent, rope, lengths)
    err = rel(got, want)
    ok &= err < (1e-5 if tiny else 2e-2)
    say("latent_xla", ms=1e3 * seconds, rel_err=err, roofline_pct=100 * least / seconds)
    return ok


def grouped_at_decode(tiny: bool) -> bool:
    """`gmm_fwd` at a token step's sizes: 512 buffer rows of which ~32 hold
    an assignment, 16 groups; the row tile as the kernel chooses it."""
    from dalle_pytorch_tpu.ops import grouped_matmul as gm

    rows, dim, width, groups = (64, 64, 32, 4) if tiny else (512, 7680, 2048, 16)
    dt = jnp.float32 if tiny else jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    lhs = jax.random.normal(ks[0], (rows, dim), dt)
    rhs = (jax.random.normal(ks[1], (groups, dim, width)) / np.sqrt(dim)).astype(dt)
    sizes = jnp.asarray(([2] * groups), jnp.int32).at[0].set(0).at[1].set(4)
    n = int(sizes.sum())
    ends = np.cumsum(np.asarray(sizes))
    owner = np.searchsorted(ends, np.arange(n), side="right")
    with jax.default_matmul_precision("highest"):
        want = jnp.einsum("rk,rkn->rn", lhs[:n].astype(jnp.float32),
                          rhs.astype(jnp.float32)[owner])
    nbytes = groups * dim * width * rhs.dtype.itemsize
    rule, ok = gm._row_tile, True
    for tile in (None, gm.TILE_ROWS):  # the kernel's own choice, then a whole tile
        if tile is not None:
            gm._row_tile = lambda rows, groups=1, tile=tile: min(tile, rows)
            jax.clear_caches()
        seconds, got = timed(jax.jit(gm.grouped_matmul), lhs, rhs, sizes)
        err = rel(np.asarray(got)[:n], want)
        ok &= err < (1e-5 if tiny else 2e-2)
        say("gmm_decode", rows=rows, tile=gm._row_tile(rows, groups), ms=1e3 * seconds,
            rel_err=err, weights_read_pct=100 * nbytes / 819e9 / seconds)
    gm._row_tile = rule
    jax.clear_caches()
    return ok


def layers_against_reference(tiny: bool) -> bool:
    """Two layers (dense, routed) at the published widths: the program's
    prefill and cached token steps against the reference's uncached forward."""
    from benchmark import build_pangu, harness
    from benchmark.reference import pangu_ref
    from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

    name = "_tiny-pangu" if tiny else "pangu-ultra-moe-ep16"
    cfg = dict(harness.load("configs", name), num_hidden_layers=2)
    n, steps, rows = (24, 8, 2) if tiny else (2048, 8, 2)
    mdl = CausalLM.from_config(cfg, n + steps, moe_buffer_rows=rows * n * 8 // 2)
    variables = build_pangu.seeded_variables(cfg, mdl, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], (rows, n + steps)),
                         jnp.int32)
    cache, counts = prefill_cached(mdl, variables, tokens[:, :n], mdl.init_cache(rows))
    _, logits, step_counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, tokens[:, n:], steps, filter_thres=1.0,
        logit_rows=rows, start=n)
    got = np.asarray(logits).transpose(1, 0, 2)
    layer = pangu_ref.dims(cfg)["kinds"].index("routed")
    choices = np.asarray(jax.jit(lambda v, t: mdl.apply(
        v, t, layer, method=CausalLM.route_choices))(variables, tokens))[:, n:]
    say("layers_counts", prefill=jax.tree.map(lambda x: np.asarray(x).tolist(), counts),
        steps=jax.tree.map(lambda x: np.asarray(x).tolist(), step_counts))
    del variables, cache
    want = pangu_ref.forward(cfg, 3, tokens, start=n)
    low = pangu_ref.forward(cfg, 3, tokens, start=n, quant="fp8")
    from benchmark.loops.train_lm import flip_share as flips

    gap = lambda x: float(np.max(np.linalg.norm(x - want["logits"], axis=-1)
                                 / np.linalg.norm(want["logits"], axis=-1)))
    say("layers", logit_gap=gap(got), control_logit_gap=gap(low["logits"]),
        route_flip_share=flips(choices, want["choices"]),
        control_route_flip_share=flips(low["choices"], want["choices"]))
    return gap(got) < (1e-4 if tiny else 0.5 * gap(low["logits"]))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--only", default="attention,gmm,layers")
    args = p.parse_args()
    say("device", platform=jax.devices()[0].platform, kind=jax.devices()[0].device_kind)
    parts = {"attention": attention_alone, "gmm": grouped_at_decode,
             "layers": layers_against_reference}
    ok = True
    for name in args.only.split(","):
        good = parts[name](args.tiny)
        say("part", name=name, ok=bool(good))
        ok &= good
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
