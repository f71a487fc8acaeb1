"""`decode_grouped` alone on the chip at the shape of a full layer's verify step
of `kexaone.decode.16k` (24 rows x 8 K/V heads x 16,960 cached positions of
128 bf16, 16 query rows a K/V head: 8 heads x 2 positions; the rows' lengths
spread over 16,386 .. 16,960), by the positions a grid step streams, beside
XLA's grouped product over the same operands under the same mask (what
`Attention._cached_grouped` ran until PR 40).

    python scripts/chip_grouped_decode.py [--blocks 2048,2432,4352] [--n 1]

Prints a line a block (and one for `xla`): microseconds a call (the mean of
`--calls` calls inside ONE dispatch, each waiting on the one before, so that
dispatch is not read as device time), the live K and V bytes a call, the share
of 819 GB/s they are read at, and the largest distance from dense float32.
`--tiny` rehearses on the CPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# rows, K/V heads, query heads a K/V head, cached positions, head size, shortest row
SHAPE = (24, 8, 8, 16960, 128, 16384)
TINY = (3, 2, 4, 150, 16, 64)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--blocks", default="2048,2432,4352",
                   help="2,432 is what `grouped_decode._block` gives this leaf")
    p.add_argument("--n", type=int, default=2, help="positions a row a step")
    p.add_argument("--calls", type=int, default=50)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dalle_pytorch_tpu.ops import grouped_decode as gd
    from dalle_pytorch_tpu.ops.attention_core import dense_attention

    rows, hkv, group, leaf, dh, shortest = TINY if args.tiny else SHAPE
    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    dt = jnp.float32 if args.tiny else jnp.bfloat16
    calls, n = (2 if args.tiny else args.calls), args.n
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (rows, hkv, group * n, dh), dt)
    k = jax.random.normal(keys[1], (rows, hkv, leaf, dh), dt)
    v = jax.random.normal(keys[2], (rows, hkv, leaf, dh), dt)
    lengths = jnp.asarray(np.linspace(shortest + n, leaf, rows).astype(np.int32))

    def xla(q, k, v, lengths, upcast=False):
        at = (lengths - n)[:, None] + jnp.arange(n, dtype=lengths.dtype)  # [B, n]
        mask = jnp.arange(leaf, dtype=lengths.dtype)[None, None] <= at[:, :, None]
        mask = jnp.broadcast_to(mask[:, None, None], (rows, 1, group, n, leaf))
        if upcast:
            q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        return dense_attention(q, k, v, mask=mask.reshape(rows, 1, group * n, leaf))

    def many(attend):
        @jax.jit
        def run(q, k, v, lengths):
            def call(_, carry):
                q, _ = carry
                out = attend(q, k, v, lengths)
                # the next call waits on this one; what it waits for is never true
                return q + (out[0, 0, 0, 0] > jnp.inf).astype(q.dtype), out
            return lax.fori_loop(0, calls, call, (q, jnp.zeros_like(q)))[1]
        return run

    want = jax.jit(lambda *a: xla(*a, upcast=True))(q, k, v, lengths)
    live = 2 * hkv * dh * int(jnp.sum(lengths)) * k.dtype.itemsize
    variants = {"xla": xla}
    for block in [int(b) for b in args.blocks.split(",") if b]:
        variants[str(block)] = lambda q, k, v, lengths, block=block: gd.grouped_decode_attention(
            q, k, v, lengths, n=n, block=block)
    for name, attend in variants.items():
        run = many(attend)
        jax.block_until_ready(run(q, k, v, lengths))
        t0 = time.perf_counter()
        got = jax.block_until_ready(run(q, k, v, lengths))
        seconds = (time.perf_counter() - t0) / calls
        print("[grouped_decode]", json.dumps({
            "block": name, "positions_a_step": n, "query_rows": group * n,
            "us_a_call": seconds * 1e6, "live_kv_mb": live / 1e6,
            "bandwidth_pct": 100 * live / 819e9 / seconds,
            "max_abs_err": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))),
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
