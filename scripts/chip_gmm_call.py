"""`gmm_fwd` alone on the chip at a step's sizes of a routed generation cell,
with the rows on a given number of the experts: what a call costs by the
experts it touches, and, with none touched, what the grid's dead steps cost.
`--shape pangu`: a token step of `pangu.decode.8k` (a 512-row buffer, 16 held
experts of 7,680 x 2,048 bf16, 58 rows present); `--shape kexaone`: a verify
step of `kexaone.decode.16k` (384 rows, 6,144 x 2,048, 43 present).

    python scripts/chip_gmm_call.py --touched 0,1,6,16 [--tree <checkout>]
    python scripts/chip_gmm_call.py --shape kexaone --touched 0,1,8,9,16 [--tile 64]

`--tree` times another checkout's `ops/grouped_matmul.py` with the same inputs
(the parent's, unpacked beside this one); `--tile` puts a row tile of that
many rows in the place of the module's rule. Prints a line a shape (gate/up,
out) and count: the tile the product got, microseconds a call (the mean of
`--calls` calls inside ONE dispatch, each waiting on the one before), and the
share of the bandwidth that the touched experts' matrices are read at.
`--tiny` rehearses on the CPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path


# rows of the buffer, the model's width, an expert's, experts held, rows present
SHAPES = {"pangu": (512, 7680, 2048, 16, 58), "kexaone": (384, 6144, 2048, 16, 43)}
TINY = {"pangu": (64, 128, 256, 4, 9), "kexaone": (48, 128, 256, 4, 7)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--shape", choices=sorted(SHAPES), default="pangu")
    p.add_argument("--touched", default="0,1,6,16")
    p.add_argument("--tile", type=int, default=0)
    p.add_argument("--tree", default=str(Path(__file__).resolve().parent.parent))
    p.add_argument("--calls", type=int, default=200)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dalle_pytorch_tpu.ops import grouped_matmul as gm

    rows, dim, width, groups, present = (TINY if args.tiny else SHAPES)[args.shape]
    if args.tile:
        gm._row_tile = lambda rows, groups=1: min(args.tile, rows)
    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    dt = jnp.float32 if args.tiny else jnp.bfloat16
    calls = 3 if args.tiny else args.calls

    @jax.jit
    def many(lhs, rhs, sizes):
        def call(_, carry):
            sizes, _ = carry
            out = gm.grouped_matmul(lhs, rhs, sizes)
            # the next call waits on this one; what it waits for is never true
            return sizes + (out[0, 0] > jnp.inf).astype(sizes.dtype), out
        return lax.fori_loop(0, calls, call, (sizes, jnp.zeros((rows, rhs.shape[2]), dt)))[1]

    for name, (k, n) in {"gate_up": (dim, width), "out": (width, dim)}.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        lhs = jax.random.normal(keys[0], (rows, k), dt)
        rhs = (jax.random.normal(keys[1], (groups, k, n)) / np.sqrt(k)).astype(dt)
        for touched in [int(t) for t in args.touched.split(",")]:
            sizes = np.zeros(groups, np.int32)
            if touched:  # the rows present, spread over experts spread over those held
                at = np.arange(touched) * groups // touched
                sizes[at] = present // touched + (np.arange(touched) < present % touched)
            got = many(lhs, rhs, jnp.asarray(sizes))
            jax.block_until_ready(got)
            t0 = time.perf_counter()
            got = many(lhs, rhs, jnp.asarray(sizes))
            jax.block_until_ready(got)
            seconds = (time.perf_counter() - t0) / calls
            live = int(sizes.sum())
            owner = np.repeat(np.arange(groups), sizes)
            want = jnp.einsum("rk,rkn->rn", lhs[:live], rhs[owner],
                              preferred_element_type=jnp.float32)
            err = float(jnp.max(jnp.abs(got[:live].astype(jnp.float32) - want))) if live else 0.0
            read = touched * k * n * rhs.dtype.itemsize
            print("[gmm_call]", json.dumps({
                "tree": args.tree, "cell": args.shape, "shape": name,
                "tile": gm._row_tile(rows, groups), "touched": touched, "rows": live,
                "us_a_call": seconds * 1e6, "matrices_mb": read / 1e6,
                "bandwidth_pct": 100 * read / 819e9 / seconds,
                "max_abs_err": err, "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
