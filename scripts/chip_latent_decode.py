"""`decode_latent` alone on the chip at the two shapes the cells call it with,
by the positions a grid step streams:

    pangu       64 rows x 128 heads x (512 + 64) over a leaf of 8,480 positions,
                the rows' lengths spread over 8,193 .. 8,480 (`pangu.decode.8k`:
                a turn's 288 steps walk that range, all rows in step)
    deepseek32  16 rows x 128 heads over a leaf of 2,048 fetched positions, all
                live (`deepseek32.decode.32k`: `ops/sparse_latent_decode.py`)

    python scripts/chip_latent_decode.py [--shapes pangu,deepseek32]
        [--blocks 1024,1792,2176,2944] [--tree <checkout>]

Prints a line a shape, block and kernel: microseconds a call (the mean of
`--calls` calls inside ONE dispatch, each waiting on the one before, so that
dispatch is not read as device time), the grid steps a row, the share of the
columns computed that hold no live position, the share of the kernel's
roofline (`benchmark/trace/costs_pangu.py:latent_attend`'s: the live
positions' bytes at 819 GB/s or their products at 197 TFLOP/s, the larger),
and the largest distance from dense float32. With `--tree` the kernel of
that checkout's `ops/latent_decode.py` is timed first at every block, as
`tree`, and this tree's line (`here`) says how far its result lies from that
one's (0 where both round alike): how PR 50 read a body that leaves the masks
out of whole blocks, and a `cost_estimate`, against the body that stands. A
block the chip's compiler refuses (VMEM) is a line with its error. `--tiny`
rehearses on the CPU.
"""

import argparse
import importlib.util
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# rows, heads, latent width, rotary width, cached positions, shortest row
SHAPES = {"pangu": (64, 128, 512, 64, 8480, 8193), "deepseek32": (16, 128, 512, 64, 2048, 2048)}
TINY = {"pangu": (3, 4, 16, 8, 300, 140), "deepseek32": (2, 4, 16, 8, 256, 256)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--shapes", default="pangu,deepseek32")
    p.add_argument("--blocks", default="1024,1792,2176,2944",
                   help="of 8,480 positions: 9, 5, 4 and 3 grid steps a row")
    p.add_argument("--tree", help="a checkout whose kernel is timed beside this tree's")
    p.add_argument("--calls", type=int, default=50)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark.trace.costs_pangu import latent_attend
    from dalle_pytorch_tpu.ops import latent_decode as ld

    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    bodies = {}
    if args.tree:
        spec = importlib.util.spec_from_file_location(
            "tree_latent_decode", Path(args.tree) / "dalle_pytorch_tpu/ops/latent_decode.py")
        bodies["tree"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bodies["tree"])
    bodies["here"] = ld
    dt = jnp.float32 if args.tiny else jnp.bfloat16
    calls = 2 if args.tiny else args.calls

    def many(attend):
        @jax.jit
        def run(q_c, q_r, latent, rope, lengths):
            def call(_, carry):
                q_c, _ = carry
                out = attend(q_c, q_r, latent, rope, lengths)
                # the next call waits on this one; what it waits for is never true
                return q_c + (out[0, 0, 0] > jnp.inf).astype(q_c.dtype), out
            return lax.fori_loop(0, calls, call, (q_c, jnp.zeros_like(q_c)))[1]
        return run

    for name in [s for s in args.shapes.split(",") if s]:
        rows, heads, width, dr, leaf, shortest = (TINY if args.tiny else SHAPES)[name]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q_c = jax.random.normal(keys[0], (rows, heads, width), dt)
        q_r = jax.random.normal(keys[1], (rows, heads, dr), dt)
        latent = jax.random.normal(keys[2], (rows, leaf, width), dt)
        rope = jax.random.normal(keys[3], (rows, dr, leaf), dt)
        lengths = jnp.asarray(np.linspace(shortest, leaf, rows).astype(np.int32))
        scale = (width / 4 + dr) ** -0.5  # scores of unit-normal operands: keep the softmax soft

        @jax.jit
        def dense(q_c, q_r, latent, rope, lengths):
            with jax.default_matmul_precision("highest"):
                f = lambda t: t.astype(jnp.float32)
                s = (jnp.einsum("bhr,blr->bhl", f(q_c), f(latent))
                     + jnp.einsum("bhd,bdl->bhl", f(q_r), f(rope))) * scale
                live = jnp.arange(leaf)[None, None] < lengths[:, None, None]
                return jnp.einsum("bhl,blr->bhr", jax.nn.softmax(jnp.where(live, s, -1e30), -1),
                                  f(latent))

        want = dense(q_c, q_r, latent, rope, lengths)
        live = float(jnp.sum(lengths))
        flops, nbytes = latent_attend(1, heads, width, dr, live, latent.dtype.itemsize)
        least = max(flops / 197e12, nbytes / 819e9)
        blocks = [128, 256] if args.tiny else [int(b) for b in args.blocks.split(",") if b]
        of_tree = {}  # block -> what the other tree's kernel gave there
        for block, (body, module) in itertools.product(
                [b for b in blocks if b <= leaf], bodies.items()):
            attend = lambda *a: module.latent_decode_attention(*a, sm_scale=scale, block=block)
            line = {"shape": name, "body": body, "block": block, "steps_a_row": -(-leaf // block)}
            computed = -(-np.asarray(lengths) // block) * block  # a cut-short block is computed whole
            line["dead_columns_pct"] = 100 * (1 - live / float(computed.sum()))
            try:
                run = many(attend)
                jax.block_until_ready(run(q_c, q_r, latent, rope, lengths))
                t0 = time.perf_counter()
                got = jax.block_until_ready(run(q_c, q_r, latent, rope, lengths))
                seconds = (time.perf_counter() - t0) / calls
                line.update(us_a_call=seconds * 1e6, roofline_pct=100 * least / seconds,
                            max_abs_err=float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))))
                if body == "tree":
                    of_tree[block] = got
                elif block in of_tree:
                    line["max_abs_from_tree"] = float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - of_tree[block].astype(jnp.float32))))
            except Exception as e:  # the compiler's refusal of a block is a reading too
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            line["device"] = jax.devices()[0].device_kind
            print("[latent_decode]", json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
