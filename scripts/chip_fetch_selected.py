"""The FETCH of a sparse latent token step alone on the chip, at the shape of
one indexed layer of `deepseek32.decode.32k` (16 rows, 2,048 selected of
33,792 cached positions, a latent of 512 and a rotary key of 64 bf16), in the
forms ISSUE 41 asks to be priced before anything is built:

  (a) `two`:        the two `take_along_axis` calls over the two leaves
                    `latent` [B, L, 512] and `rope` [B, 64, L] (what
                    `ops/sparse_latent_decode.py:fetch_selected` ran until PR 41);
  (b) `rows576`,    ONE `take_along_axis` of a positions-major leaf [B, L, 576]
      `rows640`:    (or 640: five whole tiles of lanes), then the slice and the
                    small transpose that hand `decode_latent` its two operands;
  (c) `dma8/16/32`: a Pallas fetch that starts one DMA a selected position out
                    of a leaf [B, L, 4, 128] (a position one whole 1,024-byte
                    tile), 8, 16 or 32 copies in flight: the latent alone, the
                    price of a row a position by the DMA engine.

    python scripts/chip_fetch_selected.py [--forms two,rows576,rows640,dma8,dma16,dma32]

Prints a line a form: microseconds a call (the mean of `--calls` calls inside
ONE dispatch, each waiting on the one before, so that dispatch is not read as
device time), nanoseconds a selected position, the fetched bytes a call and
the share of 819 GB/s they move at, and whether the form returned the control's
numbers bit for bit. `--attend` puts `decode_latent` over the fetched
positions behind each fetch (the whole `mla_attend` of a layer's step).
`--tiny` rehearses on the CPU.
"""

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# rows, cached positions, selected, latent, rotary, query heads
SHAPE = (16, 33792, 2048, 512, 64, 128)
TINY = (2, 96, 16, 512, 64, 4)
DMA_BLOCK = 256  # selected positions a grid step of form (c) fetches


def dma_fetch(leaf, indices, in_flight: int, interpret: bool):
    """[B, k, 4, 128]: the positions `indices` [B, k] of `leaf` [B, L, 4, 128],
    one DMA each out of the leaf where it lies, `in_flight` of them started
    before the first is waited for."""
    import jax
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, k = indices.shape
    block = min(DMA_BLOCK, k)
    in_flight = min(in_flight, block)

    def kernel(idx, src, out, sems):
        row, base = pl.program_id(0), pl.program_id(1) * block
        copy = lambda j: pltpu.make_async_copy(
            src.at[row, idx[row, base + j]], out.at[0, j], sems.at[j % in_flight])
        for j in range(in_flight):
            copy(j).start()

        def step(j, _):
            copy(j).wait()

            @pl.when(j + in_flight < block)
            def _():
                copy(j + in_flight).start()
            return 0

        lax.fori_loop(0, block, step, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, k // block),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, block) + leaf.shape[2:], lambda r, j, idx: (r, j, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((in_flight,))]),
        out_shape=jax.ShapeDtypeStruct((b, k) + leaf.shape[2:], leaf.dtype),
        interpret=interpret, name=f"fetch_dma{in_flight}",
    )(indices, leaf)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--forms", default="two,rows576,rows640,dma8,dma16,dma32")
    p.add_argument("--calls", type=int, default=50)
    p.add_argument("--attend", action="store_true")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dalle_pytorch_tpu.ops.latent_decode import latent_decode_attention

    rows, leaf, k, rank, dr, heads = TINY if args.tiny else SHAPE
    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    dt = jnp.bfloat16
    calls = 2 if args.tiny else args.calls
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    latent = jax.random.normal(keys[0], (rows, leaf, rank), dt)
    rope = jax.random.normal(keys[1], (rows, leaf, dr), dt)
    q_c = jax.random.normal(keys[2], (rows, heads, rank), dt)
    q_r = jax.random.normal(keys[3], (rows, heads, dr), dt)
    rng = np.random.default_rng(0)
    # as the selection hands them over: distinct, ascending
    indices = jnp.asarray(np.stack(
        [np.sort(rng.choice(leaf, k, replace=False)) for _ in range(rows)]).astype(np.int32))
    counts = jnp.full((rows,), k, jnp.int32)
    take = lambda t, i, axis: jnp.take_along_axis(t, i, axis=axis, mode="promise_in_bounds")

    def two(held, idx):
        lat, rot = held
        return take(lat, idx[:, :, None], 1), take(rot, idx[:, None, :], 2)

    def merged(held, idx):
        got = take(held, idx[:, :, None], 1)
        return got[..., :rank], got[..., rank:rank + dr].transpose(0, 2, 1)

    def dma(held, idx, in_flight):
        got = dma_fetch(held, idx, in_flight, interpret=args.tiny)
        return got.reshape(rows, k, rank), None

    wide = lambda width: jnp.concatenate(
        [latent, rope, jnp.zeros((rows, leaf, width - rank - dr), dt)], axis=-1)
    forms = {
        "two": (two, lambda: (latent, rope.transpose(0, 2, 1))),
        "rows576": (merged, lambda: wide(576)),
        "rows640": (merged, lambda: wide(640)),
        **{f"dma{n}": (functools.partial(dma, in_flight=n),
                       lambda: latent.reshape(rows, leaf, rank // 128, 128))
           for n in (8, 16, 32)},
    }
    want = jax.jit(two)((latent, rope.transpose(0, 2, 1)), indices)

    def many(fetch):
        @jax.jit
        def run(held, idx):
            def call(_, carry):
                idx, _ = carry
                lat, rot = fetch(held, idx)
                rot = want[1] if rot is None else rot
                out = (latent_decode_attention(q_c, q_r, lat, rot, counts, sm_scale=0.1)
                       if args.attend else lat)
                # the next call waits on this one; what it waits for is never true
                return idx + (out[0, 0, 0] > jnp.inf).astype(idx.dtype), (lat, rot)
            return lax.fori_loop(0, calls, call, (idx, want))[1]
        return run

    for name in [f for f in args.forms.split(",") if f]:
        fetch, make = forms[name]
        held = jax.block_until_ready(make())
        run = many(fetch)
        jax.block_until_ready(run(held, indices))
        t0 = time.perf_counter()
        got = jax.block_until_ready(run(held, indices))
        seconds = (time.perf_counter() - t0) / calls
        nbytes = rows * k * (rank + (0 if name.startswith("dma") else dr)) * 2
        print("[fetch_selected]", json.dumps({
            "form": name, "with_attend": args.attend, "us_a_call": seconds * 1e6,
            "ns_a_position": seconds * 1e9 / (rows * k), "fetched_mb": nbytes / 1e6,
            "bandwidth_pct": 100 * nbytes / 819e9 / seconds,
            "same_bits": bool(all(np.array_equal(np.asarray(g), np.asarray(w))
                                  for g, w in zip(got, want))),
            "device": jax.devices()[0].device_kind}), flush=True)
        del held, run, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
