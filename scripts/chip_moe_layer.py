"""One routed layer alone on the chip, forward + backward, at the language-model
cell's shapes (32,768 tokens of 2,304 bf16, 16 of 64 experts held, 8 a token,
a 262,144-row buffer), with the router steered so that every token sends a
given number of its 8 assignments to held experts: `--held 2` is the cell's
fill (a quarter of the buffer), `--held 8` a full buffer.

    python scripts/chip_moe_layer.py --held 0,2,4,8 [--tree <checkout>] [--chunk <rows>]

`--tree` times another checkout's `models/moe.py` with the same inputs (the
parent's, unpacked beside this one). Prints a line a fill: milliseconds a call,
the rows present, and what the layer counted. `--tiny` rehearses on the CPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--held", default="2,8")
    p.add_argument("--tree", default=str(Path(__file__).resolve().parent.parent))
    p.add_argument("--calls", type=int, default=10)
    p.add_argument("--chunk", type=int, help="rows a loop step moves, for a sweep (models/moe.py: CHUNK_ROWS)")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, args.tree)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.models import moe

    if args.chunk:
        moe.CHUNK_ROWS = args.chunk
    tokens, dim, width, total, per_token, held = (
        (256, 128, 128, 8, 2, (0, 4)) if args.tiny else (32768, 2304, 896, 64, 8, (0, 16)))
    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    layer = moe.RoutedExperts(dim=dim, expert_dim=width, experts_total=total,
                              experts_per_token=per_token, experts_held=held,
                              buffer_rows=tokens * per_token)
    rng = np.random.default_rng(0)
    x0 = rng.normal(size=(tokens, dim)).astype(np.float32)
    params = jax.jit(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, dim))))()["params"]
    router = np.zeros((dim, total), np.float32)
    router[:total] = np.eye(total)  # a token's first features are its logits
    params = {**params, "router": jnp.asarray(router)}
    cotangent = jnp.asarray(rng.normal(size=(1, tokens, dim)), jnp.bfloat16)

    def loss(params, x):
        y, aux = layer.apply({"params": params}, x, mutable=["stats"])
        return jnp.sum(y.astype(jnp.float32) * cotangent.astype(jnp.float32)), aux["stats"]

    step = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))
    first, count = held
    for n_held in [int(h) for h in args.held.split(",")]:
        logits = rng.normal(size=(tokens, total)).astype(np.float32) * 0.1
        t = np.arange(tokens)
        for j in range(per_token):  # slot j: a held expert for the first n_held slots
            e = (first + (t + j) % count if j < n_held
                 else first + count + (t + j) % (total - count))
            logits[t, e] = 4.0 + j
        x = x0.copy()
        x[:, :total] = logits
        x = jnp.asarray(x, jnp.bfloat16)[None]
        (_, stats), grads = step(params, x)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = step(params, x)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        print("[layer]", json.dumps({
            "tree": args.tree, "chunk": getattr(moe, "CHUNK_ROWS", None),
            "held_per_token": n_held, "ms_a_call": ms,
            "device": jax.devices()[0].device_kind,
            **{k: int(np.sum(v)) for k, v in jax.device_get(stats).items() if k != "moe_load"},
            "finite": bool(all(np.isfinite(np.asarray(g, np.float32)).all()
                               for g in jax.tree.leaves(grads)))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
