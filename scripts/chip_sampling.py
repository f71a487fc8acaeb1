"""The k-th largest logit alone on the chip at the three shapes the cells'
samplers run: `lax.top_k` (what `top_k_filter` called until PR 36, which the
chip runs as a whole sort) beside `ops/sampling.py:kth_largest` (counting),
with k a Python int (`top_k_filter`) and with a traced k a row
(`top_k_filter_per_row`, the served samplers' form).

    python scripts/chip_sampling.py [--calls 200]

Prints a line a shape and form: microseconds a call (the mean of `--calls`
calls inside ONE dispatch, each waiting on the one before) and whether the
value is bit-equal to `lax.top_k`'s. `--tiny` rehearses on the CPU.
"""

import argparse
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--calls", type=int, default=200)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from dalle_pytorch_tpu.models.dalle import NEG_MASK_VALUE
    from dalle_pytorch_tpu.ops import sampling

    if not args.tiny and jax.default_backend() != "tpu":
        raise SystemExit(f"no chip here: {jax.default_backend()}")
    # (cell, rows, vocabulary, ids masked as `generate_images_cached` masks the text's)
    shapes = [("olmohybrid.decode.512", 48, 100352, 0), ("pangu.decode.8k", 64, 19200, 0),
              ("paper64.generate", 2, 41216, 33024)]
    if args.tiny:
        shapes = [(name, 2, v // 64, m // 64) for name, _, v, m in shapes]
    calls = 3 if args.tiny else args.calls

    def timed(select, x, ks):
        @jax.jit
        def many(x, ks):
            def call(_, carry):
                x, _ = carry
                kth = select(x, ks)
                # the next call waits on this one; what it waits for is never true
                bump = jnp.where(kth[:1, :1] > jnp.inf, 1.0, x[:1, :1])
                return lax.dynamic_update_slice(x, bump, (0, 0)), kth
            return lax.fori_loop(0, calls, call, (x, jnp.zeros((x.shape[0], 1), x.dtype)))[1]
        jax.block_until_ready(many(x, ks))
        t0 = time.perf_counter()
        got = jax.block_until_ready(many(x, ks))
        return (time.perf_counter() - t0) / calls, np.asarray(got)

    for cell, rows, vocab, masked in shapes:
        k = max(int((1.0 - 0.9) * vocab), 1)
        x = 4.0 * jax.random.normal(jax.random.PRNGKey(rows), (rows, vocab), jnp.float32)
        x = jnp.where(jnp.arange(vocab)[None] < masked, NEG_MASK_VALUE, x)
        forms = {"lax.top_k": lambda x, ks: lax.top_k(x, k)[0][..., -1:],
                 "count": lambda x, ks: sampling.kth_largest(x, k),
                 "count, a traced k a row": sampling.kth_largest}
        want = None
        for form, select in forms.items():
            seconds, got = timed(select, x, jnp.full((rows,), k, jnp.int32))
            want = got if want is None else want
            print("[kth]", json.dumps({
                "cell": cell, "rows": rows, "vocabulary": vocab, "k": k, "form": form,
                "us_a_call": seconds * 1e6, "logits_mb": rows * vocab * 4 / 1e6,
                "bit_equal": bool((got.view(np.uint32) == want.view(np.uint32)).all()),
                "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
