"""Where the training numbers' gaps come from, for one seed on the chip.

Prints, per vector leaf family, the worst relative difference of the first
gradient against the float32 reference, for: the program (bf16), the
reference with bf16 rounding emulated (not a control, a yardstick for bf16
noise), and the controls. Also the reference's loss under `highest` and under
default matmul precision, to show that `highest` takes effect. Never run by
the benchmark.
"""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def per_leaf(got, want):
    from benchmark.loops import train

    return {
        name: [round(float(x), 4) for x in (rel if rel.size < 13 else
                                            np.r_[rel[:3], rel[rel.size // 2], rel[-2:]])]
        for name, rel in train.leaf_diffs(got, want).items()
    }


def main() -> int:
    from benchmark import harness

    harness.use_checkout_cache()
    import jax

    from benchmark.loops import train
    from benchmark.reference import dalle_ref

    cell, seed = sys.argv[1], int(sys.argv[2])
    kinds = sys.argv[3].split(",") if len(sys.argv) > 3 else ["bf16", "fp8"]
    workload = harness.load("workloads", cell)
    cfg = harness.load("configs", workload["config"])
    prog = train.Program(cfg, workload["job"])
    state, feed, rng = prog.begin(seed)
    try:
        got, state, rng = prog.follow(seed, state, feed, rng)
    finally:
        feed.close()
    del state, feed
    want = prog.reference(seed)
    harness.say("program", numbers={k: v[0] for k, v in train.numbers(got, want).items()},
                per_leaf=per_leaf(got["grad_small"], want["grad_small"]))
    for kind in kinds:
        low = prog.reference(seed, quant=kind)
        harness.say(kind, numbers={k: v[0] for k, v in train.numbers(low, want).items()},
                    per_leaf=per_leaf(low["grad_small"], want["grad_small"]))
    b = prog.host_batch(seed, 0)
    p = dalle_ref.init_params(cfg, seed)
    f = lambda: float(jax.jit(lambda q: dalle_ref.loss_fn(q, cfg, b["text"][:2], b["image_tokens"][:2]))(p))
    with jax.default_matmul_precision("highest"):
        hi = f()
    harness.say("precision", loss_highest=hi, loss_default=f())
    return 0


if __name__ == "__main__":
    sys.exit(main())
