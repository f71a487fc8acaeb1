"""The generation cell's rate alone over many seeds, in one process: ONE
set-up (weights, the sessions' documents prefilled), then for each seed the
cell's own window (`loops/generate_lm.py:measure`: the questions and the
sampler's keys are the seed's), with no comparison after it. The spread of
`generate_tokens_per_s` over the seeds (the quartiles' distance over the
median) is what PERF.md quotes against half the metric's bound.

    chiprun -- python3 benchmark/tests/chip_rate_pangu.py --seeds 1,2,3,...
    JAX_PLATFORMS=cpu python3 benchmark/tests/chip_rate_pangu.py --workload _tiny.generate_lm --seconds 1 --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="pangu.decode.8k")
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seeds", required=True)
    args = p.parse_args()

    from benchmark import harness
    from benchmark.loops import generate_lm

    harness.use_checkout_cache()
    prog, rates = None, []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(args.workload, seed, args.seconds, False, time.perf_counter())
        run.claim_device()
        if prog is None:
            prog = generate_lm.Program(run.config, run.workload["job"])
            prog.setup()
        values, _ = generate_lm.measure(run, prog)
        rates.append(values["generate_tokens_per_s"])
        harness.say("rate", seed=seed, batches=run.attempted, ok=all(c["ok"] for c in run.checks),
                    experts_touched=run.counters["experts_touched"], **values)
    if len(rates) > 1:
        q1, _, q3 = statistics.quantiles(rates, n=4)
        harness.say("spread", n=len(rates), median=statistics.median(rates),
                    iqr_over_median=(q3 - q1) / statistics.median(rates),
                    range_over_median=(max(rates) - min(rates)) / statistics.median(rates))
    return 0


if __name__ == "__main__":
    sys.exit(main())
