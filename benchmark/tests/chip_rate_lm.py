"""Read, on the chip, how far a language-model training cell's rate moves
with the seed: the set-up, the followed steps and the window of the cell's
own loop (`loops/train_lm.py:measure`), once per seed in ONE process, without
the reference that decides `correct` (two thirds of a whole run's time).

    python3 benchmark/tests/chip_rate_lm.py --workload mellum2.train.8k \
        --seeds 27101,27102,... --seconds 40

Prints a line per seed (rate, rows present, steps) and the spread of the rate
as the driver reckons it: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) over the median. Never run by the
benchmark itself; whole runs (`run.py`) are what `PERF.md` quotes a cell's
numbers from, this only adds seeds to the spread.
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    args = p.parse_args()

    from benchmark import harness
    from benchmark.loops import train_lm

    harness.use_checkout_cache()
    prog, rates = None, []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(args.workload, seed, args.seconds, False, time.perf_counter())
        run.claim_device()
        prog = prog or train_lm.Program(run.config, run.workload["job"])
        _, values = train_lm.measure(run, prog)
        rates.append(values["train_tokens_per_s"])
        harness.say("rate", seed=seed, ok=run.correct, **values,
                    **{k: run.counters[k] for k in ("steps", "moe_rows_mean", "moe_rows_max",
                                                    "expert_load_max_over_mean")})
    if len(rates) >= 2:
        harness.say("spread", runs=len(rates), median=statistics.median(rates),
                    low=min(rates), high=max(rates), iqr_over_median=spread(rates))
    return 0


if __name__ == "__main__":
    sys.exit(main())
