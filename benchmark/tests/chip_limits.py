"""Read, on the chip, the numbers that each limit of `correct` is set from.

    python3 benchmark/tests/chip_limits.py --workload flagship.train \
        --seeds 101,102,... --control 3

For every seed: the program's numbers against the reference at the cell's own
size (what a sound run gives). For the first `--control` seeds also the
control's: the reference put in the program's place and computed in int8, the
precision below the configurations' bf16. One process reads all seeds, so
set-up is paid once. The lines and `benchmark/out/limits-<cell>.json` are what
PERF.md quotes; a limit goes above the sound runs' largest and below the
control's smallest. Never run by the benchmark itself.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    args = p.parse_args()

    from benchmark import harness

    harness.use_checkout_cache()
    workload = harness.load("workloads", args.workload)
    config = harness.load("configs", workload["config"])
    loop = importlib.import_module(f"benchmark.loops.{workload['kind']}")
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for row in loop.readings(workload, config, seeds, args.control):
        harness.say("reading", **row)
        rows.append(row)
    names = sorted({k for r in rows for k in r["program"]})
    summary = {}
    for n in names:
        sound = [r["program"][n] for r in rows]
        control = [r["control"][n] for r in rows if r.get("control")]
        summary[n] = {"sound_max": max(sound), "control_min": min(control) if control else None,
                      "sound": sound, "control": control}
    harness.say("summary", **summary)
    harness.OUT.mkdir(parents=True, exist_ok=True)
    with open(harness.OUT / f"limits-{args.workload}.json", "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
