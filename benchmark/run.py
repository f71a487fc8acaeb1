"""Run one cell once: `python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`.

One process, the only one that touches JAX. The cell's workload file names
its configuration and the kind of loop that drives it; both are found by
name. The last line of the output is the result as one JSON object; what
else is worth a number goes on earlier lines and, in full, into
`benchmark/out/<cell>-<seed>.json`.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, to within the interpreter's own

import argparse
import importlib
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep_trace", type=int, choices=(0, 1), default=0,
                   help="leave the profiler's files under benchmark/out/ (for tests/record_trace.py)")
    args = p.parse_args(argv)

    from benchmark import harness

    run = harness.Run(args.workload, args.seed, args.seconds, bool(args.trace), T0)
    run.keep_trace = bool(args.keep_trace)
    harness.use_checkout_cache()
    run.claim_device()
    loop = importlib.import_module(f"benchmark.loops.{run.workload['kind']}")
    values = loop.run(run)
    line = harness.result_line(run, values)
    harness.write_record(run, line, values)
    harness.say("compiles", **harness.compile_tally())
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
