"""Cut a small recorded trace out of a profiler file, for `test_reduce.py`.

    python3 benchmark/tests/record_trace.py <file.xplane.pb> <out.json.gz> \
        [--platform tpu] [--offset 0] [--seconds 0.15]

Keeps the events of `--seconds` of the `bench:window` span from `--offset` (device
operations, programs, host spans), with the operation names cut to 120
characters, as the plain lists `trace/reduce.py:reduce` takes.
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("xplane")
    p.add_argument("out")
    p.add_argument("--platform", default="tpu")
    p.add_argument("--seconds", type=float, default=0.15)
    p.add_argument("--offset", type=float, default=0.0)
    args = p.parse_args()

    from benchmark.trace import reduce

    ev = reduce.load_xplane(args.xplane, args.platform)
    w0 = min(s[1] for s in ev["spans"] if s[0] == "window") + args.offset
    w1 = w0 + args.seconds
    keep = lambda rows: [[n[:120], s, d] for n, s, d in rows if s + d > w0 and s < w1]
    out = {
        "devices": [{"name": d["name"], "ops": keep(d["ops"]), "modules": keep(d["modules"])}
                    for d in ev["devices"]],
        "spans": [["window", w0, args.seconds]]
        + [list(s) for s in keep(ev["spans"]) if s[0] != "window"],
    }
    with gzip.open(args.out, "wt") as f:
        json.dump(out, f)
    print(args.out, sum(len(d["ops"]) for d in out["devices"]), "operations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
