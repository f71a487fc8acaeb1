"""Run one traced cell and cut a small recorded pair out of it, for
`test_components.py`: a slice of the device trace, and the part of the
program's own table (`dalle_pytorch_tpu/obs/scopes.py`) that the slice needs.

    python3 benchmark/tests/record_named.py --workload flagship.train --seed 7 \
        --program step --out chiprun_out/rec24 [--offset 0] [--seconds 0.15]

writes `<out>/<cell>.trace.json.gz` (the plain lists that
`trace/reduce.py:reduce` takes; an operation's name is cut after its opcode,
which keeps the instruction name and the whole result shape) and
`<out>/<cell>.scopes.json` ({instruction: [opcode, shape, component,
phase]} for the instructions in the slice); copy both to
`tests/data/named/`. The cell runs in this process,
because the table is lowered from what the program remembered while it ran.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def head(name: str) -> str:
    """`%x = shape opcode(operands), attrs` -> `%x = shape opcode(`."""
    from dalle_pytorch_tpu.obs import scopes

    got = scopes.instruction(name)
    if got is None:
        return name[:120]
    return name[: name.index(got[1] + "(", name.index(" = ")) + len(got[1]) + 1]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--program", required=True, help="the program's name, less jit_")
    p.add_argument("--out", required=True)
    p.add_argument("--seconds", type=float, default=0.15)
    p.add_argument("--offset", type=float, default=0.0)
    p.add_argument("--run_seconds", type=float, default=40.0)
    args = p.parse_args()

    from benchmark import harness, run
    from benchmark.trace import reduce
    from dalle_pytorch_tpu.obs import scopes

    run.main(["--workload", args.workload, "--seed", str(args.seed), "--seconds",
              str(args.run_seconds), "--trace", "1", "--keep_trace", "1"])
    trace_dir = harness.OUT / f"trace-{args.workload}-{args.seed}"
    files = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    import jax

    ev = reduce.load_xplane(files[0], jax.devices()[0].platform)
    w0 = min(s[1] for s in ev["spans"] if s[0] == "window") + args.offset
    w1 = w0 + args.seconds
    keep = lambda rows: [[head(n), s, d] for n, s, d in rows if s + d > w0 and s < w1]
    cut = {
        "devices": [{"name": d["name"], "ops": keep(d["ops"]), "modules": keep(d["modules"])}
                    for d in ev["devices"]],
        "spans": [["window", w0, args.seconds]]
        + [list(s) for s in keep(ev["spans"]) if s[0] != "window"],
    }
    ops = {row[0]: {"seconds": row[2]} for d in cut["devices"] for row in d["ops"]}
    table = max(scopes.tables(args.program),
                key=lambda t: scopes.join(ops, t)["placed_s"])
    seen = {scopes.instruction(n)[0] for n in ops if scopes.instruction(n)}
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cell = args.workload
    with gzip.open(out / f"{cell}.trace.json.gz", "wt") as f:
        json.dump(cut, f)
    with open(out / f"{cell}.scopes.json", "w") as f:
        json.dump({k: v for k, v in table.items() if k in seen}, f)
    with gzip.open(out / f"{cell}.whole.scopes.json.gz", "wt") as f:
        json.dump(table, f)  # to look at by hand; not for tests/data
    print(cell, len(ops), "operations,", len(seen & set(table)), "in the table")
    return 0


if __name__ == "__main__":
    sys.exit(main())
