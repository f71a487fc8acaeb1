# Where set-up goes, from the program's compile ledger: every cell traced
# twice in one call, first in a checkout (the cache's directory is new) and
# warm, then one cell traced on the PARENT with this tree's benchmark files
# laid over it (what the driver does: the ledger's metrics must be absent
# there and nothing may raise). The `[compile_ledger]` line of each run, whole,
# goes to chiprun_out/ledger/. Before the call, here:
#   rm -rf benchmark/cache .bench_archive && mkdir -p .bench_archive/parent
#   git archive <parent> | tar -x -C .bench_archive/parent
#   cp BENCHMARK.json .bench_archive/parent/ && cp -r benchmark/. .bench_archive/parent/benchmark/
# usage: chiprun --timeout 3300 -- bash benchmark/tests/chip_ledger.sh [seed-prefix] [cells...]
# rehearsal: JAX_PLATFORMS=cpu bash benchmark/tests/chip_ledger.sh 35 _tiny.train
PREFIX=${1:-35}; shift
CELLS=${@:-flagship.train paper64.generate mellum2.train.8k pangu.decode.8k olmohybrid.decode.512}
OUT=$PWD/chiprun_out/ledger; rm -rf $OUT; mkdir -p $OUT
run() { (cd $1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace 1 > $OUT/$4.log 2>&1; echo "rc=$?"
  grep -a "^\[setup\]\|^\[compiles\]\|^\[trace\]\|Error" $OUT/$4.log | cut -c1-400
  tail -n 1 $OUT/$4.log | cut -c1-2500); }
n=0
for cell in $CELLS; do n=$((n+1))
  echo "== $cell first"; run . $cell ${PREFIX}000${n}01 $cell-first
  echo "== $cell warm";  run . $cell ${PREFIX}000${n}02 $cell-warm
done
set -- $CELLS
if [ -d .bench_archive/parent ]; then
  echo "== $1 parent + this tree's benchmark files"; run .bench_archive/parent $1 ${PREFIX}000901 $1-parent
fi
python3 - $OUT <<'PY'
# one `[ledger]` line a run: the window's programs, and everything the
# ledger's timeline holds BEFORE the window (the `[setup]` line's `compiles`
# backend events lie before it), by program
import glob, json, os, sys
SECONDS = ("trace_s", "lower_s", "compile_s", "load_s")
for path in sorted(glob.glob(sys.argv[1] + "/*.log")):
    said = {}
    for line in open(path, errors="replace"):
        for tag in ("setup", "compile_ledger"):
            if line.startswith(f"[{tag}] "):
                said.setdefault(tag, json.loads(line[len(tag) + 3:]))
    led, setup = said.get("compile_ledger"), said.get("setup")
    row = {"run": os.path.basename(path)[:-4], "setup": setup}
    if led:
        hit = [p for p in led["programs"] if p["matched"]]
        row.update(
            matched={p["program"]: {k: round(p[k], 3) for k in SECONDS}
                     | {k: p[k] for k in ("traces", "compiles", "cache_hits")} for p in hit},
            span_s=round(max(p["last_at"] for p in hit) - min(p["first_at"] for p in hit), 3) if hit else None,
            all_programs={k: round(v, 2) for k, v in led["all_programs"].items()},
            names=led["names"], records=led["records"], listener=led["listener"])
        ends = sorted(e for _, ph, _, e, _ in led["timeline"] if ph in ("compile", "load"))
        if setup and 0 < setup["compiles"] <= len(ends):
            opened, before = ends[setup["compiles"] - 1], {}
            for name, phase, start, end, nested in led["timeline"]:
                if start <= opened and not nested:
                    acc = before.setdefault(name, dict.fromkeys(("trace", "lower", "compile", "load"), 0.0))
                    acc[phase] += end - start
            total = lambda a: sum(a.values())
            row["before_window"] = {
                "first_event_to_last_compile_s": round(opened - min(s for _, _, s, _, _ in led["timeline"]), 2),
                "programs": [[n] + [round(a[k], 2) for k in ("trace", "lower", "compile", "load")]
                             for n, a in sorted(before.items(), key=lambda kv: -total(kv[1]))[:10]],
                "sums": {k: round(sum(a[k] for a in before.values()), 2)
                         for k in ("trace", "lower", "compile", "load")},
            }
    print("[ledger] " + json.dumps(row))
PY
