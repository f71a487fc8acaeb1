# A `benchmark` PR that renames, folds or re-points per-layer metrics and
# leaves the program alone: each cell named TRACED once on the parent and once
# on the change, the same seed a pair (each side compiles in its own checkout:
# the cache's path is part of its key, so both sides' runs are first runs and
# the compile ledger's seconds compare), then a table of every metric of both
# lines, paired by the rule the fold followed (a name's cell suffix became
# `.lm` or went). Cells in UNTRACED are then run whole once a side as well.
# Before the call, here:
#   rm -rf .bench_archive .archive_check && mkdir -p .bench_archive/parent .archive_check/tree
#   git archive <parent> | tar -x -C .bench_archive/parent
#   git add -A && git archive $(git write-tree) | tar -x -C .archive_check/tree
# usage: chiprun --timeout 3500 -- bash benchmark/tests/chip_pairs_traced.sh <seed prefix> "<cells>" ["<more traced seeds of the change: cell:seed ...>"] ["<untraced cells>"]
prefix=$1; cells=$2; more=$3; untraced=$4
P=.bench_archive/parent; C=${CHANGE:-.archive_check/tree}
out=$PWD/chiprun_out/pairs$prefix; mkdir -p $out
keep="^\[setup\]\|^\[window\]\|^\[scopes\]\|^\[check\]\|^\[compiles\]\|^{\|Error\|error:"
run() {  # tree, label, cell, seed, trace
  echo "== $3 $2 seed $4 trace $5"
  (cd $1 && timeout 1500 python3 benchmark/run.py --workload $3 --seed $4 --seconds ${WINDOW:-40} --trace $5 > $out/$3-$2-$4-$5.log 2>&1; echo "exit $?")
  grep "$keep" $out/$3-$2-$4-$5.log | cut -c1-${6:-1600}
  grep "^\[compile_ledger\]" $out/$3-$2-$4-$5.log | python3 -c '
import json, sys
for line in sys.stdin:
    rows = json.loads(line.split(" ", 1)[1])["programs"]
    print("[ledger] matched", [r["program"] for r in rows if r["matched"]], "of", len(rows))'
  rec=$1/benchmark/out/$3-$4$([ $5 = 1 ] && echo -trace).json
  [ -f $rec ] && cp $rec $out/$2-$(basename $rec)
}
n=0
for cell in $cells; do n=$((n + 1)); seed=${prefix}0${n}01
  run $P parent $cell $seed 1
  run $C change $cell $seed 1
  python3 - $out/parent-$cell-$seed-trace.json $out/change-$cell-$seed-trace.json $cell <<'PY'
import json, re, sys
parent, change = (json.load(open(p))["line"] for p in sys.argv[1:3])
old, new = parent["metrics"], change["metrics"]
CELL = r"\.(pangu|olmo|kexaone|deepseek32|nemotron3|train|mellum)$"


def was(name):
    """The parent's name of what the change calls `name` in this cell."""
    folded = [o for o in old if name in (re.sub(CELL, ".lm", o), re.sub(CELL, "", o))]
    return name if name in old else folded[0] if folded else None


say = lambda m, name: f"{m[name]['value']:.6g}" if name in m else "none"
print(f"[pairs] {sys.argv[3]}: correct {parent['correct']} | {change['correct']}")
print("| old name | new name | parent | change |\n| --- | --- | --- | --- |")
pairs = [(was(name), name) for name in sorted(new)]
pairs += [(o, None) for o in sorted(set(old) - {o for o, _ in pairs})]
for o, name in pairs:
    print(f"| {o or '(none)'} | {name or '(none)'} | {say(old, o)} | {say(new, name)} |")
PY
done
for spec in $more; do run $C change ${spec%%:*} ${spec##*:} 1; done
n=0
for cell in $untraced; do n=$((n + 1))
  run $P parent $cell ${prefix}1${n}01 0
  run $C change $cell ${prefix}1${n}01 0
done
