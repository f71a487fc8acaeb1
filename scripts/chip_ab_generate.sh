# Parent against change on one chip, in one call, for the generation cells:
# every cell named untraced in the order parent, change, change, parent (each
# side's first run compiles: the cache's path is part of its key), a seed a
# pair; after each run its result line and the batches' walls in order (the
# cells alternate a greedy batch with a top-k 0.9 one, greedy first), from
# `batch_done_at` on the harness's record. Then each cell in TRACED once on
# the change. Before the call, here:
#   rm -rf .bench_archive && mkdir -p .bench_archive/parent && git archive <parent> | tar -x -C .bench_archive/parent
#   (the change is the tree named by CHANGE, default the working tree; for the
#   committed files alone: mkdir -p .archive_check/tree && git archive $(git write-tree) | tar -x -C .archive_check/tree)
# usage: chiprun --timeout 3300 -- bash scripts/chip_ab_generate.sh <seed prefix> "<cells>" ["<traced cells>"]
prefix=$1; cells=$2; traced=$3
P=.bench_archive/parent; C=${CHANGE:-.}
out=$PWD/chiprun_out/ab$prefix; mkdir -p $out
keep="^\[setup\]\|^\[window\]\|^\[scopes\]\|^\[compiles\]\|^{\|Error\|error:"
run() {  # tree, label, cell, seed, trace
  echo "== $3 $2 seed $4 trace $5"
  (cd $1 && timeout 1500 python3 benchmark/run.py --workload $3 --seed $4 --seconds ${WINDOW:-40} --trace $5 > $out/$3-$2-$4-$5.log 2>&1; echo "exit $?")
  grep "$keep" $out/$3-$2-$4-$5.log | cut -c1-${6:-1500}
  rec=$1/benchmark/out/$3-$4$([ $5 = 1 ] && echo -trace).json
  python3 - $rec <<'PY'
import json, sys
at = json.load(open(sys.argv[1])).get("batch_done_at") or []
print("[walls]", json.dumps([round(b - a, 4) for a, b in zip([0.0] + at, at)]))
PY
  cp $rec $out/$2-$(basename $rec)
}
n=0
for cell in $cells; do n=$((n + 1))
  run $P parent $cell ${prefix}0${n}01 0
  run $C change $cell ${prefix}0${n}01 0
  run $C change $cell ${prefix}0${n}02 0
  run $P parent $cell ${prefix}0${n}02 0
done
for cell in $traced; do n=$((n + 1)); run $C change $cell ${prefix}0${n}03 1 6000; done
