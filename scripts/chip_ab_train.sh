# Parent against change on one chip, in one call, for the train cells: each
# cell named traced once on either side (the change first: a side's first run
# in a checkout compiles, the cache's path being part of its key), then PAIRS
# pairs untraced in the order parent, change, change, parent, ..., a seed a
# pair; after each run its result line. Before the call, here:
#   rm -rf .bench_archive && mkdir -p .bench_archive/parent && git archive <parent> | tar -x -C .bench_archive/parent
#   (the change is the tree named by CHANGE, default the working tree; for the
#   committed files alone: mkdir -p .archive_check/tree && git archive $(git write-tree) | tar -x -C .archive_check/tree)
# usage: chiprun --timeout 3300 -- bash scripts/chip_ab_train.sh <seed prefix> "<cells>" <pairs> ["<traced cells>"]
# rehearse: JAX_PLATFORMS=cpu WINDOW=2 bash scripts/chip_ab_train.sh 9 _tiny.train 1 _tiny.train
prefix=$1; cells=$2; pairs=$3; traced=$4
P=.bench_archive/parent; C=${CHANGE:-.}
out=$PWD/chiprun_out/ab$prefix; mkdir -p $out
keep="^\[setup\]\|^\[scopes\]\|^{\|Error\|error:\|\"ok\": false"
run() {  # tree, label, cell, seed, trace
  echo "== $3 $2 seed $4 trace $5"
  (cd $1 && timeout 1500 python3 benchmark/run.py --workload $3 --seed $4 --seconds ${WINDOW:-40} --trace $5 > $out/$3-$2-$4-$5.log 2>&1; echo "exit $?")
  grep "$keep" $out/$3-$2-$4-$5.log | cut -c1-${6:-1200}
  cp $1/benchmark/out/$3-$4$([ $5 = 1 ] && echo -trace).json $out/$2-$3-$4-$5.json
}
n=0
for cell in $traced; do n=$((n + 1))
  run $C change $cell ${prefix}0${n}91 1 7000
  run $P parent $cell ${prefix}0${n}91 1 7000
done
n=0
for cell in $cells; do n=$((n + 1))
  for i in $(seq 1 $pairs); do
    if [ $((i % 2)) = 1 ]; then order="$P:parent $C:change"; else order="$C:change $P:parent"; fi
    for side in $order; do run ${side%%:*} ${side##*:} $cell ${prefix}0${n}$(printf %02d $i) 0; done
  done
done
