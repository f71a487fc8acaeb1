# The convolved-latent generation cell on the chip from a checkout of the
# committed files alone, in one call: one traced run (it has to read `correct:
# true`, or the call ends there), then whole untraced runs with a seed each.
# Before the call, here:
#   rm -rf .archive_check && mkdir -p .archive_check/tree && git archive $(git write-tree) | tar -x -C .archive_check/tree
# usage: chiprun --timeout 3000 -- bash benchmark/tests/chip_sets_zaya.sh [cell] [seconds] [tree] [seeds]
cell=${1:-zaya1.decode.8k}; seconds=${2:-40}; tree=${3:-.archive_check/tree}
seeds=${4:-"47101 2147483947 3999999947"}
out=$PWD/chiprun_out/sets_zaya; mkdir -p $out
keep="^\[setup\]\|^\[window\]\|^\[gaps\]\|^\[check\]\|^\[reference\]\|^\[scopes\]\|^\[compiles\]\|^{\|\"ok\": false\|Error\|error:"
run() { (cd $tree && timeout 1500 python3 benchmark/run.py --workload $cell --seed $1 --seconds $seconds --trace $2 > $out/$cell-$1-$2.log 2>&1; echo "exit $?"; grep "$keep" $out/$cell-$1-$2.log | cut -c1-$3); }
echo "== traced, seed 47000199"; run 47000199 1 3600
tail -n 1 $out/$cell-47000199-1.log | grep -q '"correct": true' || { echo "the traced run is not correct: stopping"; tail -n 30 $out/$cell-47000199-1.log | cut -c1-600; exit 1; }
for s in $seeds; do echo "== seed $s"; run $s 0 1200; done
cp $tree/benchmark/out/$cell-*.json $out/
