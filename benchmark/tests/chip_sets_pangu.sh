# The generation cell on the chip from a checkout of the committed files
# alone, in one call: one traced run (it has to read `correct: true`, or the
# call ends there), whole untraced runs with a seed each, then the rate alone
# over further seeds in one process (tests/chip_rate_pangu.py). Before the call, here:
#   rm -rf .archive_check && mkdir -p .archive_check/tree && git archive $(git write-tree) | tar -x -C .archive_check/tree
# usage: chiprun --timeout 3000 -- bash benchmark/tests/chip_sets_pangu.sh [cell] [seconds] [tree]
cell=${1:-pangu.decode.8k}; seconds=${2:-40}; tree=${3:-.archive_check/tree}
out=$PWD/chiprun_out/sets_pangu; mkdir -p $out
keep="^\[setup\]\|^\[window\]\|^\[gaps\]\|^\[reference\]\|^\[scopes\]\|^{\|\"ok\": false\|Error\|error:"
run() { (cd $tree && timeout 1200 python3 benchmark/run.py --workload $cell --seed $1 --seconds $seconds --trace $2 > $out/$cell-$1-$2.log 2>&1; echo "exit $?"; grep "$keep" $out/$cell-$1-$2.log | cut -c1-$3); }
echo "== traced, seed 31000199"; run 31000199 1 3600
tail -n 1 $out/$cell-31000199-1.log | grep -q '"correct": true' || { echo "the traced run is not correct: stopping"; tail -n 30 $out/$cell-31000199-1.log | cut -c1-600; exit 1; }
for s in 31101 2147483801 3999999941; do echo "== seed $s"; run $s 0 1200; done
cp $tree/benchmark/out/$cell-*.json $out/
echo "== the rate alone"
(cd $tree && timeout 1500 python3 benchmark/tests/chip_rate_pangu.py --workload $cell --seconds $seconds \
  --seeds 31201,31202,31203,31204,31205,31206,2147483903,3999999953 2>&1 | tee $out/$cell-rate.log | grep "^\[rate\]\|^\[spread\]\|Error" | cut -c1-500)
