# Two sets of 6 runs of one cell, same seeds, after one traced run whose
# trace is cut down for tests/data. usage: chip_sets.sh <cell> <trace_seed> <record_seconds> [record_offset]
cell=$1
run() { python3 benchmark/run.py --workload $cell --seed $1 --seconds 40 --trace $2 $3 2>&1 | grep "^\[setup\]\|^\[window\]\|^\[check\]\|^\[greedy\]\|^\[reference\]\|^{\|Error" | cut -c1-2600; }
echo "== traced"; run $2 1 "--keep_trace 1"
f=$(ls benchmark/out/trace-$cell-$2/plugins/profile/*/*.xplane.pb | head -1); ls -la $f
mkdir -p chiprun_out/rec; python3 benchmark/tests/record_trace.py $f chiprun_out/rec/$cell.trace.json.gz --seconds $3 --offset ${4:-0}
python3 -c "
import json; r=json.load(open('benchmark/out/$cell-$2-trace.json')); json.dump(r['shapes'], open('chiprun_out/rec/$cell.shapes.json','w'))"
for set in A B; do for s in 1001 1002 1003 1004 1005 2147483659; do echo "== set $set seed $s"; run $s 0 | grep "^\[setup\]\|^{\|false"; done
mkdir -p chiprun_out/sets/$set; cp benchmark/out/$cell-*.json chiprun_out/sets/$set/; done
