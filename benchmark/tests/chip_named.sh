# One traced run of each cell through record_named.py: the thirteen component
# metrics on the result line, the readers' cost on the [scopes] lines, and a
# recorded pair per cell under chiprun_out/rec24 for tests/data.
# usage: chip_named.sh <train_seed> <generate_seed>
keep() { grep "^\[setup\]\|^\[window\]\|^\[scopes\]\|^\[trace\]\|^\[compiles\]\|^{\|Error\|operations," | cut -c1-6000; }
mkdir -p chiprun_out/rec24
echo "== flagship.train"
python3 benchmark/tests/record_named.py --workload flagship.train --seed $1 --program step \
    --out chiprun_out/rec24 --seconds 0.13 2>&1 | keep
echo "== paper64.generate"
python3 benchmark/tests/record_named.py --workload paper64.generate --seed $2 --program sample_cached \
    --out chiprun_out/rec24 --seconds 0.07 2>&1 | keep
cp benchmark/out/flagship.train-$1-trace.json benchmark/out/paper64.generate-$2-trace.json chiprun_out/rec24/
