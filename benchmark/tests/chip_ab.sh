# Parent against change on one chip, in one call: every cell untraced, in the
# order parent, change, change, parent (each side's first run is cold: the
# cache's path is part of its key), then each cell traced on the PARENT with
# this tree's benchmark files laid over it (what the driver does), then one
# recorded pair for tests/data/named. Before the call, here:
#   mkdir -p .bench_archive/parent && git archive <parent> | tar -x -C .bench_archive/parent
#   cp -r benchmark/metrics/. .bench_archive/parent/benchmark/metrics/   (and new readers, BENCHMARK.json)
# usage: chiprun --timeout 3300 -- bash benchmark/tests/chip_ab.sh
run() { (cd $1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace $4 2>&1 | grep "^\[setup\]\|^\[window\]\|^\[scopes\]\|^\[compiles\]\|^{\|Error" | cut -c1-3000); }
P=.bench_archive/parent
for spec in "flagship.train 24000001" "paper64.generate 24000002"; do set -- $spec
  echo "== $1 parent cold";  run $P $1 ${2}01 0
  echo "== $1 change cold";  run .  $1 ${2}01 0
  echo "== $1 change warm";  run .  $1 ${2}02 0
  echo "== $1 parent warm";  run $P $1 ${2}02 0
done
echo "== flagship.train parent+new benchmark files traced"; run $P flagship.train 2400000103 1
echo "== paper64.generate parent+new benchmark files traced"; run $P paper64.generate 2400000203 1
echo "== record flagship.train backward slice"
python3 benchmark/tests/record_named.py --workload flagship.train --seed 2400000013 --program step --out chiprun_out/rec24 --seconds 0.13 --offset 0.45 2>&1 | grep "^\[setup\]\|^\[scopes\]\|^\[compiles\]\|^{\|Error\|operations," | cut -c1-3000
mkdir -p chiprun_out/callB; cp benchmark/out/*.json chiprun_out/callB/; for f in $P/benchmark/out/*.json; do cp $f chiprun_out/callB/parent-$(basename $f); done
