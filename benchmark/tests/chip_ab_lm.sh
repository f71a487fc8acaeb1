# parent against change on one chip, one call: the two accepted cells untraced in
# the order parent, change, change, parent; then flagship.train traced on the
# PARENT with this tree's benchmark files laid over it (what the driver does)
run() { (cd $1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace $4 2>&1 | grep "^\[setup\]\|^\[window\]\|^\[scopes\]\|^{\|Error" | cut -c1-2500); }
P=.bench_archive/parent
for spec in "flagship.train 27000001" "paper64.generate 27000002"; do set -- $spec
  echo "== $1 parent cold";  run $P $1 ${2}01 0
  echo "== $1 change cold";  run .  $1 ${2}01 0
  echo "== $1 change warm";  run .  $1 ${2}02 0
  echo "== $1 parent warm";  run $P $1 ${2}02 0
done
echo "== flagship.train parent+new benchmark files traced"; run .bench_archive/parent_new flagship.train 2700000103 1
echo "== paper64.generate change traced"; run . paper64.generate 2700000203 1
