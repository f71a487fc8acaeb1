# What the dispatch stamps cost when they are on: whole runs of a cell in
# pairs, the ledger's stamping switched ON and OFF by turns
# (`compile_guard.stamping`, a module switch: no environment variable reads
# it, so the OFF runs set it before `benchmark/run.py`'s `main`), a seed a
# run. One `[cost]` line a run: `generate_tokens_per_s`, `setup_s`,
# `listener_cost()`. Each run's output goes to chiprun_out/stamping_cost/.
# usage: chiprun --timeout 3500 -- bash scripts/chip_stamping_cost.sh <seed-prefix> <pairs> <cells...>
# rehearsal: JAX_PLATFORMS=cpu WINDOW=2 bash scripts/chip_stamping_cost.sh 49 1 _tiny.generate
PREFIX=$1; PAIRS=$2; shift 2
OUT=$PWD/chiprun_out/stamping_cost; mkdir -p $OUT
n=0
for cell in "$@"; do n=$((n+1))
  for pair in $(seq 1 $PAIRS); do
    # on, off, off, on: neither side is always the one that runs first
    if [ $((pair % 2)) = 1 ]; then order="True False"; else order="False True"; fi
    for on in $order; do
      seed=${PREFIX}00${n}${pair}$([ $on = True ] && echo 1 || echo 0)
      python3 - $cell $seed ${WINDOW:-40} $on > $OUT/$cell-$seed-$on.log 2>&1 <<'PY'
import json, sys
sys.argv[0] = "benchmark/run.py"
sys.path.insert(0, "benchmark"); sys.path.insert(0, ".")
import run  # benchmark/run.py: its clock starts here, as a run's does
from dalle_pytorch_tpu.utils import compile_guard
cell, seed, seconds, on = sys.argv[1:5]
compile_guard.stamping = on == "True"
rc = run.main(["--workload", cell, "--seed", seed, "--seconds", seconds, "--trace", "0"])
print("[listener] " + json.dumps(compile_guard.listener_cost()))
sys.exit(rc)
PY
      echo "rc=$? $(python3 - $OUT/$cell-$seed-$on.log $cell $seed $on <<'PY'
import json, sys
path, cell, seed, on = sys.argv[1:5]
line = listener = None
for l in open(path, errors="replace"):
    if l.startswith("{"): line = json.loads(l)
    if l.startswith("[listener] "): listener = json.loads(l[11:])
if line is None:
    print("[cost] " + json.dumps({"cell": cell, "seed": seed, "stamping": on, "failed": True}))
else:
    m = {k: v["value"] for k, v in line["metrics"].items()}
    print("[cost] " + json.dumps({"cell": cell, "seed": int(seed), "stamping": on == "True",
          "correct": line["correct"], **m, "listener": listener}))
PY
)"
    done
  done
done
