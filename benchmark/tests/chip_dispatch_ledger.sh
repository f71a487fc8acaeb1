# Where set-up goes, with the DEVICE's part of it: every generation cell traced
# twice in one call, first in a checkout (the cache's directory is new) and
# warm, then one cell traced on the PARENT with this tree's benchmark files
# laid over it (what the driver does: the three metrics of the dispatch ledger
# must be absent there and nothing may raise). Each run's whole output goes to
# chiprun_out/dispatch_ledger/<cell>-<first|warm|parent>.log. Before the call,
# here:
#   rm -rf benchmark/cache .bench_archive && mkdir -p .bench_archive/parent
#   git archive <parent> | tar -x -C .bench_archive/parent
#   cp BENCHMARK.json .bench_archive/parent/ && cp -r benchmark/. .bench_archive/parent/benchmark/
# usage: chiprun --timeout 3500 -- bash benchmark/tests/chip_dispatch_ledger.sh [seed-prefix] [cells...]
# rehearsal: JAX_PLATFORMS=cpu bash benchmark/tests/chip_dispatch_ledger.sh 49 _tiny.generate_lm _tiny.generate
# (TIMES="warm" traces each cell once, in a checkout whose cache an earlier call of the script filled)
PREFIX=${1:-49}; shift
CELLS=${@:-pangu.decode.8k deepseek32.decode.32k paper64.generate olmohybrid.decode.512 kexaone.decode.16k nemotron3.decode.8k zaya1.decode.8k}
TIMES=${TIMES:-first warm}
OUT=$PWD/chiprun_out/dispatch_ledger; mkdir -p $OUT
run() { (cd $1 && python3 benchmark/run.py --workload $2 --seed $3 --seconds 40 --trace 1 > $OUT/$4.log 2>&1; echo "rc=$?"
  grep -a "^\[setup\]\|^\[trace\]\|Error" $OUT/$4.log | cut -c1-300); }
n=0
for cell in $CELLS; do n=$((n+1)); k=0
  for time in $TIMES; do k=$((k+1))
    echo "== $cell $time"; run . $cell ${PREFIX}000${n}0${k} $cell-$time
  done
done
set -- $CELLS
if [ -d .bench_archive/parent ]; then
  echo "== $1 parent + this tree's benchmark files"; run .bench_archive/parent $1 ${PREFIX}000901 $1-parent
fi
python3 - $OUT <<'PY'
# a run, two lines. `[setup_account]`: `setup_s`, the compile ledger's three
# sums, the dispatch ledger's two, and the share of `setup_s` the five name
# together; per program its dispatches, device seconds and what has no stamp.
# `[stamps_vs_trace]`: the occupancy of the window batch that holds the traced
# second against steps a batch x `token_step_device_ms*` / (1 - idle share).
import glob, json, os, sys
COMPILE = ("program_trace_lower_s", "program_compile_s", "program_cache_load_s")
for path in sorted(glob.glob(sys.argv[1] + "/*.log")):
    said, last = {}, None
    for line in open(path, errors="replace"):
        for tag in ("setup", "dispatch_ledger", "trace", "window"):
            if line.startswith(f"[{tag}] "):
                said.setdefault(tag, json.loads(line[len(tag) + 3:]))
        if line.startswith("{"):
            last = line
    run = os.path.basename(path)[:-4]
    if last is None or "setup" not in said:
        print("[setup_account] " + json.dumps({"run": run, "failed": True}))
        continue
    result = json.loads(last)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    setup_s = said["setup"]["setup_s"]
    named = {k: v for k, v in metrics.items()
             if k.split(".")[0] in COMPILE or k in ("prefill_device_s", "warmup_device_s")}
    row = {"run": run, "correct": result["correct"], "setup_s": round(setup_s, 2),
           "named": {k: round(v, 3) for k, v in named.items()},
           "named_share_of_setup_s": round(sum(named.values()) / setup_s, 4),
           "sampled_batch_over_greedy": metrics.get("sampled_batch_over_greedy"),
           "compiles_in_window": metrics.get("compiles_in_window")}
    led = said.get("dispatch_ledger")
    if led:
        row.update(
            programs={p["program"]: {k: round(p[k], 3) if isinstance(p[k], float) else p[k]
                                     for k in ("dispatches", "instances", "dispatch_s", "device_s",
                                               "first_device_s", "gap_s", "gap_max_s", "unstamped")}
                      for p in led["programs"]},
            device_s=round(led["device_s"], 2), wall_s=round(led["wall_s"], 2),
            unstamped=led["unstamped"], listener=led["listener"],
            done_before_end=sum(1 for d in led["dispatches"] if d[5] is not None and d[5] < d[4]))
    print("[setup_account] " + json.dumps(row))
    step_ms = [v for k, v in metrics.items() if k.startswith("token_step_device_ms")]
    if led and step_ms and "trace" in said:
        # the window's batches are the samplers' dispatches that are nobody's first
        batches = [d for d in led["dispatches"] if not d[2] and d[6] is not None
                   and d[0] in ("lm_sample", "sample_cached", "sample_cached_batched")]
        idle = 1.0 - said["trace"]["busy_s"] / said["trace"]["window_s"]
        print("[stamps_vs_trace] " + json.dumps({
            "run": run, "token_step_device_ms": step_ms[0], "idle_share": idle,
            "step_ms_over_busy_share": step_ms[0] / (1.0 - idle),
            "window_batches_device_s": [round(d[6], 4) for d in batches],
            "window_batches_instance": [d[1] for d in batches],
            "window_batches_gap_s": [round(d[7], 4) for d in batches]}))
PY
