"""Find the knee of a served cell, once, on the chip.

    python3 benchmark/tests/chip_sweep.py --workload flagship.serve.steady \
        --seconds 30 --factors 0.8,1.0,1.2

One engine. First a closed loop of as many waiting clients as there are
slots, which gives the saturation rate; then an open loop (the cell's own
generator) at each factor of it, with the lead-in the workload file states.
Prints one line per phase; PERF.md quotes them and the two rates written into
the workload files. Never run by the benchmark itself.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def closed_loop(prog, job, d, clients, seconds, settle):
    """`clients` requests always outstanding; completions per second over the
    last `seconds` after `settle` seconds of run-in."""
    from benchmark import traffic
    from dalle_pytorch_tpu.serving.engine import SampleSpec

    ids = traffic.prompts(1, 1, 4096, job["prompt_length"], d["text_seq"], d["base_text_vocab"])
    n, live, done_at = 0, [], []
    t0 = time.monotonic()
    while time.monotonic() - t0 < settle + seconds:
        live = [r for r in live if not (r.future.done() and done_at.append(time.monotonic() - t0) is None)]
        while len(live) < clients:
            live.append(prog.batcher.submit([SampleSpec(ids[n % len(ids)], seed=n)],
                                            timeout_s=float(job["timeout_s"])))
            n += 1
        time.sleep(0.005)
    for r in live:
        r.future.result(timeout=float(job["timeout_s"]))
    counted = [t for t in done_at if t >= settle]
    return len(counted) / seconds, n


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--factors", default="0.8,1.0,1.2")
    p.add_argument("--rates", default="", help="absolute rates instead of factors")
    p.add_argument("--settle", type=float, default=None, help="closed-loop run-in, seconds")
    args = p.parse_args()

    from benchmark import harness

    harness.use_checkout_cache()
    from benchmark.loops import serve
    from benchmark.reference import dalle_ref

    workload = harness.load("workloads", args.workload)
    cfg = harness.load("configs", workload["config"])
    job = workload["job"]
    d = dalle_ref.dims(cfg)
    t = time.perf_counter()
    prog = serve.Program(cfg, job, 1)
    harness.say("engine", ready_s=time.perf_counter() - t, **harness.compile_tally())
    try:
        lead_in = float(job["lead_in_s"])
        stages0 = prog.stages()
        settle = lead_in if args.settle is None else args.settle
        cap, n = closed_loop(prog, job, d, int(job["slots"]), args.seconds, settle)
        chunk = prog.stages()["stage:chunk"], stages0.get("stage:chunk", (0.0, 0))
        harness.say("closed_loop", clients=int(job["slots"]), saturation_rps=cap, submitted=n,
                    chunk_wall_ms=1e3 * (chunk[0][0] - chunk[1][0]) / max(1, chunk[0][1] - chunk[1][1]))
        if cap <= 0 and not args.rates:
            raise SystemExit("no request completed in the closed loop: lengthen --settle")
        rates = ([float(r) for r in args.rates.split(",")] if args.rates
                 else [f * cap for f in map(float, args.factors.split(","))])
        for k, rate in enumerate(rates):
            j = dict(job, rate_rps=rate)
            plan = serve.schedule(100 + k, j, d, lead_in + args.seconds)
            stages0 = prog.stages()
            t0 = time.monotonic()
            sent = serve.offer(prog.batcher, plan, t0, lead_in, lambda: None,
                               float(job["timeout_s"]))
            time.sleep(max(0.0, t0 + lead_in + args.seconds - time.monotonic()))
            end = lead_in + args.seconds
            backlog = sum(1 for s in sent if s.done is None)
            done_in = sum(1 for s in sent if s.done is not None and lead_in <= s.done <= end)
            stages1 = prog.stages()
            for s in sent:
                s.req.future.result(timeout=float(job["timeout_s"]))
            sample = [s for s in sent if s.due >= lead_in]
            lat, failed = serve.latencies(sample, float(job["timeout_s"]))
            chunk = stages1["stage:chunk"], stages0["stage:chunk"]
            harness.say(
                "open_loop", rate_rps=rate, factor=rate / cap, offered=len(sample),
                failed=failed, latency_p50_s=float(np.median(lat)), latency_p90_s=serve.p90(lat),
                latency_max_s=float(lat.max()), unfinished_at_window_end=backlog,
                completed_in_window_rps=done_in / args.seconds,
                drain_s=time.monotonic() - t0 - end,
                chunk_wall_ms=1e3 * (chunk[0][0] - chunk[1][0]) / max(1, chunk[0][1] - chunk[1][1]),
                lateness_max_s=max(s.submitted - s.due for s in sent),
            )
    finally:
        prog.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
