"""Served cells: open-loop traffic into the continuous engine, in-process.

The generator is `bench_serving.py`'s `run_open_loop` around
`ContinuousEngine` + `ContinuousBatcher`, repaired in the three ways PERF.md
lists: the model has the configuration's own sizes; a request's latency is
timed from when it was DUE, not from when it was submitted, so a stall shows
as the wait it imposes on what comes after it; and the rate is a number in
the workload file, found once by a sweep, not calibrated inside the run.

A lead-in of uncounted traffic at the cell's rate fills the slots and counts
as set-up. Requests due inside the window are the sample. Below the knee
(`wait_for_sample`) every one of them is waited for and the latency tail is
judged; above it the queue grows by design, the window ends on time, and the
image tokens of the requests completed inside it are judged.

Every `greedy_every`-th request is greedy (`top_k` 1.0 keeps one logit): only
a greedy token can be held against the reference.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build, harness, traffic
from benchmark.reference import dalle_ref


class Program:
    """Model, seeded weights, dVAE, warmed engine and started batcher."""

    def __init__(self, cfg: dict, job: dict, seed: int):
        from dalle_pytorch_tpu.serving.batcher import ContinuousBatcher
        from dalle_pytorch_tpu.serving.engine import ContinuousEngine
        from dalle_pytorch_tpu.training.metrics import MetricsRegistry

        self.cfg, self.job, self.seed = cfg, job, seed
        self.d = dalle_ref.dims(cfg)
        mdl = build.model(cfg)
        variables = build.seeded_variables(cfg, mdl, seed)
        vae, vae_params = build.seeded_vae(cfg, seed)
        self.engine = ContinuousEngine(
            model=mdl, variables=variables, vae=vae, vae_params=vae_params,
            max_batch=int(job["slots"]), chunk_tokens=int(job["chunk_tokens"]),
            prefill_batch=int(job["prefill_batch"]), registry=MetricsRegistry(),
        )
        self.engine.warmup()
        self.batcher = ContinuousBatcher(
            self.engine, max_queue_rows=int(job["max_queue_rows"]),
            registry=self.engine.registry,
        )

    def stages(self) -> dict:
        """(sum, count) of the batcher's own histograms, for deltas."""
        reg = self.engine.registry
        out = {}
        fam = reg.get("dalle_serving_stage_seconds")
        if fam is not None:
            out.update({f"stage:{k}": (c.sum, c.count) for k, c in fam.items()})
        return out

    def close(self) -> None:
        self.batcher.shutdown(drain=False)


def schedule(seed: int, job: dict, d: dict, horizon: float) -> list:
    """[(due seconds from 0, prompt ids [T], request seed, greedy)] over
    `horizon` seconds at the cell's rate."""
    due = traffic.arrivals(seed, float(job["rate_rps"]), horizon)
    ids = traffic.prompts(seed, 0, len(due), job["prompt_length"], d["text_seq"],
                          d["base_text_vocab"])
    every = int(job["greedy_every"])
    return [(float(t), ids[i], (seed * 7919 + i) % (2**31 - 1), i % every == 0)
            for i, t in enumerate(due)]


class Sent:
    """One request as the generator saw it."""

    __slots__ = ("due", "submitted", "first", "done", "req", "greedy", "ids", "error")

    def __init__(self, due, ids, greedy):
        self.due, self.ids, self.greedy = due, ids, greedy
        self.submitted = self.first = self.done = self.req = self.error = None


def offer(batcher, plan, t_start, lead_in, on_open, timeout_s, clock=time.monotonic,
          sleep=time.sleep) -> list:
    """Submit each request of `plan` when it is due (never early), calling
    `on_open()` once as the lead-in ends. A request that the batcher refuses
    is kept with its error. Returns the `Sent` records; `done` is stamped by
    the batcher's thread as each future resolves."""
    from dalle_pytorch_tpu.serving.engine import SampleSpec

    sent, opened = [], False
    for due, ids, seed, greedy in plan:
        if not opened and due >= lead_in:
            delay = t_start + lead_in - clock()
            if delay > 0:
                sleep(delay)
            on_open()
            opened = True
        delay = t_start + due - clock()
        if delay > 0:
            sleep(delay)
        s = Sent(due, ids, greedy)
        s.submitted = clock() - t_start
        try:
            with harness.span("submit"):
                s.req = batcher.submit(
                    [SampleSpec(ids, seed=int(seed), temperature=1.0,
                                top_k=1.0 if greedy else 0.9)],
                    timeout_s=timeout_s,
                )
            s.req.future.add_done_callback(
                lambda s=s: setattr(s, "done", s.done or clock() - t_start)
            )
        except Exception as exc:  # refused: counts as failed, not as absent
            s.error = repr(exc)
        sent.append(s)
    if not opened:
        on_open()
    return sent


def latencies(sample, timeout_s: float):
    """Due-to-done seconds; a request that failed, was refused or never
    finished counts as slower than every other (the timeout)."""
    out, failed = [], 0
    for s in sample:
        ok = s.error is None and s.done is not None and s.req.future._exception is None
        failed += not ok
        out.append(s.done - s.due if ok else timeout_s)
    return np.asarray(out), failed


def p90(values) -> float:
    v = np.sort(np.asarray(values))
    return float(v[int(np.ceil(0.9 * len(v))) - 1])


def run(run: harness.Run) -> dict:
    job, cfg = run.workload["job"], run.config
    prog = Program(cfg, job, run.seed)
    d = prog.d
    lead_in, timeout_s = float(job["lead_in_s"]), float(job["timeout_s"])
    wait_for_sample = bool(job["wait_for_sample"])
    plan_t = run.workload.get("trace", {})
    seconds = run.seconds if not run.trace else float(plan_t["after_s"]) + float(plan_t["seconds"])
    run.shapes.update(slots=int(job["slots"]), chunk_tokens=int(job["chunk_tokens"]),
                      heads=d["heads"], dim_head=d["dim_head"], image_seq=d["image_seq"],
                      text_len=d["text_len"], depth=d["depth"])
    plan = schedule(run.seed, job, d, lead_in + seconds)
    tracer = harness.Tracer(run)
    marks = {}

    def on_open():
        run.window_opens()
        marks["stages"] = prog.stages()
        if run.trace:
            marks["timer"] = tracer.in_background(
                float(plan_t["after_s"]), float(plan_t["seconds"])
            )

    try:
        t_start = time.monotonic()
        sent = offer(prog.batcher, plan, t_start, lead_in, on_open, timeout_s)
        sample = [s for s in sent if s.due >= lead_in]
        end = lead_in + seconds
        remaining = t_start + end - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)
        stages_end = prog.stages()
        if wait_for_sample:
            deadline = time.monotonic() + timeout_s
            for s in sample:
                if s.req is not None:
                    try:
                        s.req.future.result(timeout=max(0.0, deadline - time.monotonic()))
                    except Exception as exc:
                        s.error = s.error or repr(exc)
        if "timer" in marks:
            marks["timer"].join()
        run.window_closes()
        for s in sent:  # when each request's first token was on the host
            if s.req is not None and s.req.first_token_at is not None:
                s.first = s.req.first_token_at - t_start
    finally:
        prog.close()

    # ---- what the window saw
    lat, failed = latencies(sample, timeout_s) if wait_for_sample else (None, 0)
    in_window = [s for s in sent if s.done is not None and lead_in <= s.done <= end
                 and s.error is None and s.req.future._exception is None]
    # refused at submit, or resolved with an exception before the window's end
    # (what is still queued then is cancelled by this loop's own shutdown)
    errors = [s for s in sent if s.error is not None
              or (s.done is not None and s.done <= end and s.req.future._exception is not None)]
    lateness = np.asarray([s.submitted - s.due for s in sent])
    tokens_per_s = len(in_window) * d["image_seq"] / seconds
    run.attempted = len(sample) if wait_for_sample else len(in_window) + len(errors)
    run.failed = failed if wait_for_sample else len(errors)
    both = {"serve_tokens_per_s": tokens_per_s}
    if wait_for_sample:
        both["serve_latency_p90_s"] = p90(lat)
    values = {k: both[k] for k in job["judged_on"]}
    delta = {k: (v[0] - marks["stages"].get(k, (0.0, 0))[0],
                 v[1] - marks["stages"].get(k, (0.0, 0))[1]) for k, v in stages_end.items()}
    mean = lambda k: delta[k][0] / delta[k][1] if k in delta and delta[k][1] else None
    if mean("stage:chunk") is not None:
        run.counters["chunk_wall_ms"] = 1e3 * mean("stage:chunk")
    if mean("stage:queue") is not None:
        run.counters["queue_wait_ms"] = 1e3 * (mean("stage:queue") + float(lateness.mean()))
    # slots in use over the window: each request holds one from about its
    # first token until it is done
    held = sum(
        max(0.0, min(s.done if s.done is not None else end, end) - max(s.first, lead_in))
        for s in sent if s.first is not None
    )
    rows_live = held / seconds
    run.counters["slot_occupancy_pct"] = 100.0 * rows_live / int(job["slots"])
    run.shapes["live_positions"] = rows_live * (d["text_len"] + d["image_seq"] / 2)
    run.shapes["batch"] = int(job["slots"])
    run.counters.update(generator_lateness_max_s=float(lateness.max()),
                        completed_in_window=len(in_window))
    run.record.update(
        due=[s.due for s in sent], submitted=[s.submitted for s in sent],
        done=[s.done for s in sent], errors=[s.error for s in errors][:20],
        stage_deltas={k: list(v) for k, v in delta.items()},
    )
    harness.say("window", offered=len(sample), completed_in_window=len(in_window),
                errors=len(errors), lateness_max_s=float(lateness.max()),
                latency_p50_s=float(np.median(lat)) if lat is not None else None,
                counters=run.counters, **both)
    run.check("generator_lateness_s", float(lateness.max()), run.limit("generator_lateness_s"))
    run.check("failed_requests", run.failed, 0)

    # ---- the program is freed; the reference reads greedy requests
    finished = [s for s in in_window if s.greedy]
    results = [(s.ids, np.asarray(s.req.future._result[0])[0], s) for s in finished]
    del prog, sent, sample, in_window
    t = time.perf_counter()
    if not results:
        run.check("greedy_requests_served", 0, 0, ok=False)
        return values
    # the longest prompt, and a sample of the others drawn from the seed
    order = sorted(range(len(results)), key=lambda i: -int((results[i][0] != 0).sum()))
    rest = order[1:]
    picked = [order[0]] + [rest[j] for j in traffic.sample(
        run.seed, "check_rows", len(rest), int(run.workload["check"]["rows"]) - 1)]
    text = np.stack([results[i][0] for i in picked])
    toks = np.stack([results[i][1] for i in picked])
    gaps = dalle_ref.greedy_gaps(cfg, dalle_ref.init_params(cfg, run.seed), text, toks)
    harness.say("greedy", rows=len(picked), tokens=int(gaps.size),
                nonzero=int((gaps > 0).sum()), p99=float(np.quantile(gaps, 0.99)))
    run.check("greedy_gap", float(gaps.max()), run.limit("greedy_gap"))
    harness.say("reference", seconds=time.perf_counter() - t,
                memory_peak_after_reference=run.memory_peak())
    return values


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: per seed a short window at the cell's own
    load, the widest gap over its greedy requests, and for the first
    `n_control` seeds the control's on the same prompts and tokens. One
    engine serves every seed's traffic; the weights are the first seed's."""
    job = dict(workload["job"], wait_for_sample=True)
    d = dalle_ref.dims(cfg)
    prog = Program(cfg, job, seeds[0])
    rows = []
    try:
        for seed in seeds:
            plan = schedule(seed, job, d, float(workload["check"]["reading_seconds"]))
            sent = offer(prog.batcher, plan, time.monotonic(), 0.0, lambda: None,
                         float(job["timeout_s"]))
            got = []
            for s in sent:
                if s.greedy and s.req is not None:
                    got.append((s.ids, np.asarray(s.req.future.result(
                        timeout=float(job["timeout_s"]))[0])[0]))
            for s in sent:
                if s.req is not None:
                    s.req.future.result(timeout=float(job["timeout_s"]))
            rows.append((seed, got[: int(workload["check"]["rows"])]))
    finally:
        prog.close()
    del prog
    params = dalle_ref.init_params(cfg, seeds[0])
    for k, (seed, got) in enumerate(rows):
        text, toks = np.stack([g[0] for g in got]), np.stack([g[1] for g in got])
        gaps = dalle_ref.greedy_gaps(cfg, params, text, toks)
        row = {"seed": seed, "program": {"greedy_gap": float(gaps.max())},
               "nonzero": int((gaps > 0).sum()), "tokens": int(gaps.size)}
        if k < n_control:
            low = dalle_ref.greedy_gaps(cfg, params, text, toks, workload["check"]["control"])
            row["control"] = {"greedy_gap": float(low.max())}
        yield row
