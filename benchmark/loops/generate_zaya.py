"""Generation cells of the convolved-latent family (`model_type: zaya`:
attention in a compressed latent behind two causal convolutions, top-1 experts
behind an MLP router that carries its state down the depth, a scaled residual,
a head that is the embedding): sessions that hold a document each in one
per-row decode cache (K/V of 2 heads a layer and the last position's tail)
take further turns through the cached sampler.

Set-up builds the model (`CausalLM.from_config`), its seeded weights
(`build_zaya.py`, stored as the configuration says) and ONE cache of
`job.sessions` rows of `job.document_tokens` + a turn's positions. The
documents are prefilled through the program's own `prefill_cached`,
`job.prefill_tokens` ids a dispatch, which also takes each layer's tail as the
snapshot a turn restores. Documents and weights are made from
`job.documents_seed` and `job.weights_seed` in every run.

A timed batch is one further turn of all sessions in ONE dispatch
(`generate_tokens_cached`): every layer's tail restored from its snapshot (a
device copy), its K/V index set back to the document's length (no copy),
`job.question_tokens` a row forced (drawn per batch and row from `--seed`: the
first is fed, the others must be emitted), then `job.answer_tokens` sampled, a
position a step. The workload file's `batches` is a cycle of sampler settings
(greedy and top-k, keys from `--seed`); a batch ends when its tokens, counts and
the logits of the first `check.rows` rows are on the host. The next batch is
dispatched BEFORE the last one's results are fetched, as long as it would start
inside the window. The rate is `sessions x answer_tokens` a batch over whole
CYCLES of the settings, over the time to the last counted batch's end, as
`loops/generate_nemotron_h.py` counts it.

`correct`, after the window: the program's first router chooses again for the
checked rows outside the timed program, the program's state is freed, and the
reference (`reference/zaya_ref.py`) runs its uncached forward over each checked
row's document, question and emitted tokens (teacher forcing), a layer's
weights at a time: the logits of every token step, which went through the
prefill, the snapshot, the restore and every cached step on their way, the
tokens greedy rows chose, and the first router's choices.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build_zaya, harness, traffic_lm
from benchmark.loops.generate_lm import WARM_INDEX, is_greedy, moe_counters, pick
from benchmark.loops.train_lm import flip_share
from benchmark.reference import zaya_ref


class Program:
    """Model, seeded weights, the sessions' cache and the sampler call."""

    def __init__(self, cfg: dict, job: dict):
        from dalle_pytorch_tpu.models.lm import CausalLM

        self.cfg, self.job = cfg, job
        self.d = zaya_ref.dims(cfg)
        self.sessions, self.doc = int(job["sessions"]), int(job["document_tokens"])
        self.question, self.answer = int(job["question_tokens"]), int(job["answer_tokens"])
        self.steps = self.question + self.answer
        self.mdl = CausalLM.from_config(cfg, self.doc + self.steps, **job.get("model", {}))
        self.tables = (traffic_lm.zipf_cdf(self.d["vocab"], job["tokens"]["exponent"]),
                       traffic_lm.rank_to_id(self.d["vocab"]))
        self.documents = self._tokens(int(job["documents_seed"]), 0, self.doc)
        self.variables = self.cache = self._choose = None
        self.prefill_counts, self.warmed = [], []

    def _tokens(self, seed: int, index: int, length: int) -> np.ndarray:
        return traffic_lm.token_batch(seed, index, self.sessions, length, self.job["tokens"],
                                      self.d["vocab"], self.tables)["tokens"]

    def questions(self, seed: int, i: int) -> np.ndarray:
        return self._tokens(seed, 1 + i, self.question)

    def setup(self) -> None:
        """Weights on the device, every session's document in the cache."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import prefill_cached

        self.variables = build_zaya.seeded_variables(
            self.cfg, self.mdl, int(self.job["weights_seed"]))
        self.cache = self.mdl.init_cache(self.sessions)
        per = max(1, int(self.job["prefill_tokens"]) // self.doc)
        for r in range(0, self.sessions, per):
            with harness.span("prefill"):
                self.cache, counts = prefill_cached(
                    self.mdl, self.variables, jnp.asarray(self.documents[r:r + per]),
                    self.cache, r)
            self.prefill_counts.append(counts)

    def start_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        """Dispatch the timed unit, a turn of every session, and return what
        `finish_batch` fetches: nothing here waits for the chip."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import generate_tokens_cached

        forced = self.questions(seed, i)
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), i % (2**31 - 1))
        cache, self.cache = self.cache, None  # donated
        with harness.span("sample"):
            toks, logits, counts, self.cache = generate_tokens_cached(
                self.mdl, self.variables, key, cache, jnp.asarray(forced), self.steps,
                filter_thres=float(setting["filter_thres"]),
                temperature=float(setting["temperature"]), logit_rows=rows, start=self.doc)
        return {"forced": forced, "toks": toks, "logits": logits["logits"], "counts": counts,
                "greedy": is_greedy(setting)}

    def finish_batch(self, batch: dict) -> dict:
        """The batch on the host: the questions, the tokens [B, steps] (entry
        j the j-th a row emitted after its first question token), the logits
        [steps, rows, V] of the first `rows` rows, and the sampler's counts."""
        import jax

        with harness.span("to_host"):
            return {**batch, "counts": jax.device_get(batch["counts"]),
                    "toks": np.asarray(batch["toks"]),
                    "logits": np.asarray(batch["logits"])[:, :, 0]}

    def one_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        return self.finish_batch(self.start_batch(seed, i, setting, rows))

    def sequences(self, batch: dict, rows) -> np.ndarray:
        """[rows, doc + steps]: what the checked rows' token steps were fed
        after their documents: the first question token, then what each step
        emitted but the last."""
        rows = np.asarray(list(rows))
        return np.concatenate([self.documents[rows], batch["forced"][rows, :1],
                               batch["toks"][rows, :self.steps - 1]], axis=1).astype(np.int32)

    def route_choices(self, seq: np.ndarray) -> np.ndarray:
        """[steps, 1]: the program's first router on one row's turn, through
        its uncached forward (outside the timed program)."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import CausalLM

        if self._choose is None:
            self._choose = jax.jit(lambda v, t: self.mdl.apply(
                v, t, 0, method=CausalLM.route_choices)[0, -self.steps:])
        return np.asarray(self._choose(self.variables, jnp.asarray(seq[None])))

    def free_cache(self) -> None:
        self.cache = None

    def free(self) -> None:
        self.variables = self.cache = None


def numbers(logits: np.ndarray, toks: np.ndarray, greedy: np.ndarray, choices: np.ndarray,
            sampled_from: int, want: dict) -> dict:
    """The numbers compared, of checked rows [R]: `logits` [R, steps, V] of
    whoever is judged, `toks` [R, steps] the token it put after each step's,
    `greedy` [R] which rows sampled greedily, `choices` [R, steps, 1] its first
    router's; `sampled_from`: the first step whose token was sampled, not
    forced; `want` the reference's over the same fed tokens. `logit_gap` is the
    WORST step: with seeded weights a top-1 router's near-tie flips under any
    rounding and moves that step's logits wholly, so its limit bounds a gross
    fault alone. The other numbers are ones that a precision lost anywhere on
    the path moves and one outlying step does not: `logit_gap_median`, and
    `greedy_gap`, the MEAN distance (logit units) of a greedy row's sampled
    token below the reference's best. As `loops/generate_nemotron_h.py`'s."""
    ref = want["logits"].astype(np.float64)
    diff = np.linalg.norm(logits - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    below = ref.max(-1) - np.take_along_axis(ref, toks[..., None].astype(np.int64), -1)[..., 0]
    below = below[greedy][:, sampled_from:]
    if not below.size:
        below = np.zeros(1)
    return {
        "logit_gap": float(diff.max()),
        "logit_gap_median": float(np.median(diff)),
        "greedy_gap": float(below.mean()),
        "route_flip_share": flip_share(choices, want["choices"]),
        # how the steps' gaps lie under the worst one (printed, not compared)
        "spread": {"logit_gap_p99": float(np.quantile(diff, 0.99)),
                   "greedy_gap_max": float(below.max()),
                   "greedy_tokens_off_best": float((below > 0).mean())},
    }


def shapes_of(prog: Program) -> dict:
    d = prog.d
    return dict(
        batch=prog.sessions, dim=d["dim"], depth=d["depth"], heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head_dim"], vocab=d["vocab"],
        expert_dim=d["expert_dim"], experts=d["experts"], router_dim=d["router_dim"],
        # grouped products a token step runs: gate, up and down a layer
        gmm_calls=3 * d["depth"],
        # the mean live length of a turn's token steps: step i attends doc + i + 1
        positions=prog.doc + (prog.steps + 1) / 2.0,
    )


def run(run: harness.Run) -> dict:
    prog = Program(run.config, run.workload["job"])
    prog.setup()
    values, served = measure(run, prog)
    compare(run, prog, served)
    return values


def measure(run: harness.Run, prog: Program):
    """The warm-up turns (once a process) and the window of one run, over a
    program that is set up: (the end-to-end values, the batches counted)."""
    import jax

    job = prog.job
    cycle = job["batches"]
    n_rows = int(run.workload["check"]["rows"])
    run.shapes.update(shapes_of(prog))
    # warm every sampler setting of the cycle once: one compiled program each
    settings = [s for k, s in enumerate(cycle) if s not in cycle[:k]]
    for k, s in enumerate(settings):
        if s not in prog.warmed:
            prog.one_batch(run.seed, WARM_INDEX + k, s, n_rows)
            prog.warmed.append(s)

    tracer = harness.Tracer(run)
    plan = run.workload.get("trace", {})
    t_open = run.window_opens()
    timer = None
    if run.trace:
        timer = tracer.in_background(float(plan["after_s"]), float(plan["seconds"]))
    until = run.seconds if not run.trace else float(plan["after_s"]) + float(plan["seconds"])
    done_at, served, bad = [], [], 0
    start = lambda i: prog.start_batch(run.seed, i, cycle[i % len(cycle)], n_rows)
    in_flight, started, period = start(0), 1, 0.0
    while in_flight is not None:
        # another, if it would start inside the window (it starts when the one
        # in flight ends); at least one whole cycle, whatever the window's length
        now = time.perf_counter() - t_open
        following = start(started) if now + period < until or started < len(cycle) else None
        started += following is not None
        batch = prog.finish_batch(in_flight)
        done_at.append(time.perf_counter() - t_open)
        period = done_at[-1] - (done_at[-2] if len(done_at) > 1 else 0.0)
        if not (np.isfinite(batch["logits"]).all() and batch["toks"].min() >= 0
                and batch["toks"].max() < prog.d["vocab"]
                and batch["counts"]["emitted"].min() >= prog.steps):
            bad += 1
        served.append(batch)
        in_flight = following
    if timer is not None:
        timer.join()
    run.window_closes()
    counted = len(served) // len(cycle) * len(cycle)
    run.attempted, run.failed = len(served), bad
    elapsed = done_at[counted - 1]
    values = {"generate_tokens_per_s": counted * prog.sessions * prog.answer / elapsed}
    counts = [b["counts"] for b in served[:counted]]
    prefill = jax.device_get(prog.prefill_counts)
    counters = moe_counters(counts, prog.steps)
    counters["moe_dropped"] += float(sum(np.sum(c["moe_dropped"]) for c in prefill))
    last = served[-1]["counts"]
    counters.update({k: float(last[k])
                     for k in ("state_bytes", "kv_bytes", "state_restored_bytes")})
    run.counters.update(batches=len(served), batches_counted=counted, **counters)
    run.shapes.update(moe_touched=counters["experts_touched"],
                      moe_rows=counters["moe_rows_mean"])
    run.record.update(batch_done_at=done_at,
                      prefill_rows_max=float(max(np.max(c["moe_rows"]) for c in prefill)))
    # the experts with a row, layer by layer (a step's mean): a seeded router that
    # sends every row one way shows here, and nowhere in the rate's own name
    by_layer = np.mean([c["moe_touched"] for c in counts], axis=0) / prog.steps
    harness.say("window", batches=len(served), counted=counted, elapsed_s=elapsed, **counters,
                prefill_rows_max=run.record["prefill_rows_max"],
                experts_touched_by_layer=[round(float(t), 2) for t in by_layer], **values)
    run.check("bad_batches", bad, run.limit("bad_batches"))
    run.check("moe_dropped", counters["moe_dropped"], run.limit("moe_dropped"))
    return values, served[:counted]


def judged(prog: Program, picked: list, n_rows: int) -> tuple:
    """(logits [R, steps, V], tokens [R, steps], greedy [R], choices [R,
    steps, 1]) of the picked batches' checked rows, stacked as `numbers` takes
    them (batch-major: every batch's rows in row order)."""
    return (np.concatenate([p["logits"].transpose(1, 0, 2) for p in picked]),
            np.concatenate([p["toks"][:n_rows, :prog.steps] for p in picked]),
            np.repeat([p["greedy"] for p in picked], n_rows),
            np.concatenate([p["choices"] for p in picked]))


def reference(prog: Program, picked: list, quant=None) -> dict:
    """The reference's forward over the checked rows of the `picked` batches,
    all rows in one pass over the layers."""
    seqs = np.concatenate([p["seqs"] for p in picked])
    return zaya_ref.forward(prog.cfg, int(prog.job["weights_seed"]), seqs, start=prog.doc,
                            quant=quant)


def prepare(prog: Program, picked: list, n_rows: int) -> None:
    """What the comparison needs of the program beyond the batch itself: the
    checked rows' sequences and its first router's choices on their turns."""
    for p in picked:
        p["seqs"] = prog.sequences(p, range(n_rows))
        p["choices"] = np.stack([prog.route_choices(s) for s in p["seqs"]])


def compare(run: harness.Run, prog: Program, served: list) -> None:
    """The program's first router chooses again for the checked rows of one
    greedy and one sampled batch; then its state is freed and the reference
    judges them, each number against a limit of its own (workload file;
    PERF.md gives the readings each was set from)."""
    t = time.perf_counter()
    n_rows = int(run.workload["check"]["rows"])
    picked = pick(run.seed, served)
    if not any(p["greedy"] for p in picked):
        run.check("greedy_rows_served", 0, 0, ok=False)
        return
    prog.free_cache()
    prepare(prog, picked, n_rows)
    prog.free()
    got = numbers(*judged(prog, picked, n_rows), prog.question - 1, reference(prog, picked))
    harness.say("gaps", **got.pop("spread"))
    for name, value in got.items():
        run.check(name, value, run.limit(name))
    harness.say("reference", seconds=time.perf_counter() - t, rows=len(picked) * n_rows,
                memory_peak_after_reference=run.memory_peak())


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: one set-up, then per seed one greedy and
    one sampled turn through the sampler (the cell's own size); the program
    is freed, and per seed the reference gives the program's numbers and, for
    the first `n_control` seeds, those of the `control`: the reference in the
    precision `check.control` names, put in the program's place and judged as
    the program is (its logits, the tokens it would pick greedily, its
    router's choices)."""
    job = workload["job"]
    n_rows = int(workload["check"]["rows"])
    prog = Program(cfg, job)
    prog.setup()
    settings = [next(s for s in job["batches"] if is_greedy(s)),
                next(s for s in job["batches"] if not is_greedy(s))]
    kept = [(seed, [prog.one_batch(seed, k, s, n_rows) for k, s in enumerate(settings)])
            for seed in seeds]
    prog.free_cache()
    for _, picked in kept:
        prepare(prog, picked, n_rows)
    prog.free()
    for k, (seed, picked) in enumerate(kept):
        want = reference(prog, picked)
        theirs = judged(prog, picked, n_rows)
        got = numbers(*theirs, prog.question - 1, want)
        counts = [p["counts"] for p in picked]
        row = {"seed": seed, **moe_counters(counts, prog.steps), **got.pop("spread"),
               "program": got}
        if k < n_control:
            low = reference(prog, picked, quant=workload["check"]["control"])
            row["control"] = numbers(low["logits"], low["logits"].argmax(-1), theirs[2],
                                     low["choices"], prog.question - 1, want)
            row["control_spread"] = row["control"].pop("spread")
        yield row
