"""Language-model generation cells: sessions that hold a long document in
their decode cache take further turns through the cached sampler.

Set-up builds the model (`CausalLM.from_config`), its seeded weights
(`build_pangu.py`, stored as the configuration says) and ONE cache of
`job.sessions` rows, and prefills every session's document through the
program's own `prefill_cached`, `job.prefill_rows` rows a dispatch. Documents
and weights are made from `job.documents_seed` and `job.weights_seed` in
EVERY run: seeded weights route by sequence (PERF.md, PR 27), so which held
experts a step touches follows the documents, and a cell times one routing,
as a deployment has one checkpoint and its sessions.

A timed batch is one further turn of all sessions in ONE dispatch
(`generate_tokens_cached`): the cache's index set back to the documents'
length (no copy), a question of `job.question_tokens` a row forced through
the token step (drawn per batch and row from `--seed`), then
`job.answer_tokens` sampled. The workload file's `batches` is a cycle of
sampler settings (greedy and top-k, keys from `--seed`); a batch ends when
its tokens, the logits of the first `check.rows` rows and the routed layers'
counts are on the host. Batches run back to back; whole batches are counted,
over the time to the last one's end.

`correct`, after the window: the program's first routed layer chooses again
for the checked rows outside the timed program, the program's state is
freed, and the reference (`reference/pangu_ref.py`) runs its uncached
forward over each checked row's document, question and served tokens
(teacher forcing), a layer's weights at a time.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build_pangu, harness, traffic, traffic_lm
from benchmark.loops.train_lm import flip_share
from benchmark.reference import pangu_ref

WARM_INDEX = 1 << 30  # warm-up batches draw questions no window batch draws
COUNTS = ("moe_load", "moe_rows", "moe_dropped", "moe_touched")


def is_greedy(setting: dict) -> bool:
    return float(setting["filter_thres"]) >= 1.0


class Program:
    """Model, seeded weights, the sessions' cache and the sampler call."""

    def __init__(self, cfg: dict, job: dict):
        from dalle_pytorch_tpu.models.lm import CausalLM

        self.cfg, self.job = cfg, job
        self.d = pangu_ref.dims(cfg)
        self.sessions, self.doc = int(job["sessions"]), int(job["document_tokens"])
        self.question, self.answer = int(job["question_tokens"]), int(job["answer_tokens"])
        self.steps = self.question + self.answer
        self.max_len = self.doc + self.steps
        self.mdl = CausalLM.from_config(cfg, self.max_len, **job.get("model", {}))
        self.tables = (traffic_lm.zipf_cdf(self.d["vocab"], job["tokens"]["exponent"]),
                       traffic_lm.rank_to_id(self.d["vocab"]))
        self.documents = self._tokens(int(job["documents_seed"]), 0, self.doc)
        self.variables = self.cache = None
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

        self.variables = build_pangu.seeded_variables(
            self.cfg, self.mdl, int(self.job["weights_seed"]))
        self.cache = self.mdl.init_cache(self.sessions)
        rows = int(self.job["prefill_rows"])
        for r in range(0, self.sessions, rows):
            with harness.span("prefill"):
                self.cache, counts = prefill_cached(
                    self.mdl, self.variables, jnp.asarray(self.documents[r:r + rows]),
                    self.cache, r)
            self.prefill_counts.append(counts)

    def one_batch(self, seed: int, i: int, setting: dict, logit_rows: int):
        """The timed unit: a turn of every session. Returns (questions,
        tokens [B, steps], logits [steps, logit_rows, V], counts), on the host."""
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
                temperature=float(setting["temperature"]), logit_rows=logit_rows,
                start=self.doc)
        with harness.span("to_host"):
            return forced, np.asarray(toks), np.asarray(logits), jax.device_get(counts)

    def sequences(self, forced: np.ndarray, toks: np.ndarray, rows) -> np.ndarray:
        """[len(rows), doc + steps]: what the checked rows' token steps were
        fed, after their documents: the question, then each step's sample."""
        fed = np.concatenate([forced, toks[:, self.question - 1:-1]], axis=1)
        return np.concatenate([self.documents[rows], fed[rows]], axis=1).astype(np.int32)

    def route_choices(self, seqs: np.ndarray) -> np.ndarray:
        """[R, steps, k]: the program's first routed layer on the token
        steps' positions of `seqs`, through its uncached forward, a row at a
        time (outside the timed program)."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import CausalLM

        layer = self.d["kinds"].index("routed")
        choose = jax.jit(lambda v, t: self.mdl.apply(
            v, t, layer, method=CausalLM.route_choices)[:, self.doc:])
        return np.concatenate(
            [np.asarray(choose(self.variables, jnp.asarray(s[None]))) for s in seqs])

    def free_cache(self) -> None:
        self.cache = None

    def free(self) -> None:
        self.variables = self.cache = None


def moe_counters(counts: list, steps: int) -> dict:
    """From the turns' routing counts (each summed over `steps` token steps:
    `moe_load` [L, G], `moe_rows`, `moe_dropped`, `moe_touched` [L]): the
    counters the metric files read, per routed layer and step."""
    load = np.stack([c["moe_load"] for c in counts]).astype(np.float64)  # [turns, L, G]
    per = lambda name: np.stack([c[name] for c in counts]).astype(np.float64) / steps
    return {
        "experts_touched": float(per("moe_touched").mean()),
        "expert_load_max_over_mean": float(
            np.mean(load.max(-1) / np.maximum(load.mean(-1), 1e-9))),
        "moe_rows_mean": float(per("moe_rows").mean()),
        "moe_dropped": float(sum(np.sum(c["moe_dropped"]) for c in counts)),
    }


def numbers(logits: np.ndarray, toks: np.ndarray, greedy: np.ndarray, choices: np.ndarray,
            want: dict) -> dict:
    """The numbers compared, of checked rows [R]: `logits` [R, steps, V] and
    `choices` [R, steps, k] of whoever is judged, `toks` [R, steps] what it
    sampled, `greedy` [R] which rows sampled greedily; `want` the
    reference's `forward` over the same fed tokens."""
    ref = want["logits"].astype(np.float64)
    diff = np.linalg.norm(logits - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    below = ref.max(-1) - np.take_along_axis(ref, toks[..., None].astype(np.int64), -1)[..., 0]
    return {
        "logit_gap": float(diff.max()),
        "greedy_gap": float(below[greedy].max()) if greedy.any() else 0.0,
        "route_flip_share": flip_share(choices, want["choices"]),
        # how the steps' gaps lie under the worst one (printed, not compared)
        "spread": {"logit_gap_median": float(np.median(diff)),
                   "logit_gap_p99": float(np.quantile(diff, 0.99)),
                   "greedy_tokens_off_best": float((below[greedy] > 0).mean())
                   if greedy.any() else 0.0},
    }


def shapes_of(prog: Program) -> dict:
    d = prog.d
    return dict(
        batch=prog.sessions, heads=d["heads"], kv_rank=d["kv_rank"], rope=d["rope"],
        nope=d["nope"], v_dim=d["v_dim"], q_rank=d["q_rank"], dim=d["dim"], depth=d["depth"],
        vocab=d["vocab"], kinds=list(d["kinds"]), dense_dim=d["dense_dim"],
        expert_dim=d["expert_dim"], shared_dim=d["shared_dim"],
        experts_held=d["experts_held"], experts_total=d["experts_total"],
        per_token=d["per_token"],
        # the mean live length of a turn's token steps: step i attends doc + i + 1
        positions=prog.doc + (prog.steps + 1) / 2.0,
    )


def reference(prog: Program, picked: list, quant=None) -> dict:
    """The reference's forward over the checked rows of the `picked`
    batches, all rows in one pass over the layers."""
    seqs = np.concatenate([p["seqs"] for p in picked])
    return pangu_ref.forward(prog.cfg, int(prog.job["weights_seed"]), seqs, start=prog.doc,
                             quant=quant)


def run(run: harness.Run) -> dict:
    prog = Program(run.config, run.workload["job"])
    prog.setup()
    values, served = measure(run, prog)
    compare(run, prog, served)
    return values


def measure(run: harness.Run, prog: Program):
    """The warm-up turns (once a process) and the window of one run, over a
    program that is set up: (the end-to-end values, the batches served)."""
    import jax

    job = prog.job
    n_rows = int(run.workload["check"]["rows"])
    run.shapes.update(shapes_of(prog))
    # warm every sampler setting of the cycle once: one compiled program each
    settings = [s for k, s in enumerate(job["batches"]) if s not in job["batches"][:k]]
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
    done_at, served, counts, bad = [], [], [], 0
    i = 0
    # at least one batch of every sampler setting, whatever the window's length
    least = 0 if run.trace else len(settings)
    while time.perf_counter() - t_open < until or i < least:
        setting = job["batches"][i % len(job["batches"])]
        forced, toks, logits, c = prog.one_batch(run.seed, i, setting, n_rows)
        done_at.append(time.perf_counter() - t_open)
        if not (np.isfinite(logits).all() and toks.min() >= 0 and toks.max() < prog.d["vocab"]):
            bad += 1
        served.append({"forced": forced, "toks": toks, "logits": logits,
                       "greedy": is_greedy(setting)})
        counts.append(c)
        i += 1
    if timer is not None:
        timer.join()
    run.window_closes()
    run.attempted, run.failed = len(done_at), bad
    elapsed = done_at[-1]
    values = {"generate_tokens_per_s": len(done_at) * prog.sessions * prog.answer / elapsed}
    prefill = jax.device_get(prog.prefill_counts)
    counters = moe_counters(counts, prog.steps)
    counters["moe_dropped"] += float(sum(np.sum(c["moe_dropped"]) for c in prefill))
    run.counters.update(batches=len(done_at), **counters)
    run.shapes.update(moe_touched=counters["experts_touched"], moe_rows=counters["moe_rows_mean"])
    run.record.update(batch_done_at=done_at,
                      prefill_rows_max=float(max(np.max(c["moe_rows"]) for c in prefill)))
    harness.say("window", batches=len(done_at), elapsed_s=elapsed, **counters,
                prefill_rows_max=run.record["prefill_rows_max"], **values)
    run.check("bad_batches", bad, run.limit("bad_batches"))
    run.check("moe_dropped", counters["moe_dropped"], run.limit("moe_dropped"))
    return values, served


def pick(seed: int, served: list) -> list:
    """One greedy and one sampled batch of those served, by the seed."""
    picked = []
    for greedy in (True, False):
        pool = [b for b in served if b["greedy"] == greedy]
        if pool:
            picked.append(pool[int(traffic.sample(seed, f"check_batch_{greedy}",
                                                  len(pool), 1)[0])])
    return picked


def judged(picked: list, n_rows: int):
    """(logits [R, steps, V], tokens [R, steps], greedy [R], choices) of the
    picked batches' checked rows, stacked as `numbers` takes them."""
    rows = np.arange(n_rows)
    return (np.concatenate([p["logits"].transpose(1, 0, 2) for p in picked]),
            np.concatenate([p["toks"][rows] for p in picked]),
            np.repeat([p["greedy"] for p in picked], n_rows),
            np.concatenate([p["choices"] for p in picked]))


def compare(run: harness.Run, prog: Program, served: list) -> None:
    """The program's first routed layer chooses again for the checked rows of
    one greedy and one sampled batch; then its state is freed and the
    reference judges them, each number against a limit of its own (workload
    file; PERF.md gives the readings each was set from)."""
    t = time.perf_counter()
    n_rows = int(run.workload["check"]["rows"])
    picked = pick(run.seed, served)
    if not any(p["greedy"] for p in picked):
        run.check("greedy_rows_served", 0, 0, ok=False)
        return
    prog.free_cache()
    for p in picked:
        p["seqs"] = prog.sequences(p["forced"], p["toks"], np.arange(n_rows))
        p["choices"] = prog.route_choices(p["seqs"])
    prog.free()
    want = reference(prog, picked)
    got = numbers(*judged(picked, n_rows), want)
    harness.say("gaps", **got.pop("spread"))
    for name, value in got.items():
        run.check(name, value, run.limit(name))
    harness.say("reference", seconds=time.perf_counter() - t, rows=len(picked) * n_rows,
                memory_peak_after_reference=run.memory_peak())


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: one set-up, then per seed one greedy and
    one sampled turn through the sampler (the cell's own size); the program
    is freed, and per seed the reference gives the program's numbers and,
    for the first `n_control` seeds, the control's (the reference in the
    control's precision, judged as the program is: its logits, the tokens it
    would pick greedily, its router's choices)."""
    job = workload["job"]
    n_rows = int(workload["check"]["rows"])
    prog = Program(cfg, job)
    prog.setup()
    rows = np.arange(n_rows)
    settings = [next(s for s in job["batches"] if is_greedy(s)),
                next(s for s in job["batches"] if not is_greedy(s))]
    kept = []
    for seed in seeds:
        picked, counts = [], []
        for k, s in enumerate(settings):
            forced, toks, logits, c = prog.one_batch(seed, k, s, n_rows)
            picked.append({"forced": forced, "toks": toks, "logits": logits,
                           "greedy": is_greedy(s), "seqs": prog.sequences(forced, toks, rows)})
            counts.append(c)
        kept.append((seed, picked, counts))
    prog.free_cache()
    for _, picked, _ in kept:
        for p in picked:
            p["choices"] = prog.route_choices(p["seqs"])
    prog.free()
    for k, (seed, picked, counts) in enumerate(kept):
        want = reference(prog, picked)
        theirs = judged(picked, n_rows)
        got = numbers(*theirs, want)
        row = {"seed": seed, **moe_counters(counts, prog.steps), **got.pop("spread"),
               "program": got}
        if k < n_control:
            low = reference(prog, picked, quant=workload["check"]["control"])
            row["control"] = numbers(low["logits"], low["logits"].argmax(-1), theirs[2],
                                     low["choices"], want)
            row["control"].pop("spread")
        yield row
