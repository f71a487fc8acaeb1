"""Generation cells of a model whose layers keep different kinds of state:
sessions that hold a prompt in a decode cache of recurrent (gated delta rule)
and K/V layers take further turns through the cached sampler.

Set-up builds the model (`CausalLM.from_config`), its seeded weights
(`build_olmo.py`, stored as the configuration says) and ONE cache of
`job.sessions` rows, and prefills every session's prompt through the
program's own `prefill_cached`, `job.prefill_rows` rows a dispatch, which
also takes each recurrent layer's snapshot. Prompts and weights are made from
`job.documents_seed` and `job.weights_seed` in every run.

A timed batch is one further turn of all sessions in ONE dispatch
(`generate_tokens_cached`): the recurrent layers' state restored from the
snapshot (a device copy), the K/V layers' index set back to the prompts'
length (no copy), `job.question_tokens` a row forced through the token step
(drawn per batch and row from `--seed`), then `job.answer_tokens` sampled.
The workload file's `batches` is a cycle of sampler settings (greedy and
top-k, keys from `--seed`); a batch ends when its tokens, the logits and the
first linear layer's state of the first `check.rows` rows are on the host.
The next batch is dispatched BEFORE the last one's results are fetched (the
cache goes from one dispatch to the next on the device; the logits of two
rows over 256 steps are 205 MB at 100,352 ids, half a second of the host's
time that the chip would otherwise wait out), as long as it would start
inside the window. The rate is counted over whole CYCLES of the settings,
over the time to the last counted batch's end: every run of the cell weighs
the settings alike, however many batches its window held.

`correct`, after the window: the program's state is freed, and the reference
(`reference/olmo_hybrid_ref.py`) runs its uncached forward over each checked
row's prompt, question and served tokens (teacher forcing), a layer's weights
at a time: the logits of the 256 token steps, the tokens greedy rows chose,
and the first linear layer's state after the turn's last step, which went
through prefill, snapshot, restore and every cached step on its way.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build_olmo, harness, traffic_lm
from benchmark.loops.generate_lm import WARM_INDEX, is_greedy, pick
from benchmark.reference import olmo_hybrid_ref


class Program:
    """Model, seeded weights, the sessions' cache and the sampler call."""

    def __init__(self, cfg: dict, job: dict):
        from dalle_pytorch_tpu.models.lm import CausalLM

        self.cfg, self.job = cfg, job
        self.d = olmo_hybrid_ref.dims(cfg)
        self.sessions, self.doc = int(job["sessions"]), int(job["document_tokens"])
        self.question, self.answer = int(job["question_tokens"]), int(job["answer_tokens"])
        self.steps = self.question + self.answer
        self.max_len = self.doc + self.steps
        self.mdl = CausalLM.from_config(cfg, self.max_len, **job.get("model", {}))
        self.first_linear = self.d["kinds"].index("linear")
        self.tables = (traffic_lm.zipf_cdf(self.d["vocab"], job["tokens"]["exponent"]),
                       traffic_lm.rank_to_id(self.d["vocab"]))
        self.documents = self._tokens(int(job["documents_seed"]), 0, self.doc)
        self.variables = self.cache = None
        self.warmed = []

    def _tokens(self, seed: int, index: int, length: int) -> np.ndarray:
        return traffic_lm.token_batch(seed, index, self.sessions, length, self.job["tokens"],
                                      self.d["vocab"], self.tables)["tokens"]

    def questions(self, seed: int, i: int) -> np.ndarray:
        return self._tokens(seed, 1 + i, self.question)

    def setup(self) -> None:
        """Weights on the device, every session's prompt in the cache."""
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import prefill_cached

        self.variables = build_olmo.seeded_variables(
            self.cfg, self.mdl, int(self.job["weights_seed"]))
        self.cache = self.mdl.init_cache(self.sessions)
        rows = int(self.job["prefill_rows"])
        for r in range(0, self.sessions, rows):
            with harness.span("prefill"):
                self.cache, _ = prefill_cached(
                    self.mdl, self.variables, jnp.asarray(self.documents[r:r + rows]),
                    self.cache, r)

    def start_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        """Dispatch the timed unit, a turn of every session, and return what
        `finish_batch` fetches: nothing here waits for the chip."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models import decode_cache
        from dalle_pytorch_tpu.models.lm import generate_tokens_cached

        forced = self.questions(seed, i)
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), i % (2**31 - 1))
        cache, self.cache = self.cache, None  # donated
        with harness.span("sample"):
            toks, logits, counts, self.cache = generate_tokens_cached(
                self.mdl, self.variables, key, cache, jnp.asarray(forced), self.steps,
                filter_thres=float(setting["filter_thres"]),
                temperature=float(setting["temperature"]), logit_rows=rows, start=self.doc)
            state = decode_cache.running_state(
                self.cache, self.first_linear, self.d["lin_heads"], rows)
        return {"forced": forced, "toks": toks, "logits": logits, "state": state,
                "counts": counts, "greedy": is_greedy(setting)}

    def finish_batch(self, batch: dict) -> dict:
        """The batch on the host: the questions, the tokens [B, steps], the
        logits [steps, rows, V] and the first linear layer's state [rows, H,
        d_k, d_v] of the first `rows` rows, and the sampler's counts."""
        import jax

        with harness.span("to_host"):
            return {**batch, "counts": jax.device_get(batch["counts"]),
                    **{k: np.asarray(batch[k]) for k in ("toks", "logits", "state")}}

    def one_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        return self.finish_batch(self.start_batch(seed, i, setting, rows))

    def sequences(self, forced: np.ndarray, toks: np.ndarray, rows) -> np.ndarray:
        """[len(rows), doc + steps]: what the checked rows' token steps were
        fed, after their prompts: the question, then each step's sample."""
        fed = np.concatenate([forced, toks[:, self.question - 1:-1]], axis=1)
        return np.concatenate([self.documents[rows], fed[rows]], axis=1).astype(np.int32)

    def free(self) -> None:
        self.variables = self.cache = None


def numbers(logits: np.ndarray, toks: np.ndarray, greedy: np.ndarray, state: np.ndarray,
            want: dict) -> dict:
    """The numbers compared, of checked rows [R]: `logits` [R, steps, V] of
    whoever is judged, `toks` [R, steps] what it sampled, `greedy` [R] which
    rows sampled greedily, `state` [R, H, d_k, d_v] its first linear layer's
    after the last step; `want` the reference's `forward` over the same fed
    tokens."""
    ref = want["logits"].astype(np.float64)
    diff = np.linalg.norm(logits - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    below = ref.max(-1) - np.take_along_axis(ref, toks[..., None].astype(np.int64), -1)[..., 0]
    flat = lambda s: s.reshape(len(s), -1).astype(np.float64)
    off = np.linalg.norm(flat(state) - flat(want["state"]), axis=-1)
    return {
        "logit_gap": float(diff.max()),
        "greedy_gap": float(below[greedy].max()) if greedy.any() else 0.0,
        "state_gap": float((off / np.linalg.norm(flat(want["state"]), axis=-1)).max()),
        # how the steps' gaps lie under the worst one (printed, not compared)
        "spread": {"logit_gap_median": float(np.median(diff)),
                   "logit_gap_p99": float(np.quantile(diff, 0.99)),
                   "greedy_tokens_off_best": float((below[greedy] > 0).mean())
                   if greedy.any() else 0.0},
    }


def shapes_of(prog: Program) -> dict:
    d = prog.d
    return dict(
        batch=prog.sessions, dim=d["dim"], heads=d["heads"], head_dim=d["head_dim"],
        lin_heads=d["lin_heads"], dk=d["dk"], dv=d["dv"], taps=d["taps"], ff=d["ff"],
        vocab=d["vocab"], kinds=list(d["kinds"]),
        linear_layers=sum(k == "linear" for k in d["kinds"]),
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
        if not (np.isfinite(batch["logits"]).all() and np.isfinite(batch["state"]).all()
                and batch["toks"].min() >= 0 and batch["toks"].max() < prog.d["vocab"]):
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
    counters = {k: float(served[-1]["counts"][k])
                for k in ("state_bytes", "kv_bytes", "state_restored_bytes")}
    run.counters.update(batches=len(served), batches_counted=counted, **counters)
    run.record.update(batch_done_at=done_at)
    harness.say("window", batches=len(served), counted=counted, elapsed_s=elapsed, **counters,
                **values)
    run.check("bad_batches", bad, run.limit("bad_batches"))
    return values, served[:counted]


def judged(picked: list, n_rows: int):
    """(logits [R, steps, V], tokens [R, steps], greedy [R], state) of the
    picked batches' checked rows, stacked as `numbers` takes them."""
    return (np.concatenate([p["logits"].transpose(1, 0, 2) for p in picked]),
            np.concatenate([p["toks"][:n_rows] for p in picked]),
            np.repeat([p["greedy"] for p in picked], n_rows),
            np.concatenate([p["state"] for p in picked]))


def reference(prog: Program, picked: list, n_rows: int, **control) -> dict:
    """The reference's forward over the checked rows of the `picked`
    batches, all rows in one pass over the layers."""
    seqs = np.concatenate(
        [prog.sequences(p["forced"], p["toks"], np.arange(n_rows)) for p in picked])
    return olmo_hybrid_ref.forward(prog.cfg, int(prog.job["weights_seed"]), seqs,
                                   start=prog.doc, **control)


def compare(run: harness.Run, prog: Program, served: list) -> None:
    """The program's state is freed and the reference judges the checked rows
    of one greedy and one sampled batch, each number against a limit of its
    own (workload file; PERF.md gives the readings each was set from)."""
    t = time.perf_counter()
    n_rows = int(run.workload["check"]["rows"])
    picked = pick(run.seed, served)
    if not any(p["greedy"] for p in picked):
        run.check("greedy_rows_served", 0, 0, ok=False)
        return
    prog.free()
    got = numbers(*judged(picked, n_rows), reference(prog, picked, n_rows))
    harness.say("gaps", **got.pop("spread"))
    for name, value in got.items():
        run.check(name, value, run.limit(name))
    harness.say("reference", seconds=time.perf_counter() - t, rows=len(picked) * n_rows,
                memory_peak_after_reference=run.memory_peak())


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: one set-up, then per seed one greedy and
    one sampled turn through the sampler (the cell's own size); the program
    is freed, and per seed the reference gives the program's numbers and, for
    the first `n_control` seeds, those of the two controls, each the reference
    put in the program's place and judged as the program is (its logits, the
    tokens it would pick greedily, its state): `control`, in the precision
    `check.control` names, and `control_state`, in float32 with the linear
    layers' state rounded to `check.control_state` after every token."""
    job = workload["job"]
    n_rows = int(workload["check"]["rows"])
    prog = Program(cfg, job)
    prog.setup()
    settings = [next(s for s in job["batches"] if is_greedy(s)),
                next(s for s in job["batches"] if not is_greedy(s))]
    kept = [(seed, [prog.one_batch(seed, k, s, n_rows) for k, s in enumerate(settings)])
            for seed in seeds]
    prog.free()
    for k, (seed, picked) in enumerate(kept):
        want = reference(prog, picked, n_rows)
        theirs = judged(picked, n_rows)
        got = numbers(*theirs, want)
        row = {"seed": seed, **got.pop("spread"), "program": got}
        if k < n_control:
            for name, how in (("control", {"quant": workload["check"]["control"]}),
                              ("control_state",
                               {"state_round": workload["check"]["control_state"]})):
                low = reference(prog, picked, n_rows, **how)
                row[name] = numbers(low["logits"], low["logits"].argmax(-1), theirs[2],
                                    low["state"], want)
                row[name].pop("spread")
        yield row
