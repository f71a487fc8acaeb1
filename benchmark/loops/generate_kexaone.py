"""Generation cells of a window-and-full model that drafts for itself:
sessions that hold a long document in a decode cache of window rings and full
K/V layers take further turns through the cached sampler's VERIFY steps, the
model's multi-token module drafting one token a row a step.

Set-up builds the model (`CausalLM.from_config`), its seeded weights
(`build_kexaone.py`, stored as the configuration says) and ONE cache of
`job.sessions` rows (full K/V layers of document + turn positions, rings of
window + draft slots with their snapshot beside them), and prefills every
session's document through the program's own `prefill_cached`,
`job.prefill_rows` rows a dispatch: the trunk over the document, the module
over all but its last position. Documents and weights are made from `job.documents_seed` and
`job.weights_seed` in every run.

A timed batch is one further turn of all sessions in ONE dispatch
(`generate_tokens_cached`): every row's position set back to the document's
length (the full layers by their index, the rings from their snapshot: 2 MB a
session), then `job.steps` verify steps; the first
`job.question_tokens` tokens of a row are forced (drawn per batch and row
from `--seed`: the first is fed, the others must be emitted), the rest
sampled. The workload file's `batches` is a cycle of sampler settings (greedy
and top-k, keys from `--seed`); a batch ends when its tokens, counts and the
logits of the first `check.rows` rows (both positions of every step, and the
module's) are on the host. The next batch is dispatched BEFORE the last one's
results are fetched, as long as it would start inside the window. The rate is
the tokens the steps EMITTED (forced and sampled, both of a step that kept
its draft) over whole CYCLES of the settings, over the time to the last
counted batch's end.

With seeded weights the module's draft and the trunk's pick agree about once
in the vocabulary's size: nearly every step emits one token, and the rate is
the LOWER bound of what the same device work gives a trained checkpoint
(`mtp_accept_rate`, `tokens_per_step` say so on the line).

`correct`, after the window: the program's first routed layer chooses again
for the checked rows outside the timed program, the program's state is
freed, and the reference (`reference/kexaone_ref.py`) runs its uncached
forward of trunk and module over each checked row's document, question and
emitted tokens (teacher forcing), a layer's weights at a time, with the draft
each step fed as a second stream beside it (`forward(drafts=)`): BOTH
positions of every timed step are compared, kept or not.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build_kexaone, harness, traffic_lm
from benchmark.loops.generate_lm import WARM_INDEX, is_greedy, moe_counters, pick
from benchmark.loops.train_lm import flip_share
from benchmark.reference import kexaone_ref


class Program:
    """Model, seeded weights, the sessions' cache and the sampler call."""

    def __init__(self, cfg: dict, job: dict):
        from dalle_pytorch_tpu.models.lm import CausalLM

        self.cfg, self.job = cfg, job
        self.d = kexaone_ref.dims(cfg)
        self.sessions, self.doc = int(job["sessions"]), int(job["document_tokens"])
        self.question, self.steps = int(job["question_tokens"]), int(job["steps"])
        self.per_step = 1 + self.d["drafts"]  # positions a verify step takes
        self.turn = self.per_step * self.steps  # positions a turn can add
        self.max_len = self.doc + self.turn
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

        self.variables = build_kexaone.seeded_variables(
            self.cfg, self.mdl, int(self.job["weights_seed"]))
        self.cache = self.mdl.init_cache(self.sessions)
        rows = int(self.job["prefill_rows"])
        for r in range(0, self.sessions, rows):
            with harness.span("prefill"):
                self.cache, counts = prefill_cached(
                    self.mdl, self.variables, jnp.asarray(self.documents[r:r + rows]),
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
        return {"forced": forced, "toks": toks, "logits": logits, "counts": counts,
                "greedy": is_greedy(setting)}

    def finish_batch(self, batch: dict) -> dict:
        """The batch on the host: the tokens [B, cap], the counts, and of the
        first `rows` rows every step's logits, draft logits, position, the
        draft it fed and whether that was kept."""
        import jax

        with harness.span("to_host"):
            return {**batch, "toks": np.asarray(batch["toks"]),
                    "counts": jax.device_get(batch["counts"]),
                    "logits": {k: np.asarray(v) for k, v in batch["logits"].items()}}

    def one_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        return self.finish_batch(self.start_batch(seed, i, setting, rows))

    def sequences(self, batch: dict, rows) -> np.ndarray:
        """[len(rows), doc + 1 + m]: the checked rows' documents, the first
        question token and what their steps emitted, cut to the fewest tokens
        `m` a checked row emitted (rows that kept more drafts emitted more)."""
        m = int(batch["counts"]["emitted"][rows].min())
        return np.concatenate([self.documents[rows], batch["forced"][rows, :1],
                               batch["toks"][rows, :m]], axis=1).astype(np.int32)

    def route_choices(self, seqs: np.ndarray) -> np.ndarray:
        """[R, n - doc, k]: the program's first routed layer on the positions
        from the document's end on, through its uncached forward, a row at a
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


def by_position(batch: dict, rows, doc: int, n: int) -> dict:
    """The checked rows' step outputs laid out by POSITION, entries for doc ..
    n - 1. `logits` [R, n - doc, V]: a position's logits from the FIRST place
    of the step that fed it as its committed token; `second`: from the second
    place of the step that stood at this position, whose draft, `drafted` [R,
    n - doc], took the next one (kept or not: the reference is given the
    draft); `draft` the module's (entry j: position doc - 1 + j, as the
    reference's); and `has_logits`, `has_second`, `has_draft` [R, n - doc]:
    which entries a step gave (no step stands at a position that an earlier
    step's kept draft took, and the module hands out its last position's
    alone)."""
    lg = batch["logits"]
    steps, r, _, vocab = lg["logits"].shape
    out = {name: np.zeros((r, n - doc, vocab), np.float32)
           for name in ("logits", "second", "draft")}
    out.update({name: np.zeros((r, n - doc), bool)
                for name in ("has_logits", "has_second", "has_draft")})
    out["drafted"] = np.zeros((r, n - doc), np.int32)
    for s in range(steps):
        for i in range(len(rows)):
            at, kept = int(lg["at"][s, i]) - doc, int(lg["accepted"][s, i])
            if at < n - doc:
                out["logits"][i, at], out["has_logits"][i, at] = lg["logits"][s, i, 0], True
                out["second"][i, at], out["has_second"][i, at] = lg["logits"][s, i, 1], True
                out["drafted"][i, at] = lg["drafted"][s, i]
            if at + kept + 1 < n - doc:  # module position at + kept, entry + 1
                out["draft"][i, at + kept + 1] = lg["draft"][s, i]
                out["has_draft"][i, at + kept + 1] = True
    return out


def numbers(got: dict, greedy: np.ndarray, sampled_from: int, want: dict) -> dict:
    """The numbers compared, of checked rows [R] over positions doc .. n - 1:
    `got` whoever is judged (`logits`, `second`, `draft` [R, P, V] with
    `has_logits`, `has_second`, `has_draft`; `picked` [R, P] the token it put
    at the NEXT position; `choices` [R, P, k]); `greedy` [R] which rows
    sampled greedily; `sampled_from`: the first entry whose next token was
    sampled, not forced; `want` the reference's `forward` over the same
    tokens and drafts. `logit_gap` and `draft_logit_gap` are the WORST place
    of any step (first or second) and the worst draft: a fault at one
    position shows there and nowhere else. The other numbers are ones that a
    precision lost anywhere on the timed path moves and one outlying position
    does not: `logit_gap_median` and `draft_logit_gap_median` over the same
    populations, `greedy_gap` the MEAN distance (logit units) of a greedy
    row's sampled token below the reference's best, `greedy_off_best_share`
    the share of such tokens that are not the best. The control reads 6 to 14
    times the program in these and 1.05 to 1.4 times in the worst cases,
    which wander by a third from seed to seed (PERF.md section 6, PR 37)."""
    gap = lambda a, ref: np.linalg.norm(a - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    ref = want["logits"].astype(np.float64)
    first = gap(got["logits"], ref)[got["has_logits"]]
    second = gap(got["second"], want["second"].astype(np.float64))[got["has_second"]]
    diff = np.concatenate([first, second])
    ddiff = gap(got["draft"], want["draft"].astype(np.float64))[got["has_draft"]]
    below = ref.max(-1) - np.take_along_axis(
        ref, got["picked"][..., None].astype(np.int64), -1)[..., 0]
    below = below[greedy][:, sampled_from:-1]  # the last entry's next token is not in `want`
    if not below.size:
        below = np.zeros(1)
    return {
        "logit_gap": float(diff.max()),
        "logit_gap_median": float(np.median(diff)),
        "draft_logit_gap": float(ddiff.max()),
        "draft_logit_gap_median": float(np.median(ddiff)),
        "greedy_gap": float(below.mean()),
        "greedy_off_best_share": float((below > 0).mean()),
        "route_flip_share": flip_share(got["choices"], want["choices"]),
        # how the gaps lie (printed, not compared)
        "spread": {"first_gap_max": float(first.max()), "second_gap_max": float(second.max()),
                   "second_gap_median": float(np.median(second)),
                   "logit_gap_p99": float(np.quantile(diff, 0.99)),
                   "draft_logit_gap_p99": float(np.quantile(ddiff, 0.99)),
                   "greedy_gap_max": float(below.max()),
                   "places_compared": int(diff.size), "drafts_compared": int(ddiff.size)},
    }


def shapes_of(prog: Program) -> dict:
    d = prog.d
    return dict(
        batch=prog.sessions, dim=d["dim"], heads=d["heads"], kv_heads=d["kv_heads"],
        head_dim=d["head_dim"], window=d["window"], vocab=d["vocab"], attn=list(d["attn"]),
        kinds=list(d["kinds"]), drafts=d["drafts"], dense_dim=d["dense_dim"],
        expert_dim=d["expert_dim"], shared_dim=d["shared_dim"],
        experts_total=d["experts_total"], per_token=d["per_token"],
        step_positions=prog.per_step,
        # grouped products a verify step runs: three a routed block, the module's too
        gmm_calls=3 * (sum(k == "routed" for k in d["kinds"]) + d["drafts"]),
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
        emitted = batch["counts"]["emitted"]
        tokens = batch["toks"][np.arange(batch["toks"].shape[1])[None] < emitted[:, None]]
        if not (all(np.isfinite(v).all() for v in batch["logits"].values())
                and tokens.min() >= 0 and tokens.max() < prog.d["vocab"]
                and emitted.min() >= prog.steps):
            bad += 1
        served.append(batch)
        in_flight = following
    if timer is not None:
        timer.join()
    run.window_closes()
    counted = len(served) // len(cycle) * len(cycle)
    run.attempted, run.failed = len(served), bad
    elapsed = done_at[counted - 1]
    counts = [b["counts"] for b in served[:counted]]
    emitted = float(sum(c["emitted"].sum() for c in counts))
    accepted = float(sum(c["accepted"].sum() for c in counts))
    row_steps = float(counted * prog.sessions * prog.steps)
    values = {"generate_tokens_per_s": emitted / elapsed}
    prefill = jax.device_get(prog.prefill_counts)
    counters = moe_counters(counts, prog.steps)
    counters["moe_dropped"] += float(sum(np.sum(c["moe_dropped"]) for c in prefill))
    last = served[-1]["counts"]
    counters.update(
        {"lm.accepted": accepted, "lm.verify_steps": float(counted * last["verify_steps"])},
        mtp_accept_rate=accepted / row_steps, tokens_per_step=emitted / row_steps,
        **{k: float(last[k]) for k in ("kv_bytes", "ring_slots", "ring_bytes")})
    run.counters.update(batches=len(served), batches_counted=counted, **counters)
    run.shapes.update(
        moe_touched=counters["experts_touched"], moe_rows=counters["moe_rows_mean"],
        tokens_per_step=counters["tokens_per_step"],
        # the mean live length of a turn's steps: step i of a row that keeps no
        # draft attends doc + i + 1 positions from its first place
        positions=prog.doc + (emitted / (counted * prog.sessions) + 1) / 2.0)
    run.record.update(batch_done_at=done_at,
                      prefill_rows_max=float(max(np.max(c["moe_rows"]) for c in prefill)))
    harness.say("window", batches=len(served), counted=counted, elapsed_s=elapsed, **counters,
                prefill_rows_max=run.record["prefill_rows_max"], **values)
    run.check("bad_batches", bad, run.limit("bad_batches"))
    run.check("moe_dropped", counters["moe_dropped"], run.limit("moe_dropped"))
    return values, served[:counted]


def judged(prog: Program, picked: list, n_rows: int) -> tuple:
    """(`got` as `numbers` takes it, greedy [R]) of the picked batches'
    checked rows; every batch cut to the same length n (`p["seqs"]`)."""
    n = min(p["seqs"].shape[1] for p in picked)
    rows = np.arange(n_rows)
    parts = [by_position(p, rows, prog.doc, n) for p in picked]
    got = {k: np.concatenate([part[k] for part in parts]) for k in parts[0]}
    # the token at position j + 1, for entry j (position doc + j)
    got["picked"] = np.concatenate(
        [np.concatenate([p["seqs"][:, prog.doc + 1:n], p["seqs"][:, n - 1:n]], 1)
         for p in picked])
    got["choices"] = np.concatenate([p["choices"][:, :n - prog.doc] for p in picked])
    return got, np.repeat([p["greedy"] for p in picked], n_rows), n


def reference(prog: Program, picked: list, n: int, drafted: np.ndarray, quant=None) -> dict:
    """The reference's forward over the checked rows of the `picked`
    batches, all rows in one pass over the layers, with the drafts the steps
    fed (`drafted` [R, n - doc]) as its second stream."""
    seqs = np.concatenate([p["seqs"][:, :n] for p in picked])
    return kexaone_ref.forward(prog.cfg, int(prog.job["weights_seed"]), seqs, start=prog.doc,
                               quant=quant, drafts=drafted)


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
        p["seqs"] = prog.sequences(p, np.arange(n_rows))
        p["choices"] = prog.route_choices(p["seqs"])
    prog.free()
    got, greedy, n = judged(prog, picked, n_rows)
    out = numbers(got, greedy, prog.question - 1, reference(prog, picked, n, got["drafted"]))
    harness.say("gaps", **out.pop("spread"))
    for name, value in out.items():
        run.check(name, value, run.limit(name))
    harness.say("reference", seconds=time.perf_counter() - t, rows=len(picked) * n_rows,
                memory_peak_after_reference=run.memory_peak())


def as_judged(low: dict, got: dict) -> dict:
    """The control's forward put in the program's place: its logits and the
    module's at every position, the tokens it would pick greedily, its
    router's choices."""
    every = np.ones(low["logits"].shape[:2], bool)
    return {"logits": low["logits"], "second": low["second"], "draft": low["draft"],
            "has_logits": every, "has_second": got["has_second"],
            "has_draft": got["has_draft"], "picked": low["logits"].argmax(-1),
            "choices": low["choices"]}


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: one set-up, then per seed one greedy and
    one sampled turn through the sampler (the cell's own size); the program
    is freed, and per seed the reference gives the program's numbers and,
    for the first `n_control` seeds, the control's (the reference in the
    control's precision, judged as the program is)."""
    job = workload["job"]
    n_rows = int(workload["check"]["rows"])
    prog = Program(cfg, job)
    prog.setup()
    rows = np.arange(n_rows)
    settings = [next(s for s in job["batches"] if is_greedy(s)),
                next(s for s in job["batches"] if not is_greedy(s))]
    kept = []
    for seed in seeds:
        picked = [prog.one_batch(seed, k, s, n_rows) for k, s in enumerate(settings)]
        for p in picked:
            p["seqs"] = prog.sequences(p, rows)
        kept.append((seed, picked))
    prog.free_cache()
    for _, picked in kept:
        for p in picked:
            p["choices"] = prog.route_choices(p["seqs"])
    prog.free()
    for k, (seed, picked) in enumerate(kept):
        got, greedy, n = judged(prog, picked, n_rows)
        want = reference(prog, picked, n, got["drafted"])
        out = numbers(got, greedy, prog.question - 1, want)
        counts = [p["counts"] for p in picked]
        row = {"seed": seed, **moe_counters(counts, prog.steps),
               "accepted": float(sum(c["accepted"].sum() for c in counts)),
               **out.pop("spread"), "program": out}
        if k < n_control:
            low = reference(prog, picked, n, got["drafted"], quant=workload["check"]["control"])
            row["control"] = numbers(as_judged(low, got), greedy, prog.question - 1, want)
            row["control_spread"] = row["control"].pop("spread")
        yield row
