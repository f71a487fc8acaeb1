"""Generation cells of a latent-attention model that SELECTS the cached
positions it attends (learned sparse attention: a lightning indexer beside
every layer's latent cache): sessions that each hold a long document take
further turns through the cached sampler, several sessions to a document.

Set-up builds the model (`CausalLM.from_config`) and its seeded weights
(`build_deepseek_v32.py`, stored as the configuration says), prefills each of
the `job.sessions / job.sessions_per_document` DOCUMENTS once through the
program's chunked prefill (`prefill_chunks`: `job.prefill_chunk` tokens a
dispatch, each chunk against what its cache holds by then and itself, every
query with its own selection), then makes ONE cache of `job.sessions` rows,
whose length is document + turn rounded up to `job.cache_block`, and copies
each document's two leaves a layer (`rows`, `index_k`) to the sessions that
hold it (`place_rows`: there are no pages to share, so every session owns its
copy, which is what fills the memory). Session r holds document r mod the
number of documents, so the first rows, whose logits the sampler keeps, hold
DIFFERENT documents; and set-up compares every session's leaves with its
document's prefill on the device, bit for bit (`copies_off`). Documents and
weights are made from `job.documents_seed` and `job.weights_seed` in EVERY
run: one routing and one selection pattern, as a deployment has one checkpoint.

A timed batch is one further turn of all sessions in ONE dispatch
(`generate_tokens_cached`): the cache's index set back to the documents'
length (no copy), a question of `job.question_tokens` a row forced through
the token step (drawn per batch and row from `--seed`), then
`job.answer_tokens` sampled. The workload file's `batches` is a cycle of
sampler settings (greedy and top-k, keys from `--seed`); a batch ends when
its TOKENS are on the host (18 KB); what `correct` judges of it (the logits,
selections and router choices of the first `check.rows` rows, 60 MB) and the
counts follow, behind the next batch, and a host that is slow to take them
does not stretch the batch. The next batch is dispatched BEFORE the last
one's results are fetched, as long as it would start inside the window. The rate is counted over whole CYCLES of the
settings, over the time to the last counted batch's end.

`correct`, after the window: the program's state is freed and the reference
(`reference/deepseek_v32_ref.py`) judges ONE of the `check.rows` kept rows of
one greedy and of one sampled batch, a different row each and which by the
seed (`judge`: with two rows kept, two documents in a run, and over the seeds
each document under both samplers). It runs its uncached forward over that
row's document, question and served tokens (teacher forcing), a layer's
weights at a time: the logits of the token steps, the tokens greedy rows
chose, the first routed layer's choices, and EVERY layer's selected positions
at every token step, which the timed program itself gave for that row
(`selected_short`: the kept row-steps whose selection is not exactly
min(index_topk, live) distinct live positions, plus the positions by which the
program's own count over ALL rows misses that).
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmark import build_deepseek_v32, harness, traffic_lm
from benchmark.loops.generate_lm import WARM_INDEX, is_greedy, moe_counters, pick
from benchmark.loops.train_lm import flip_share
from benchmark.reference import deepseek_v32_ref


class Program:
    """Model, seeded weights, the sessions' cache and the sampler call."""

    def __init__(self, cfg: dict, job: dict):
        from dalle_pytorch_tpu.models.lm import CausalLM

        self.cfg, self.job = cfg, job
        self.d = deepseek_v32_ref.dims(cfg)
        self.sessions, self.doc = int(job["sessions"]), int(job["document_tokens"])
        self.per_doc = int(job["sessions_per_document"])
        assert self.sessions % self.per_doc == 0, "whole documents"
        self.n_docs = self.sessions // self.per_doc
        self.question, self.answer = int(job["question_tokens"]), int(job["answer_tokens"])
        self.steps = self.question + self.answer
        block = int(job["cache_block"])
        self.max_len = -(-(self.doc + self.steps) // block) * block
        self.mdl = CausalLM.from_config(cfg, self.max_len, **job.get("model", {}))
        self.tables = (traffic_lm.zipf_cdf(self.d["vocab"], job["tokens"]["exponent"]),
                       traffic_lm.rank_to_id(self.d["vocab"]))
        self.documents = self._tokens(int(job["documents_seed"]), 0, self.n_docs, self.doc)
        self.variables = self.cache = None
        self.prefill_counts, self.warmed, self.setup_parts = [], [], {}
        self.selected_off = self.copies_off = 0

    def _tokens(self, seed: int, index: int, rows: int, length: int) -> np.ndarray:
        return traffic_lm.token_batch(seed, index, rows, length, self.job["tokens"],
                                      self.d["vocab"], self.tables)["tokens"]

    def questions(self, seed: int, i: int) -> np.ndarray:
        return self._tokens(seed, 1 + i, self.sessions, self.question)

    def document_of(self, rows) -> np.ndarray:
        """The documents that session rows `rows` hold: row r, document r
        mod the number of documents."""
        return self.documents[np.asarray(rows) % self.n_docs]

    def setup(self) -> None:
        """Weights on the device, every document prefilled once, then every
        session's copy of its document in the cache, each compared with the
        prefill it was copied from."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.lm import place_rows, prefill_chunks

        t = time.perf_counter()
        self.variables = jax.block_until_ready(build_deepseek_v32.seeded_variables(
            self.cfg, self.mdl, int(self.job["weights_seed"])))
        self.setup_parts["weights_s"] = time.perf_counter() - t
        t = time.perf_counter()
        fresh = []
        for doc in self.documents:
            with harness.span("prefill"):
                made, counts = prefill_chunks(self.mdl, self.variables, jnp.asarray(doc[None]),
                                              int(self.job["prefill_chunk"]))
            fresh.append(made)
            self.prefill_counts.append(counts)
        jax.block_until_ready(fresh)
        self.setup_parts["prefill_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.cache = self.mdl.init_cache(self.sessions)
        off = []
        for k in range(self.n_docs):
            holders = range(k, self.sessions, self.n_docs)
            for row in holders:
                self.cache = place_rows(self.mdl, self.cache, fresh[k], row)
            off.append(_copies_off()(self.cache, fresh[k], jnp.asarray(holders, jnp.int32)))
            fresh[k] = None
        self.copies_off = int(sum(jax.device_get(off)))
        self.setup_parts["copies_s"] = time.perf_counter() - t

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
        """The batch on the host: the questions, the tokens [B, steps] and
        `done`, the clock when they arrived (the batch's end); then the
        logits [steps, rows, V] of the first `rows` rows and the sampler's
        counts with, under `picks`, those rows' selections [steps, layers,
        rows, k] and first router's choices [steps, rows, k]."""
        import jax

        with harness.span("to_host"):
            toks = np.asarray(batch["toks"])
            done = time.perf_counter()
            return {**batch, "toks": toks, "done": done, "logits": np.asarray(batch["logits"]),
                    "counts": jax.device_get(batch["counts"])}

    def one_batch(self, seed: int, i: int, setting: dict, rows: int) -> dict:
        return self.finish_batch(self.start_batch(seed, i, setting, rows))

    def sequences(self, forced: np.ndarray, toks: np.ndarray, rows) -> np.ndarray:
        """[len(rows), doc + steps]: what the session rows `rows`' token steps
        were fed, after their documents: the question, then each step's sample."""
        fed = np.concatenate([forced, toks[:, self.question - 1:-1]], axis=1)
        return np.concatenate([self.document_of(rows), fed[rows]], axis=1).astype(np.int32)

    def free(self) -> None:
        self.variables = self.cache = None


@functools.cache
def _copies_off():
    """The set-up program `off(cache, fresh, rows)`: how many (session, leaf)
    pairs of `cache`'s rows `rows` are NOT, bit for bit, the one row of
    `fresh` (a document's prefill, as long as the document) over the positions
    it has: 0 where every copy is whole. The per-layer indices are no copies
    and are passed over."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def leaf(c, f, rows):
        if c.ndim < 2:
            return jnp.zeros((), jnp.int32)
        held = lax.slice(c[rows], (0,) * c.ndim, (rows.shape[0],) + f.shape[1:])
        return jnp.sum(jnp.any(held != f, axis=tuple(range(1, c.ndim))), dtype=jnp.int32)

    return jax.jit(lambda cache, fresh, rows: sum(jax.tree.leaves(
        jax.tree.map(lambda c, f: leaf(c, f, rows), cache, fresh))))


def dsa_counters(counts: list, sessions: int, steps: int) -> dict:
    """From the turns' counts (`dsa_scored`, `dsa_selected` [L], each summed
    over a turn's token steps and rows): positions a row-step a layer."""
    per = lambda name: float(np.mean([c[name] for c in counts])) / (sessions * steps)
    return {"scored_per_row_step": per("dsa_scored"),
            "selected_per_row_step": per("dsa_selected")}


def selected_short(selected: np.ndarray, count: np.ndarray, at: np.ndarray, topk: int) -> int:
    """Row-steps (a layer each) whose selection is not exactly min(topk, live)
    distinct live positions: `selected` [..., k] with `count` [...] of them
    in use, the query at position `at` [...] (live: 0 .. at)."""
    short = 0
    for picked, n, t in zip(selected.reshape(-1, selected.shape[-1]), count.reshape(-1),
                            at.reshape(-1)):
        picked = picked[:n]
        short += not (n == min(topk, t + 1) and len(np.unique(picked)) == n
                      and picked.min() >= 0 and picked.max() <= t)
    return short


def select_flip_share(got: np.ndarray, want: np.ndarray) -> float:
    """The share of selected positions of `got` [..., k] (entries < 0 are
    empty slots) that `want`'s set for the same query does not hold."""
    got, want = got.reshape(-1, got.shape[-1]), want.reshape(-1, want.shape[-1])
    missed = sum(int(np.sum(~np.isin(g[g >= 0], w[w >= 0]))) for g, w in zip(got, want))
    return missed / max(int(np.sum(got >= 0)), 1)


def numbers(logits: np.ndarray, toks: np.ndarray, greedy: np.ndarray, choices: np.ndarray,
            selected: np.ndarray, want: dict) -> dict:
    """The numbers compared, of judged rows [R]: `logits` [R, steps, V],
    `choices` [R, steps, k] and `selected` [R, layers, steps, topk] (< 0:
    an empty slot) of whoever is judged, `toks` [R, steps] what it sampled,
    `greedy` [R] which rows sampled greedily; `want` the reference's `forward`
    over the same fed tokens."""
    ref = want["logits"].astype(np.float64)
    diff = np.linalg.norm(logits - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    below = ref.max(-1) - np.take_along_axis(ref, toks[..., None].astype(np.int64), -1)[..., 0]
    return {
        # the WORST step: an extreme of some 600 that swings 50% from seed to seed, so
        # its limit is a backstop for one step gone wrong (unrelated logits read 1.4)
        # and the 99th percentile, steady to 15%, is what the control is held by
        "logit_gap": float(diff.max()),
        "logit_gap_p99": float(np.quantile(diff, 0.99)),
        # the step in the middle, which a few outlying steps do not move
        "logit_gap_median": float(np.median(diff)),
        # the distance, in logit units, of a greedy token below the reference's best:
        # the MEAN, which the control is held by and one badly wrong token hardly moves,
        # and the worst token's, an extreme again and a backstop for that one token
        "greedy_gap": float(below[greedy].mean()) if greedy.any() else 0.0,
        "greedy_gap_worst": float(below[greedy].max()) if greedy.any() else 0.0,
        "route_flip_share": flip_share(choices, want["choices"]),
        "select_flip_share": select_flip_share(selected, want["selected"]),
        # how the steps' gaps lie (printed, not compared)
        "spread": {"greedy_tokens_off_best": float((below[greedy] > 0).mean())
                   if greedy.any() else 0.0,
                   # a layer's flips move the next layer's input, and so its flips
                   "select_flip_share_by_layer": [
                       select_flip_share(selected[:, i], want["selected"][:, i])
                       for i in range(selected.shape[1])]},
    }


def shapes_of(prog: Program) -> dict:
    d = prog.d
    return dict(
        batch=prog.sessions, heads=d["heads"], kv_rank=d["kv_rank"], rope=d["rope"],
        nope=d["nope"], v_dim=d["v_dim"], q_rank=d["q_rank"], dim=d["dim"], depth=d["depth"],
        vocab=d["vocab"], kinds=list(d["kinds"]), dense_dim=d["dense_dim"],
        expert_dim=d["expert_dim"], shared_dim=d["shared_dim"],
        experts_held=d["experts_held"], experts_total=d["experts_total"],
        per_token=d["per_token"], index_heads=d["index_heads"], index_dim=d["index_dim"],
        index_topk=d["index_topk"],
        # the grouped products a token step calls: three a routed layer
        gmm_calls=3 * sum(k == "routed" for k in d["kinds"]),
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
    t = time.perf_counter()
    for k, s in enumerate(settings):
        if s not in prog.warmed:
            prog.one_batch(run.seed, WARM_INDEX + k, s, n_rows)
            prog.warmed.append(s)
    harness.say("setup_parts", **prog.setup_parts, warm_batches_s=time.perf_counter() - t)

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
        done_at.append(batch["done"] - t_open)
        period = done_at[-1] - (done_at[-2] if len(done_at) > 1 else 0.0)
        if not (np.isfinite(batch["logits"]).all() and batch["toks"].min() >= 0
                and batch["toks"].max() < prog.d["vocab"]):
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
    prefill = jax.device_get(prog.prefill_counts)
    counts = [b["counts"] for b in served]
    counters = {**moe_counters(counts, prog.steps),
                **dsa_counters(counts, prog.sessions, prog.steps)}
    counters["moe_dropped"] += float(sum(np.sum(c["moe_dropped"]) for c in prefill))
    run.counters.update(batches=len(served), batches_counted=counted, **counters)
    run.shapes.update(moe_touched=counters["experts_touched"], moe_rows=counters["moe_rows_mean"])
    run.record.update(batch_done_at=done_at, setup_parts=prog.setup_parts)
    harness.say("window", batches=len(served), counted=counted, elapsed_s=elapsed, **counters,
                **values)
    run.check("bad_batches", bad, run.limit("bad_batches"))
    run.check("moe_dropped", counters["moe_dropped"], run.limit("moe_dropped"))
    run.check("copies_off", prog.copies_off, run.limit("copies_off"))
    # positions by which ALL rows' selections, by the program's own count, miss
    # a full one (min(index_topk, live) a row-step a layer): `selected_short`'s part
    full = min(prog.d["index_topk"], prog.doc)
    prog.selected_off = abs(
        int(sum(np.sum(c["dsa_selected"]) for c in counts))
        - len(served) * prog.d["depth"] * prog.sessions * prog.steps * full)
    return values, served[:counted]


def judge(seed: int, picked: list, n_rows: int) -> list:
    """The picked batches, each with the `row` it is judged on: one of the
    `n_rows` rows the sampler kept, another for each batch, the first by the
    seed."""
    return [{**p, "row": (seed + i) % n_rows} for i, p in enumerate(picked)]


def judged(picked: list):
    """(logits [R, steps, V], tokens [R, steps], greedy [R], choices [R,
    steps, k], selected [R, layers, steps, topk] with < 0 in unused slots) of
    the picked batches' judged rows, a row a batch, as `numbers` takes them."""
    def chosen(p):
        picks = p["counts"]["picks"]  # [steps, layers, rows, k]
        slots = np.arange(picks["selected"].shape[-1])
        sel = np.where(slots < picks["selected_count"][..., None], picks["selected"], -1)
        return sel[:, :, p["row"]].transpose(1, 0, 2)

    return (np.stack([p["logits"][:, p["row"]] for p in picked]),
            np.stack([p["toks"][p["row"]] for p in picked]),
            np.asarray([p["greedy"] for p in picked]),
            np.stack([p["counts"]["picks"]["experts"][:, p["row"]] for p in picked]),
            np.stack([chosen(p) for p in picked]))


def short_of(prog: Program, picked: list) -> int:
    """`selected_short` over every row the sampler kept of the picked batches."""
    at = prog.doc + np.arange(prog.steps)
    total = 0
    for p in picked:
        picks = p["counts"]["picks"]
        shape = picks["selected_count"].shape  # [steps, layers, rows]
        total += selected_short(picks["selected"], picks["selected_count"],
                                np.broadcast_to(at[:, None, None], shape), prog.d["index_topk"])
    return total


def reference(prog: Program, picked: list, quant=None) -> dict:
    """The reference's forward over the judged rows of the `picked` batches,
    all in one pass over the layers."""
    seqs = np.concatenate([prog.sequences(p["forced"], p["toks"], [p["row"]]) for p in picked])
    return deepseek_v32_ref.forward(prog.cfg, int(prog.job["weights_seed"]), seqs,
                                    start=prog.doc, quant=quant)


def compare(run: harness.Run, prog: Program, served: list) -> None:
    """The program's state is freed and the reference judges a kept row of
    one greedy and another of one sampled batch, each number against a limit
    of its own (workload file; PERF.md gives the readings each was set from)."""
    t = time.perf_counter()
    picked = judge(run.seed, pick(run.seed, served), int(run.workload["check"]["rows"]))
    if not any(p["greedy"] for p in picked):
        run.check("greedy_rows_served", 0, 0, ok=False)
        return
    prog.free()
    run.check("selected_short", short_of(prog, picked) + prog.selected_off,
              run.limit("selected_short"))
    got = numbers(*judged(picked), reference(prog, picked))
    harness.say("gaps", rows=[p["row"] for p in picked], **got.pop("spread"))
    for name, value in got.items():
        run.check(name, value, run.limit(name))
    harness.say("reference", seconds=time.perf_counter() - t, rows=len(picked),
                memory_peak_after_reference=run.memory_peak())


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: one set-up, then per seed one greedy and
    one sampled turn through the sampler (the cell's own size); the program
    is freed, and per seed the reference gives the program's numbers and, for
    the first `n_control` seeds, the control's (the reference in the control's
    precision, judged as the program is: its logits, the tokens it would pick
    greedily, its router's choices, its indexer's selections)."""
    job = workload["job"]
    n_rows = int(workload["check"]["rows"])
    prog = Program(cfg, job)
    prog.setup()
    settings = [next(s for s in job["batches"] if is_greedy(s)),
                next(s for s in job["batches"] if not is_greedy(s))]
    kept = [(seed, judge(seed, [prog.one_batch(seed, k, s, n_rows)
                                for k, s in enumerate(settings)], n_rows)) for seed in seeds]
    prog.free()
    for k, (seed, picked) in enumerate(kept):
        want = reference(prog, picked)
        theirs = judged(picked)
        got = numbers(*theirs, want)
        counts = [p["counts"] for p in picked]
        row = {"seed": seed, **moe_counters(counts, prog.steps),
               **dsa_counters(counts, prog.sessions, prog.steps),
               "selected_short": short_of(prog, picked), "copies_off": prog.copies_off,
               "rows": [p["row"] for p in picked], **got.pop("spread"), "program": got}
        if k < n_control:
            low = reference(prog, picked, quant=workload["check"]["control"])
            row["control"] = numbers(low["logits"], low["logits"].argmax(-1), theirs[2],
                                     low["choices"], low["selected"], want)
            row["control_spread"] = row["control"].pop("spread")
        yield row
