"""Offline generation cells: whole batches through the cached sampler.

The loop is `bench_generate.py`'s around the call `generate.py` makes
(`generate_images_cached` with the dVAE's pixel decode fused in), without
its shell: one batch is one dispatch of prefill + `image_seq` token steps +
pixel decode, and ends when tokens and pixels are on the host. Batches run
back to back until the window is used up; whole batches are counted, over
the time to the last one's end.

The workload file's `batches` is a cycle of sampler settings. Part of the
traffic is greedy (`filter_thres` 1.0 keeps one logit), because only a
greedy token can be checked against the reference: the widest gap by which a
served token's reference logit lies below the reference's best.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import build, harness, traffic
from benchmark.reference import dalle_ref


WARM_INDEX = 1 << 30  # warm-up batches draw prompts no window batch draws


class Program:
    """Model, seeded weights, dVAE and the sampler call, built once."""

    def __init__(self, cfg: dict, job: dict, seed: int):
        import jax

        self.cfg, self.job, self.seed = cfg, job, seed
        self.d = dalle_ref.dims(cfg)
        self.batch = int(job["batch"])
        self.mdl = build.model(cfg)
        self.variables = build.seeded_variables(cfg, self.mdl, seed)
        self.vae, self.vae_params = build.seeded_vae(cfg, seed)
        self.key = jax.random.PRNGKey(seed % (2**31 - 1))

    def setting(self, i: int) -> dict:
        return self.job["batches"][i % len(self.job["batches"])]

    def prompts(self, i: int) -> np.ndarray:
        return traffic.prompts(self.seed, i, self.batch, self.job["prompt_length"],
                               self.d["text_seq"], self.d["base_text_vocab"])

    def one_batch(self, i: int, setting=None):
        """The timed unit: prompts -> (tokens, pixels) on the host."""
        import jax
        import jax.numpy as jnp

        from dalle_pytorch_tpu.models.dalle import generate_images_cached

        s = setting or self.setting(i)
        text = self.prompts(i)
        with harness.span("sample"):
            toks, pixels = generate_images_cached(
                self.mdl, self.variables, jax.random.fold_in(self.key, i % (2**31 - 1)),
                jnp.asarray(text),
                filter_thres=float(s["filter_thres"]), temperature=float(s["temperature"]),
                cond_scale=float(self.job["cond_scale"]),
                vae=self.vae, vae_params=self.vae_params,
            )
        with harness.span("to_host"):
            return text, np.asarray(toks), np.asarray(pixels)

    def free(self) -> None:
        self.variables = self.vae_params = None


def is_greedy(setting: dict) -> bool:
    return float(setting["filter_thres"]) >= 1.0


def widest_gap(cfg, seed, rows, quant=None):
    """rows: [(text [T], tokens [N])]. The reference runs once over each
    prompt with its served tokens; returns (widest gap, the gaps)."""
    text = np.stack([r[0] for r in rows])
    toks = np.stack([r[1] for r in rows])
    gaps = dalle_ref.greedy_gaps(cfg, dalle_ref.init_params(cfg, seed), text, toks, quant)
    return float(gaps.max()), gaps


def run(run: harness.Run) -> dict:
    job, cfg = run.workload["job"], run.config
    prog = Program(cfg, job, run.seed)
    d, batch = prog.d, prog.batch
    run.shapes.update(batch=batch, seq=d["seq"], image_seq=d["image_seq"], heads=d["heads"],
                      dim_head=d["dim_head"], depth=d["depth"])
    # warm every sampler setting of the cycle once: one compiled program each
    warmed = []
    for k, s in enumerate(job["batches"]):
        if s not in warmed:
            prog.one_batch(WARM_INDEX + k, s)
            warmed.append(s)

    tracer = harness.Tracer(run)
    plan = run.workload.get("trace", {})
    t_open = run.window_opens()
    timer = None
    if run.trace:
        timer = tracer.in_background(float(plan["after_s"]), float(plan["seconds"]))
    until = run.seconds if not run.trace else float(plan["after_s"]) + float(plan["seconds"])
    done_at, greedy, bad = [], [], 0
    i = 0
    # at least one batch of every sampler setting, whatever the window's length
    least = 0 if run.trace else len(warmed)
    while time.perf_counter() - t_open < until or i < least:
        text, toks, pixels = prog.one_batch(i)
        done_at.append(time.perf_counter() - t_open)
        if not (np.isfinite(pixels).all() and toks.min() >= 0
                and toks.max() < d["image_vocab"]):
            bad += 1
        if is_greedy(prog.setting(i)):
            greedy.append((text, toks))
        i += 1
    if timer is not None:
        timer.join()
    run.window_closes()
    run.attempted, run.failed = len(done_at), bad
    elapsed = done_at[-1]
    values = {"generate_tokens_per_s": len(done_at) * batch * d["image_seq"] / elapsed}
    run.counters.update(batches=len(done_at))
    run.record.update(batch_done_at=done_at)
    harness.say("window", batches=len(done_at), elapsed_s=elapsed, greedy_batches=len(greedy),
                **values)
    run.check("bad_batches", bad, 0)

    # ---- the program is freed; the reference reads a sample of greedy rows
    prog.free()
    t = time.perf_counter()
    n_rows = int(run.workload["check"]["rows"])
    pool = [(tx[r], tk[r]) for tx, tk in greedy for r in range(batch)]
    if not pool:
        run.check("greedy_rows_served", 0, 0, ok=False)
        return values
    picked = [pool[j] for j in traffic.sample(run.seed, "check_rows", len(pool), n_rows)]
    gap, gaps = widest_gap(cfg, run.seed, picked)
    run.record["greedy_gaps"] = {"rows": len(picked), "tokens": int(gaps.size),
                                 "nonzero": int((gaps > 0).sum()),
                                 "p99": float(np.quantile(gaps, 0.99))}
    harness.say("greedy", **run.record["greedy_gaps"])
    run.check("greedy_gap", gap, run.limit("greedy_gap"))
    harness.say("reference", seconds=time.perf_counter() - t,
                memory_peak_after_reference=run.memory_peak())
    return values


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: per seed one greedy batch through the
    sampler (the cell's own size), the widest gap of the sampled rows, and for
    the first `n_control` seeds the control's on the same prompts and tokens."""
    job = workload["job"]
    greedy = next(s for s in job["batches"] if is_greedy(s))
    n_rows = int(workload["check"]["rows"])
    for k, seed in enumerate(seeds):
        prog = Program(cfg, job, seed)
        text, toks, _ = prog.one_batch(0, greedy)
        prog.free()
        del prog
        pool = [(text[r], toks[r]) for r in range(text.shape[0])]
        picked = [pool[j] for j in traffic.sample(seed, "check_rows", len(pool), n_rows)]
        gap, gaps = widest_gap(cfg, seed, picked)
        row = {"seed": seed, "program": {"greedy_gap": gap},
               "nonzero": int((gaps > 0).sum()), "tokens": int(gaps.size)}
        if k < n_control:
            low, _ = widest_gap(cfg, seed, picked, quant=workload["check"]["control"])
            row["control"] = {"greedy_gap": low}
        yield row
