"""Language-model training cells: `loops/train.py`'s loop for token sequences.

One optimizer step per dispatch, fed from the host, through the program's
own trainer pieces (`CausalLM`, `make_lm_train_step`, `make_optimizer`,
`TrainState`, `Prefetcher`). Token ids are drawn on the host per step from
`--seed` (`traffic_lm.py`); the weights are made from `job.weights_seed`
where the workload file gives one (the routing a step sees is a property of
the weights, and a cell times one routing: PERF.md, PR 27) and from `--seed`
otherwise; the jitted step and its state are built once,
driven through their first three steps for the comparison with the
reference (`reference/mellum_ref.py`), and handed to the window. The step's
metrics carry what the routed layers counted (`moe_load`, `moe_rows`,
`moe_dropped`, per layer); the loop keeps them on the device and reads them
after the window, so that nothing syncs inside it.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import build_lm, harness, traffic_lm
from benchmark.loops.train import FOLLOWED, _adam_mu, worst_leaf_diff, worst_leaf_gap
from benchmark.reference import mellum_ref

MOE = ("moe_load", "moe_rows", "moe_dropped")


class Program:
    """The trainer's pieces for one configuration and job, built once."""

    def __init__(self, cfg: dict, job: dict):
        import jax

        from dalle_pytorch_tpu.models.lm import CausalLM
        from dalle_pytorch_tpu.training import make_lm_train_step

        self.cfg, self.job, self.opt = cfg, job, job["optimizer"]
        self.d = mellum_ref.dims(cfg)
        self.batch, self.seq = int(job["batch"]), int(job["seq_len"])
        self.weights_seed = job.get("weights_seed")
        self.mdl = CausalLM.from_config(cfg, self.seq, **job.get("model", {}))
        depth = self.mdl.depth
        self.step = jax.jit(make_lm_train_step(self.mdl), donate_argnums=0)
        self.norms = jax.jit(lambda t: (
            build_lm.leaf_norms_of(t, depth),
            mellum_ref.small_leaves(build_lm.from_program(t, depth)),
        ))
        self.change = jax.jit(
            lambda p, p0: build_lm.leaf_norms_of(jax.tree.map(lambda a, b: a - b, p, p0), depth)
        )
        # the program's own routed layer, outside any train step
        self.choices = jax.jit(lambda p, tokens: self.mdl.apply(
            {"params": p}, tokens, method=CausalLM.route_choices))
        self.tables = (traffic_lm.zipf_cdf(self.d["vocab"], job["tokens"]["exponent"]),
                       traffic_lm.rank_to_id(self.d["vocab"]))

    def weights(self, seed: int) -> int:
        """The seed the weights are made from in a run of `seed`."""
        return seed if self.weights_seed is None else int(self.weights_seed)

    def host_batch(self, seed: int, i: int) -> dict:
        return traffic_lm.token_batch(seed, i, self.batch, self.seq, self.job["tokens"],
                                      self.d["vocab"], self.tables)

    def begin(self, seed: int):
        """(state, feed, rng) from the seed: weights made on the device (from
        `weights(seed)`), the optimizer as the trainer builds it, batches
        drawn on the host."""
        import jax

        from dalle_pytorch_tpu.data.prefetch import Prefetcher
        from dalle_pytorch_tpu.training import TrainState, make_optimizer

        variables = build_lm.seeded_variables(self.cfg, self.mdl, self.weights(seed))
        state = TrainState.create(
            apply_fn=self.mdl.apply, params=variables["params"],
            tx=make_optimizer(self.opt["learning_rate"],
                              clip_grad_norm=self.opt["clip_grad_norm"],
                              warmup_steps=int(self.opt.get("warmup_steps", 0))),
        )

        def host_batches():
            i = 0
            while True:
                yield self.host_batch(seed, i)
                i += 1

        feed = Prefetcher(
            host_batches(),
            transform=lambda b: {k: jax.device_put(v) for k, v in b.items()},
            depth=int(self.job["prefetch_depth"]),
        )
        return state, feed, jax.random.PRNGKey(seed % (2**31 - 1))

    def dispatch(self, state, feed, rng):
        import jax

        with harness.span("feed"):
            dev_batch = next(feed)
        rng, r = jax.random.split(rng)
        with harness.span("dispatch"):
            state, metrics = self.step(state, dev_batch, r)
        return state, rng, metrics

    def follow(self, seed: int, state, feed, rng):
        """The first layer's choices on the first batch at the seeded
        weights; then the first steps, through the window's own call and
        feed: each step's loss and routing counts, the first gradient as
        Adam got it (from its first moment after one step) and the
        parameters' change, by leaf."""
        import jax

        got = {"losses": [], "moe": [],
               "choices": np.asarray(self.choices(state.params,
                                                  self.host_batch(seed, 0)["tokens"]))}
        for i in range(FOLLOWED):
            state, rng, metrics = self.dispatch(state, feed, rng)
            got["losses"].append(float(metrics["loss"]))
            got["moe"].append(jax.device_get({k: metrics[k] for k in MOE}))
            if i == 0:
                mu, small = jax.device_get(self.norms(_adam_mu(state.opt_state)))
                got["grad_norms"] = {k: v / (1.0 - self.opt["b1"]) for k, v in mu.items()}
                got["grad_small"] = {k: v / (1.0 - self.opt["b1"]) for k, v in small.items()}
        p0 = build_lm.seeded_variables(self.cfg, self.mdl, self.weights(seed),
                                       check=False)["params"]
        got["change_norms"] = jax.device_get(self.change(state.params, p0))
        return got, state, rng

    def reference(self, seed: int, quant=None) -> dict:
        batches = [self.host_batch(seed, i)["tokens"] for i in range(FOLLOWED)]
        weights = self.weights(seed)
        want = mellum_ref.train_steps(self.cfg, weights, batches, self.opt, quant=quant)
        want["choices"] = mellum_ref.route_choices(
            mellum_ref.init_params(self.cfg, weights), self.cfg, batches[0], quant=quant)
        return want


def flip_share(got: np.ndarray, want: np.ndarray) -> float:
    """The share of (token, slot) choices of `got` that `want` did not make
    for that token."""
    same = (got[..., :, None] == want[..., None, :]).any(-1)
    return float(1.0 - same.mean())


def moe_counters(moe: list, buffer_rows: int) -> dict:
    """From the steps' routing counts (each {moe_load [L, G], moe_rows [L],
    moe_dropped [L]}): the counters the metric files read."""
    load = np.stack([m["moe_load"] for m in moe]).astype(np.float64)  # [S, L, G]
    rows = np.stack([m["moe_rows"] for m in moe]).astype(np.float64)
    return {
        "expert_load_max_over_mean": float(
            np.mean(load.max(-1) / np.maximum(load.mean(-1), 1e-9))),
        "moe_padding_pct": float(100.0 * np.mean(1.0 - np.minimum(rows, buffer_rows) / buffer_rows)),
        "moe_rows_mean": float(rows.mean()),
        "moe_rows_max": float(rows.max()),
        "moe_dropped": float(sum(np.sum(m["moe_dropped"]) for m in moe)),
    }


def run(run: harness.Run) -> dict:
    prog = Program(run.config, run.workload["job"])
    got, values = measure(run, prog)
    # ---- the program's state is freed; the reference follows the first steps
    t = time.perf_counter()
    compare(run, got, prog.reference(run.seed))
    harness.say("reference", seconds=time.perf_counter() - t,
                memory_peak_after_reference=run.memory_peak())
    return values


def measure(run: harness.Run, prog: Program):
    """Set-up, the followed steps and the window of one run: (what the
    followed steps read, the end-to-end values). The state is freed on return."""
    import jax

    job = prog.job
    d, batch, seq = prog.d, prog.batch, prog.seq
    buffer_rows = int(prog.mdl.trunk["moe_buffer_rows"])
    run.shapes.update(
        batch=batch, seq=seq, heads=d["heads"], kv_heads=d["kv_heads"],
        dim_head=d["dim_head"], dim=d["dim"], depth=d["depth"], vocab=d["vocab"],
        window=d["window"], kinds=list(d["kinds"]), experts_held=d["experts_held"],
        expert_dim=d["expert_dim"], moe_buffer_rows=buffer_rows,
    )
    state, feed, rng = prog.begin(run.seed)
    try:
        got, state, rng = prog.follow(run.seed, state, feed, rng)
        harness.say("first_steps", losses=got["losses"],
                    **moe_counters(got["moe"], buffer_rows))

        # ---- the window, as `loops/train.py` drives it: the host runs ahead
        # of the device by up to `steps_in_flight` dispatched steps; a traced
        # window keeps two in flight, so that it ends when it should
        tracer = harness.Tracer(run)
        trace_plan = run.workload.get("trace", {})
        in_flight = 2 if run.trace else int(job["steps_in_flight"])
        t_open = run.window_opens()
        done_at, pending, kept = [], collections.deque(), []
        tokens_per_step = batch * seq

        def settle(metrics):
            with harness.span("wait"):
                metrics["loss"].block_until_ready()
            done_at.append(time.perf_counter() - t_open)
            kept.append(metrics)  # a few scalars a step, read after the window

        def drive(until):
            nonlocal state, rng
            while time.perf_counter() - t_open < until:
                state, rng, metrics = prog.dispatch(state, feed, rng)
                pending.append(metrics)
                if len(pending) >= in_flight:
                    settle(pending.popleft())
            while pending:
                settle(pending.popleft())

        n_before = 0
        if run.trace:
            drive(float(trace_plan["after_s"]))
            n_before = len(done_at)
            with tracer.window():
                drive(float(trace_plan["after_s"]) + float(trace_plan["seconds"]))
            run.counters["traced_steps"] = len(done_at) - n_before
        else:
            drive(run.seconds)
        run.window_closes()
        run.attempted, run.failed = len(done_at), 0
        elapsed = done_at[-1]
        values = {"train_tokens_per_s": len(done_at) * tokens_per_step / elapsed}
        timed = jax.device_get([{k: m[k] for k in MOE + ("loss",)} for m in kept])
        last_loss = float(timed[-1]["loss"])
        counters = moe_counters(got["moe"] + timed, buffer_rows)
        run.counters.update(steps=len(done_at), input_wait_fraction=feed.wait_fraction,
                            **counters)
        # what the cost functions count: the rows present in the steps traced
        seen = timed[n_before:] if run.trace else timed
        run.shapes["moe_rows"] = float(np.mean([m["moe_rows"] for m in seen]))
        run.record.update(step_done_at=done_at, last_loss=last_loss)
        harness.say("window", steps=len(done_at), elapsed_s=elapsed,
                    input_wait_fraction=feed.wait_fraction, last_loss=last_loss,
                    **counters, **values)
        run.check("last_loss_finite", 0.0 if np.isfinite(last_loss) else 1.0, 0)
        run.check("moe_dropped", counters["moe_dropped"], run.limit("moe_dropped"))
    finally:
        feed.close()
    del state, feed, kept, pending  # the reference needs the chip's memory
    return got, values


def numbers(got: dict, want: dict) -> dict:
    """The numbers compared: name -> (value, the leaf it was worst on)."""
    loss_gap = max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"]))
    return {
        "loss_gap": (loss_gap, None),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
        "grad_diff": worst_leaf_diff(got["grad_small"], want["grad_small"]),
        "change_norm_gap": worst_leaf_gap(got["change_norms"], want["change_norms"]),
        "route_flip_share": (flip_share(got["choices"], want["choices"]), None),
    }


def compare(run: harness.Run, got: dict, want: dict) -> None:
    """Loss of each followed step, the first gradient as Adam got it, the
    parameters' change and the first layer's routing, each against a limit
    of its own (workload file; PERF.md gives the readings each was set from)."""
    run.record["followed"] = {"losses": got["losses"], "reference_losses": want["losses"]}
    for name, (value, where) in numbers(got, want).items():
        if where:
            harness.say("worst_leaf", number=name, leaf=where)
        run.check(name, value, run.limit(name))


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: per seed the program's numbers and, for
    the first `n_control` seeds, the control's, both against the float32
    reference (the control routes on its own rounded inputs)."""
    prog = Program(cfg, workload["job"])
    buffer_rows = int(prog.mdl.trunk["moe_buffer_rows"])
    for k, seed in enumerate(seeds):
        state, feed, rng = prog.begin(seed)
        try:
            got, state, rng = prog.follow(seed, state, feed, rng)
        finally:
            feed.close()
        del state, feed
        want = prog.reference(seed)
        row = {"seed": seed, "program": {n: v for n, (v, _) in numbers(got, want).items()},
               "losses": got["losses"], "reference_losses": want["losses"],
               **moe_counters(got["moe"], buffer_rows)}
        if k < n_control:
            low = prog.reference(seed, quant=workload["check"]["control"])
            row["control"] = {n: v for n, (v, _) in numbers(low, want).items()}
            row["control_losses"] = low["losses"]
        yield row
