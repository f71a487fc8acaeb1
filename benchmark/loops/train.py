"""Training cells: one optimizer step per dispatch, fed from the host.

The loop is `bench.py`'s (the program's own trainer pieces: `DALLE`,
`make_dalle_train_step`, `make_optimizer`, `TrainState`, `Prefetcher`),
without its shell: sizes come from the workload file, token batches are
drawn on the host from `--seed` (a fresh batch per step), and one object (the
jitted step and its state) is built once, driven through its first three
steps for the comparison with the reference, and handed to the window.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmark import build, harness, traffic
from benchmark.reference import dalle_ref

FOLLOWED = 3  # first steps that the reference follows


def _adam_mu(opt_state):
    import jax

    found = [
        x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(x, "mu")
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


def worst_leaf_gap(got: dict, want: dict):
    """Worst over leaves (and layers) of |got - want| / max(want, median want):
    the gap between the norms, not the norm of the difference."""
    floor = float(np.median(np.concatenate([np.ravel(v) for v in want.values()])))
    worst, where = 0.0, None
    for name, w in want.items():
        w, g = np.ravel(np.asarray(w, np.float64)), np.ravel(np.asarray(got[name], np.float64))
        gap = np.abs(g - w) / np.maximum(w, floor)
        i = int(np.argmax(gap))
        if not np.isfinite(gap).all():
            return float("inf"), name
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{name}[{i}]" if w.size > 1 else name
    return worst, where


class Program:
    """The trainer's pieces for one configuration and job, built once: the
    jitted step, and the small programs that read its state."""

    def __init__(self, cfg: dict, job: dict):
        import jax

        from dalle_pytorch_tpu.training import make_dalle_train_step

        self.cfg, self.job, self.opt = cfg, job, job["optimizer"]
        self.d = dalle_ref.dims(cfg)
        self.batch = int(job["batch"])
        self.mdl = build.model(cfg, **job.get("model", {}))
        ex, depth = self.mdl.executor, self.mdl.depth
        self.step = jax.jit(make_dalle_train_step(self.mdl), donate_argnums=0)
        self.norms = jax.jit(lambda t: (
            build.leaf_norms_of(t, ex, depth),
            dalle_ref.small_leaves(build.from_program(t, ex, depth)),
        ))
        self.change = jax.jit(
            lambda p, p0: build.leaf_norms_of(
                jax.tree.map(lambda a, b: a - b, p, p0), ex, depth
            )
        )

    def host_batch(self, seed: int, i: int) -> dict:
        return traffic.token_batch(seed, i, self.batch, self.job["tokens"], self.d)

    def begin(self, seed: int):
        """(state, feed, rng) from the seed: weights made on the device, the
        optimizer as the trainer builds it, batches drawn on the host."""
        import jax

        from dalle_pytorch_tpu.data.prefetch import Prefetcher
        from dalle_pytorch_tpu.training import TrainState, make_optimizer

        variables = build.seeded_variables(self.cfg, self.mdl, seed)
        state = TrainState.create(
            apply_fn=self.mdl.apply, params=variables["params"],
            tx=make_optimizer(self.opt["learning_rate"],
                              clip_grad_norm=self.opt["clip_grad_norm"]),
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
        return state, rng, metrics["loss"]

    def follow(self, seed: int, state, feed, rng):
        """The first steps, through the window's own call and feed: each
        step's loss, the first gradient as Adam got it (from its first
        moment after one step) and the parameters' change, by leaf."""
        import jax

        got = {"losses": []}
        for i in range(FOLLOWED):
            state, rng, loss = self.dispatch(state, feed, rng)
            got["losses"].append(float(loss))
            if i == 0:
                mu, small = jax.device_get(self.norms(_adam_mu(state.opt_state)))
                got["grad_norms"] = {k: v / (1.0 - self.opt["b1"]) for k, v in mu.items()}
                got["grad_small"] = {k: v / (1.0 - self.opt["b1"]) for k, v in small.items()}
        p0 = build.seeded_variables(self.cfg, self.mdl, seed, check=False)["params"]
        got["change_norms"] = jax.device_get(self.change(state.params, p0))
        return got, state, rng

    def reference(self, seed: int, quant=None) -> dict:
        batches = [self.host_batch(seed, i) for i in range(FOLLOWED)]
        return dalle_ref.train_steps(
            self.cfg, dalle_ref.init_params(self.cfg, seed),
            [(b["text"], b["image_tokens"]) for b in batches], self.opt,
            int(self.job["reference_rows_per_block"]), quant=quant,
        )


def leaf_diffs(got: dict, want: dict) -> dict:
    """Per vector leaf (and layer): |got - want| / max(|want|, median |want|),
    the norm of the difference, which rounding noise moves where the gap
    between two norms hardly sees it."""
    norm = lambda x: np.sqrt(np.sum(np.square(np.asarray(x, np.float64)), axis=-1))
    floor = float(np.median(np.concatenate([np.ravel(norm(v)) for v in want.values()])))
    return {
        name: np.ravel(norm(np.asarray(got[name], np.float64) - np.asarray(w, np.float64)))
        / np.maximum(np.ravel(norm(w)), floor)
        for name, w in want.items()
    }


def worst_leaf_diff(got: dict, want: dict):
    """(the worst of `leaf_diffs`, the leaf it is on)."""
    worst, where = 0.0, None
    for name, rel in leaf_diffs(got, want).items():
        if not np.isfinite(rel).all():
            return float("inf"), name
        i = int(np.argmax(rel))
        if rel[i] > worst:
            worst, where = float(rel[i]), f"{name}[{i}]" if rel.size > 1 else name
    return worst, where


def run(run: harness.Run) -> dict:
    job, cfg = run.workload["job"], run.config
    prog = Program(cfg, job)
    d, batch = prog.d, prog.batch
    run.shapes.update(batch=batch, seq=d["seq"], heads=d["heads"], dim_head=d["dim_head"],
                      dim=d["dim"], depth=d["depth"], vocab=d["vocab"],
                      ff_mult=cfg["model"]["ff_mult"])
    state, feed, rng = prog.begin(run.seed)
    try:
        got, state, rng = prog.follow(run.seed, state, feed, rng)
        harness.say("first_steps", losses=got["losses"])

        # ---- the window. The host runs ahead of the device by up to
        # `steps_in_flight` dispatched steps, as the trainer's own loop does
        # (it syncs only to log): a host that stalls for some seconds, which
        # this machine's does (PERF.md, PR 23), then costs the device nothing.
        # A traced window keeps two in flight, so that it ends when it should.
        tracer = harness.Tracer(run)
        trace_plan = run.workload.get("trace", {})
        in_flight = 2 if run.trace else int(job["steps_in_flight"])
        t_open = run.window_opens()
        done_at, pending, settled = [], collections.deque(), []
        tokens_per_step = batch * d["seq"]

        def settle(loss):
            with harness.span("wait"):
                loss.block_until_ready()
            done_at.append(time.perf_counter() - t_open)
            settled[:] = [loss]

        def drive(until):
            nonlocal state, rng
            while time.perf_counter() - t_open < until:
                state, rng, loss = prog.dispatch(state, feed, rng)
                pending.append(loss)
                if len(pending) >= in_flight:
                    settle(pending.popleft())
            while pending:
                settle(pending.popleft())

        if run.trace:
            drive(float(trace_plan["after_s"]))
            n_before = len(done_at)
            with tracer.window():
                drive(float(trace_plan["after_s"]) + float(trace_plan["seconds"]))
            run.counters["traced_steps"] = len(done_at) - n_before
        else:
            drive(run.seconds)
        last_loss = float(settled[0])
        run.window_closes()
        run.attempted, run.failed = len(done_at), 0
        elapsed = done_at[-1]
        values = {"train_tokens_per_s": len(done_at) * tokens_per_step / elapsed}
        run.counters.update(steps=len(done_at), input_wait_fraction=feed.wait_fraction)
        run.record.update(step_done_at=done_at, last_loss=last_loss)
        harness.say("window", steps=len(done_at), elapsed_s=elapsed,
                    input_wait_fraction=feed.wait_fraction, last_loss=last_loss, **values)
        run.check("last_loss_finite", 0.0 if np.isfinite(last_loss) else 1.0, 0)
    finally:
        feed.close()

    # ---- the program is freed; the reference follows the first steps
    del state, feed, settled, pending
    t = time.perf_counter()
    want = prog.reference(run.seed)
    compare(run, got, want)
    harness.say("reference", seconds=time.perf_counter() - t,
                memory_peak_after_reference=run.memory_peak())
    return values


def numbers(got: dict, want: dict) -> dict:
    """The numbers compared: name -> (value, the leaf it was worst on)."""
    loss_gap = max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"]))
    return {
        "loss_gap": (loss_gap, None),
        "grad_norm_gap": worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
        "grad_diff": worst_leaf_diff(got["grad_small"], want["grad_small"]),
        "change_norm_gap": worst_leaf_gap(got["change_norms"], want["change_norms"]),
    }


def compare(run: harness.Run, got: dict, want: dict) -> None:
    """Loss of each followed step, the first gradient as Adam got it and the
    parameters' change, each against a limit of its own (workload file;
    PERF.md gives the readings each was set from)."""
    run.record["followed"] = {"losses": got["losses"], "reference_losses": want["losses"]}
    for name, (value, where) in numbers(got, want).items():
        if where:
            harness.say("worst_leaf", number=name, leaf=where)
        run.check(name, value, run.limit(name))


def readings(workload: dict, cfg: dict, seeds, n_control: int):
    """For `tests/chip_limits.py`: per seed the program's numbers and, for
    the first `n_control` seeds, the int8 control's, both against the
    float32 reference."""
    prog = Program(cfg, workload["job"])
    for k, seed in enumerate(seeds):
        state, feed, rng = prog.begin(seed)
        try:
            got, state, rng = prog.follow(seed, state, feed, rng)
        finally:
            feed.close()
        del state, feed
        want = prog.reference(seed)
        row = {"seed": seed, "program": {n: v for n, (v, _) in numbers(got, want).items()},
               "losses": got["losses"], "reference_losses": want["losses"]}
        if k < n_control:
            low = prog.reference(seed, quant=workload["check"]["control"])
            row["control"] = {n: v for n, (v, _) in numbers(low, want).items()}
            row["control_losses"] = low["losses"]
        yield row
