"""`correct` comes out false when the timed path is broken underneath, and the
open loop times a request from when it was due.

These drive the rest of a run in-process (only the harness's look for a chip
is skipped: the `_tiny` files are rehearsals, so any platform passes it).
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.loops import generate, serve, train


def a_run(cell, seconds=0.5):
    run = harness.Run(cell, seed=4, seconds=seconds, trace=False, t0=time.perf_counter())
    run.claim_device()
    return run


def test_sound_train_run_is_correct():
    run = a_run("_tiny.train")
    train.run(run)
    assert run.correct, run.checks


def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    import dalle_pytorch_tpu.training as training

    real = training.make_dalle_train_step

    def broken(model, *a, **kw):
        step = real(model, *a, **kw)

        def same_state(state, batch, rng):
            _, metrics = step(state, batch, rng)
            return state, metrics  # the loss is right, nothing is learned

        return same_state

    monkeypatch.setattr(training, "make_dalle_train_step", broken)
    run = a_run("_tiny.train")
    train.run(run)
    assert not run.correct
    failed = {c["name"] for c in run.checks if not c["ok"]}
    assert "change_norm_gap" in failed  # the number that is there to catch it


def test_a_step_that_leaves_out_part_of_the_batch_is_caught(monkeypatch):
    import dalle_pytorch_tpu.training as training

    real = training.make_dalle_train_step

    def broken(model, *a, **kw):
        step = real(model, *a, **kw)

        def half_batch(state, batch, rng):
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, rng)

        return half_batch

    monkeypatch.setattr(training, "make_dalle_train_step", broken)
    run = a_run("_tiny.train")
    train.run(run)
    assert not run.correct
    assert not all(c["ok"] for c in run.checks if c["name"] in ("loss_gap", "grad_diff"))


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    import dalle_pytorch_tpu.models.dalle as dalle

    real = dalle.gumbel_sample

    def off_by_one(rng, logits, temperature=1.0):
        return (real(rng, logits, temperature=temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(dalle, "gumbel_sample", off_by_one)
    dalle._jitted_sampler.cache_clear()
    try:
        run = a_run("_tiny.generate")
        generate.run(run)
    finally:
        dalle._jitted_sampler.cache_clear()
    assert not run.correct
    assert [c for c in run.checks if c["name"] == "greedy_gap" and not c["ok"]]


class _StalledBatcher:
    """Accepts at once, but the FIRST submit blocks the caller for a while:
    what a stall in the system does to an open-loop generator."""

    def __init__(self, stall_s):
        self.stall_s, self.n = stall_s, 0

    def submit(self, specs, timeout_s):
        from dalle_pytorch_tpu.serving.batcher import GenRequest

        if self.n == 0:
            time.sleep(self.stall_s)
        self.n += 1
        req = GenRequest(specs, timeout_s=timeout_s)
        req.future.set_result((np.zeros((1, 4), np.int32), None))
        return req


def test_open_loop_latency_is_timed_from_the_due_time():
    stall = 0.3
    plan = [(0.02 * i, np.ones(8, np.int32), i, False) for i in range(5)]
    t0 = time.monotonic()
    sent = serve.offer(_StalledBatcher(stall), plan, t0, 0.0, lambda: None, 5.0)
    lat, failed = serve.latencies(sent, 5.0)
    assert failed == 0
    # everything due during the stall waited for it, and the wait shows
    assert lat[1] > stall - 0.05 and lat[4] > stall - 0.1
    # timed from submission it would have hidden: submit-to-done is ~0
    assert max(s.done - s.submitted for s in sent[1:]) < 0.05
    # and the generator's lateness is reported for what it is
    assert max(s.submitted - s.due for s in sent) > stall - 0.1


@pytest.mark.parametrize("cell", ["_tiny.train", "_tiny.generate", "_tiny.serve"])
def test_the_control_fails_where_the_program_passes(cell):
    """The control (the reference computed in fp8, the precision below the
    configurations' bf16) put in the program's place comes out as not correct
    under the cell's limits, on every seed, while the program passes. The
    same reading is made on the chip at the cells' own sizes by
    `tests/chip_limits.py`; PERF.md quotes it."""
    import importlib

    workload = harness.load("workloads", cell)
    config = harness.load("configs", workload["config"])
    loop = importlib.import_module(f"benchmark.loops.{workload['kind']}")
    limits = workload["check"]["limits"]
    rows = list(loop.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row


def test_above_the_knee_the_window_ends_on_time_and_counts_completions():
    """The saturated variant differs from the steady cell by numbers in its
    workload file: the queue grows, nothing is waited for, what the loop's own
    shutdown cancels is not a failure."""
    run = a_run("_tiny.serve", seconds=1.5)
    run.workload["job"].update(rate_rps=200.0, wait_for_sample=False,
                               judged_on=["serve_tokens_per_s"])
    values = serve.run(run)
    assert set(values) == {"serve_tokens_per_s"} and values["serve_tokens_per_s"] > 0
    assert run.correct and run.failed == 0
    assert run.attempted < len(run.record["due"])  # the backlog was left behind
