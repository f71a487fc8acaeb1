"""CPU tests of the language-model cell's benchmark files on the
`_tiny-mellum` / `_tiny.train_lm` rehearsal files: the loop end to end, a
traced run that reports the routed layers' counters, the broken paths that
must read `correct: false`, the control, and the reference's own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (logits, loss, every gradient leaf,
three optimizer steps, the share test) are in `tests/test_lm.py`, which the
repo's tier-1 command collects.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.loops import train_lm
from benchmark.reference import mellum_ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell

CELL = "_tiny.train_lm"
COUNTERS = {"expert_load_max_over_mean.mellum", "moe_padding_pct.mellum"}


def a_run(seconds=0.5, trace=False):
    run = harness.Run(CELL, seed=4, seconds=seconds, trace=trace, t0=time.perf_counter())
    run.claim_device()
    return run


def failed(run):
    return {c["name"] for c in run.checks if not c["ok"]}


def test_loop_end_to_end():
    line = last_line(run_cell(CELL))
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_routed_layers_counters():
    p = run_cell(CELL, trace=1)
    line = last_line(p)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert COUNTERS | {"compiles_in_window"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["expert_load_max_over_mean.mellum"]["value"] >= 1.0
    assert 0 <= line["metrics"]["moe_padding_pct.mellum"]["value"] < 100
    # no other cell's metric leaks in, and the device metrics of this one
    # need a device trace by HLO name, which the CPU has not: left out
    assert not [m for m in line["metrics"] if m.endswith((".train", ".gen"))]
    assert "moe_dropped" in p.stdout and '"name": "route_flip_share"' in p.stdout


def test_sound_run_is_correct_and_checks_every_number():
    run = a_run()
    train_lm.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "loss_gap", "grad_norm_gap", "grad_diff", "change_norm_gap", "route_flip_share",
        "moe_dropped", "compiles_in_window"}


def test_a_held_experts_output_left_out_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    real = moe.route

    def without_the_first_held_expert(probs, per_token, held, buffer_rows):
        r = real(probs, per_token, held, buffer_rows)
        r["pos"] = jnp.where(r["experts"] == held[0], buffer_rows, r["pos"])
        return r

    monkeypatch.setattr(moe, "route", without_the_first_held_expert)
    run = a_run()
    train_lm.run(run)
    assert not run.correct and failed(run) & {"loss_gap", "grad_norm_gap", "grad_diff"}


def test_a_window_one_key_too_long_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = attention.flash_attention

    def one_key_more(q, k, v, **kw):
        if kw.get("window") is not None:
            kw["window"] += 1
        return real(q, k, v, **kw)

    monkeypatch.setattr(attention, "flash_attention", one_key_more)
    run = a_run()
    train_lm.run(run)
    assert not run.correct and failed(run) & {"loss_gap", "grad_norm_gap", "grad_diff"}


def test_an_assignment_dropped_past_a_too_small_buffer_is_caught():
    run = a_run()
    run.workload["job"]["model"]["moe_buffer_rows"] = 40  # ~64 assignments a layer
    train_lm.run(run)
    assert not run.correct and "moe_dropped" in failed(run)


def test_the_control_fails_where_the_program_passes():
    """The reference computed in fp8, the precision below the configuration's
    bf16, put in the program's place, is not correct under the cell's limits
    on any seed, while the program passes; `tests/chip_limits.py` makes
    the same reading on the chip at the cell's sizes."""
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(train_lm.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row
        assert row["moe_dropped"] == 0


def test_every_seed_offers_the_same_skew_over_the_same_ids():
    from benchmark import traffic_lm

    spec = {"dist": "zipf", "exponent": 1.0}
    a = traffic_lm.token_batch(1, 0, 8, 4096, spec, 96)["tokens"]
    b = traffic_lm.token_batch(2**31 + 5, 0, 8, 4096, spec, 96)["tokens"]
    assert a.dtype == np.int32 and a.shape == (8, 4096) and not np.array_equal(a, b)
    assert 0 <= a.min() and a.max() < 96
    top = lambda t: np.argsort(-np.bincount(t.ravel(), minlength=96))[:3]
    assert np.array_equal(top(a), top(b))  # the rank-to-id permutation is the cell's
    assert np.array_equal(top(a), traffic_lm.rank_to_id(96)[:3])
    share = np.bincount(a.ravel(), minlength=96)[top(a)[0]] / a.size
    assert abs(share - 1 / np.sum(1 / np.arange(1, 97))) < 0.02  # rank 1 of Zipf(1)
    again = traffic_lm.token_batch(1, 0, 8, 4096, spec, 96)["tokens"]
    assert np.array_equal(a, again)  # the same seed gives the same inputs


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-mellum")
    ref = mellum_ref.init_params(cfg, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 32)), jnp.int32)
    whole = mellum_ref.logits_fn(ref, cfg, tokens)
    monkeypatch.setattr(mellum_ref, "Q_BLOCK", 8)
    np.testing.assert_allclose(mellum_ref.logits_fn(ref, cfg, tokens), whole, atol=1e-5)


def test_the_reference_and_the_program_turn_by_the_same_frequencies():
    from dalle_pytorch_tpu.models.lm import rotary_spec
    from dalle_pytorch_tpu.ops.rotary import rotary_inv_freq

    cfg = harness.load("configs", "mellum2-12b-ep4")
    for kind, spec in cfg["rope_parameters"].items():
        np.testing.assert_array_equal(
            mellum_ref.inv_freq(spec, cfg["head_dim"]),
            rotary_inv_freq(rotary_spec(spec, cfg["head_dim"])), err_msg=kind)


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every number of the catalog's `config` under the same key; `reduced`
    lists exactly the three that differ, the published counts beside them."""
    cfg = harness.load("configs", "mellum2-12b-ep4")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 24576)
    published = dict(
        hidden_size=2304, num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        moe_intermediate_size=896, num_experts_per_tok=8, sliding_window=1024,
        rms_norm_eps=1e-6, intermediate_size=7168, max_position_embeddings=131072)
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 28 and cfg["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["deployment"]["chips_per_layer"] == 4
    assert {"qk_norm", "router_aux_loss", "weights"} <= set(cfg["assumed"])
    d = mellum_ref.dims(cfg)
    n = sum(int(np.prod(s)) for s in mellum_ref.param_shapes(cfg).values())
    assert abs(n - 595.1e6) < 0.5e6 and d["experts_total"] == 64  # the issue's arithmetic
