"""CPU tests of the language-model generation cell's benchmark files on the
`_tiny-pangu` / `_tiny.generate_lm` rehearsal files: the loop end to end, a
traced run that reports the new counters, the broken paths that must read
`correct: false`, the control, and the reference's own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (logits, prefill then cached steps,
the latent decode kernel, the share test) are in `tests/test_lm_decode.py`,
which the repo's tier-1 command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.loops import generate_lm
from benchmark.reference import pangu_ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_pangu

CELL = "_tiny.generate_lm"
COUNTERS = {"experts_touched.lm", "expert_load_max_over_mean.lm"}


def a_run(seconds=0.5, trace=False, seed=4):
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # a broken path is traced anew, and a sound one after it
    run = harness.Run(CELL, seed=seed, seconds=seconds, trace=trace, t0=time.perf_counter())
    run.claim_device()
    return run


def failed(run):
    return {c["name"] for c in run.checks if not c["ok"]}


def test_loop_end_to_end():
    line = last_line(run_cell(CELL, seed=3000000019))  # more than 32 signed bits hold
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "generate_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_new_counters():
    p = run_cell(CELL, trace=1)
    line = last_line(p)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert COUNTERS | {"compiles_in_window"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["metrics"]["experts_touched.lm"]["value"] <= 4
    assert line["metrics"]["expert_load_max_over_mean.lm"]["value"] >= 1.0
    # no other cell's metric leaks in, and the device metrics of this one
    # need a device trace by HLO name, which the CPU has not: left out
    assert not [m for m in line["metrics"] if m.endswith((".train", ".gen", ".mellum"))]
    assert '"name": "moe_dropped"' in p.stdout and '"name": "route_flip_share"' in p.stdout


def test_sound_run_is_correct_and_checks_every_number():
    run = a_run()
    generate_lm.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "greedy_gap", "route_flip_share", "moe_dropped", "bad_batches",
        "compiles_in_window"}


def test_the_shared_expert_left_out_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    monkeypatch.setattr(moe.RoutedExperts, "shared", lambda self, h: jnp.zeros_like(h))
    run = a_run()
    generate_lm.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_the_rotary_key_cached_unrotated_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = attention.apply_rotary_half
    # the shared key is the one operand without a heads axis
    monkeypatch.setattr(attention, "apply_rotary_half",
                        lambda cos, sin, t: t if t.ndim == 3 else real(cos, sin, t))
    run = a_run()
    generate_lm.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_softmax_scores_in_place_of_sigmoid_are_caught(monkeypatch):
    monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jax.nn.softmax(x, axis=-1))
    run = a_run()
    try:
        generate_lm.run(run)
    finally:
        monkeypatch.undo()
    assert not run.correct and "logit_gap" in failed(run)


def test_an_assignment_dropped_past_a_too_small_buffer_is_caught():
    run = a_run()
    run.workload["job"]["model"]["moe_buffer_rows"] = 8  # a prefill makes ~24 a layer
    generate_lm.run(run)
    assert not run.correct and "moe_dropped" in failed(run)


def test_the_control_fails_where_the_program_passes():
    """The reference computed in fp8, the precision below the configuration's
    bf16, put in the program's place, is not correct under the cell's limits
    on any seed, while the program passes; `tests/chip_limits.py` makes the
    same reading on the chip at the cell's sizes."""
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(generate_lm.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row
        assert row["moe_dropped"] == 0


def test_documents_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (generate_lm.Program(config, workload["job"]) for _ in range(2))
    assert np.array_equal(a.documents, b.documents)
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert not np.array_equal(a.questions(1, 0), a.questions(1, 1))
    assert a.questions(3000000019, 0).max() < config["vocab_size"]


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-pangu")
    tokens = np.random.default_rng(0).integers(0, 64, (2, 24))
    want = pangu_ref.forward(cfg, 5, tokens)["logits"]
    monkeypatch.setattr(pangu_ref, "Q_BLOCK", 8)
    pangu_ref._layer_rows.clear_cache()
    np.testing.assert_allclose(pangu_ref.forward(cfg, 5, tokens)["logits"], want, atol=1e-5)
    pangu_ref._layer_rows.clear_cache()


def test_one_layers_weights_can_be_made_alone():
    cfg = harness.load("configs", "_tiny-pangu")
    every = pangu_ref.init_params(cfg, 9)
    alone = pangu_ref.init_layer(cfg, 9, 2)
    for name, leaf in alone.items():
        np.testing.assert_array_equal(leaf, every["layers"][2][name])
    assert not np.array_equal(alone["o_w"], every["layers"][1]["o_w"])
    stored = dict(cfg, program=dict(cfg["program"], weights_dtype="bfloat16"))
    rounded = pangu_ref.init_layer(stored, 9, 2)
    assert np.array_equal(rounded["o_w"], alone["o_w"].astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(rounded["router_w"], alone["router_w"])  # float32 in both
    assert np.array_equal(rounded["norm_ff_g"], alone["norm_ff_g"])


def test_the_reference_imports_nothing_of_the_program():
    text = open(pangu_ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))
    assert 'default_matmul_precision("highest")' in text


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every number of the catalog's `config` under the same key; `reduced`
    lists exactly the five that differ, the published values beside them."""
    cfg = harness.load("configs", "pangu-ultra-moe-ep16")
    catalog = {"attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
               "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
               "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
               "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
               "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 61, "num_key_value_heads": 128,
               "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 25600000,
               "routed_scaling_factor": 2.5, "sandwich_norm": True,
               "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
    differs = [k for k, v in catalog.items() if cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"]) and len(differs) == 5
    assert cfg["published"] == {k: catalog[k] for k in cfg["reduced"]}
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
        "vocab_size": 19200, "num_nextn_predict_layers": 0}
    assert cfg["deployment"]["chips_per_layer"] == 16
    assert {"scoring", "grouping", "sandwich_norm", "latent_norms", "rotary", "softmax_scale",
            "weights", "mtp"} <= set(cfg["assumed"])
    assert cfg["program"]["weights_dtype"] == "bfloat16" and cfg["program"]["moe_buffer_rows"] == 512
    assert pangu_ref.n_params(cfg) == 4_919_139_840
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "pangu-ultra-moe-ep16")
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = harness.load("workloads", "pangu.decode.8k")["job"]
    assert (cell["sessions"], cell["document_tokens"], cell["question_tokens"],
            cell["answer_tokens"], cell["documents_seed"], cell["weights_seed"]) == (
        64, 8192, 32, 256, 1, 1)


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_pangu).failed == 0
    # the issue's arithmetic for one layer of the cell: 242 FLOP a byte of cache
    ops, nbytes = costs_pangu.latent_attend(64, 128, 512, 64, 8336.0)
    assert abs(ops / nbytes - 241.8) < 0.5 and abs(nbytes - 0.6146e9) < 1e6
