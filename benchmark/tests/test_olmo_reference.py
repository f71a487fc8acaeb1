"""CPU tests of the hybrid generation cell's benchmark files on the
`_tiny-olmo` / `_tiny.generate_hybrid` rehearsal files: the loop end to end,
a traced run that reports the new counter, the broken paths that must read
`correct: false`, both controls, and the reference's own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (the recurrence, the chunked form,
prefill then cached steps, snapshot and restore, the kernel) are in
`tests/test_lm_hybrid.py`, which the repo's tier-1 command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.loops import generate_hybrid
from benchmark.reference import olmo_hybrid_ref as ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_olmo

CELL = "_tiny.generate_hybrid"


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
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"setup_s", "generate_tokens_per_s"}
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_new_counter():
    p = run_cell(CELL, trace=1)
    line = last_line(p)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert {"state_restored_bytes.lm", "compiles_in_window"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # 4 rows x 6 linear layers x (4 heads x 8 x 24 + a ring of 3 x 160) float32
    assert line["metrics"]["state_restored_bytes.lm"]["value"] == 4 * 6 * (768 + 480) * 4
    # no other cell's metric leaks in, and the device metrics of this one
    # need a device trace by HLO name, which the CPU has not: left out
    assert not [m for m in line["metrics"] if m.endswith((".train", ".gen", ".mellum", ".pangu"))]
    assert '"name": "state_gap"' in p.stdout and '"state_bytes"' in p.stdout


def test_sound_run_is_correct_counts_whole_cycles_and_checks_every_number():
    run = a_run()
    values = generate_hybrid.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "greedy_gap", "state_gap", "bad_batches", "compiles_in_window"}
    counted, done = run.counters["batches_counted"], run.record["batch_done_at"]
    assert counted % 2 == 0 and 0 <= run.counters["batches"] - counted < 2
    assert values["generate_tokens_per_s"] == counted * 4 * 5 / done[counted - 1]


def test_a_turn_that_starts_from_the_last_turns_state_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import decode_cache

    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, None))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    run = a_run()
    generate_hybrid.run(run)
    assert not run.correct and {"state_gap", "logit_gap"} <= failed(run)


def test_beta_without_its_factor_two_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = jax.nn.sigmoid
    monkeypatch.setattr(attention.jax.nn, "sigmoid", lambda x: real(x) / 2)
    run = a_run()
    try:
        generate_hybrid.run(run)
    finally:
        monkeypatch.undo()
    assert not run.correct and "state_gap" in failed(run)


def test_a_token_step_that_forgets_to_decay_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = attention.delta_step
    monkeypatch.setattr(attention, "delta_step",
                        lambda s, q, k, v, alpha, beta: real(s, q, k, v, jnp.ones_like(alpha), beta))
    run = a_run()
    generate_hybrid.run(run)
    assert not run.correct and "state_gap" in failed(run)


def test_both_controls_fail_where_the_program_passes():
    """The reference computed in fp8, and the float32 reference with its
    state rounded to bf16 at every token, each put in the program's place, are
    not correct under the cell's limits on any seed, while the program passes;
    `tests/chip_limits.py` makes the same reading on the chip at the cell's
    sizes."""
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # no program that an earlier test broke
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(generate_hybrid.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert all(row["control"][k] > limits[k] for k in ("logit_gap", "state_gap")), row
        assert row["control_state"]["state_gap"] > limits["state_gap"], row


def test_prompts_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (generate_hybrid.Program(config, workload["job"]) for _ in range(2))
    assert np.array_equal(a.documents, b.documents)
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert not np.array_equal(a.questions(1, 0), a.questions(1, 1))
    assert a.questions(3000000019, 0).max() < config["vocab_size"]


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-olmo")
    tokens = np.random.default_rng(0).integers(0, 96, (2, 24))
    want = ref.forward(cfg, 5, tokens)["logits"]
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    ref._layer_rows.clear_cache()
    np.testing.assert_allclose(ref.forward(cfg, 5, tokens)["logits"], want, atol=1e-4)
    ref._layer_rows.clear_cache()


def test_the_recurrence_is_the_two_forms_the_docstring_gives():
    """alpha (I - beta k k^T) S + beta k v^T, a matrix at a time, against
    `recurrence`'s rank-one form."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    n, h, dk, dv = 9, 2, 4, 6
    q, k = jax.random.normal(ks[0], (n, h, dk)), jax.random.normal(ks[1], (n, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (n, h, dv))
    alpha, beta = jax.random.uniform(ks[3], (n, h)), 2 * jax.random.uniform(ks[4], (n, h))
    o, state = ref.recurrence(q, k, v, alpha, beta)
    s = np.zeros((h, dk, dv))
    for t in range(n):
        for j in range(h):
            kk = np.outer(k[t, j], k[t, j])
            s[j] = alpha[t, j] * (np.eye(dk) - beta[t, j] * kk) @ s[j] + beta[t, j] * np.outer(
                k[t, j], v[t, j])
            np.testing.assert_allclose(o[t, j], s[j].T @ q[t, j], atol=1e-5)
    np.testing.assert_allclose(state, s, atol=1e-5)


def test_one_layers_weights_can_be_made_alone():
    cfg = harness.load("configs", "_tiny-olmo")
    every = ref.init_params(cfg, 9)
    alone = ref.init_layer(cfg, 9, 2)
    for name, leaf in alone.items():
        np.testing.assert_array_equal(leaf, every["layers"][2][name])
    assert not np.array_equal(alone["o_w"], every["layers"][1]["o_w"])
    assert set(every["layers"][3]) == {"qkv_w", "q_norm_g", "k_norm_g", "o_w", "post_attn_g",
                                       "post_ff_g", "gate_w", "up_w", "down_w"}
    stored = dict(cfg, program=dict(cfg["program"], weights_dtype="bfloat16"))
    rounded = ref.init_layer(stored, 9, 2)
    for name in ("o_w", "conv_w"):
        assert np.array_equal(rounded[name],
                              np.asarray(alone[name]).astype(jnp.bfloat16).astype(np.float32))
    for name in ("a_log", "dt_bias", "o_norm_g"):  # float32 in both
        assert np.array_equal(rounded[name], alone[name])
    assert 0.0 <= float(alone["a_log"].min()) and float(alone["a_log"].max()) <= np.log(16.0)
    dt = np.log1p(np.exp(np.asarray(alone["dt_bias"], np.float64)))
    assert 0.001 <= dt.min() and dt.max() <= 0.1 + 1e-6


def test_the_reference_imports_nothing_of_the_program():
    text = open(ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))
    assert 'default_matmul_precision("highest")' in text


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every number of the catalog's `config` under the same key; `reduced`
    lists the one that differs, the published value beside it."""
    cfg = harness.load("configs", "olmo-hybrid-7b-pp2")
    catalog = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
               "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
               "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
               "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
               "layer_types": ["linear_attention"] * 3 + ["full_attention"],
               "linear_num_key_heads": 30, "linear_num_value_heads": 30,
               "linear_key_head_dim": 96, "linear_value_head_dim": 192,
               "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
               "rope_parameters": {"rope_theta": None}}
    catalog["layer_types"] = catalog["layer_types"] * 8
    differs = [k for k, v in catalog.items() if cfg[k] != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32} and cfg["num_hidden_layers"] == 16
    assert cfg["deployment"]["pipeline_stages"] == 2 and cfg["deployment"]["stage"] == 0
    assert cfg["deployment"]["chips_per_layer"] == 1
    assert {"conv_activation", "qk_l2norm", "decay", "output_norm", "state_dtype",
            "norm_placement", "qk_norm", "rotary", "head_dim", "weights"} <= set(cfg["assumed"])
    assert cfg["program"]["weights_dtype"] == "bfloat16" and cfg["program"]["dtype"] == "bfloat16"
    # 12 x 215.56 M + 4 x 185.80 M + 770.70 M
    assert ref.n_params(cfg) == 4_100_788_944
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b-pp2")
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = harness.load("workloads", "olmohybrid.decode.512")["job"]
    assert (cell["document_tokens"], cell["question_tokens"], cell["answer_tokens"],
            cell["documents_seed"], cell["weights_seed"]) == (512, 32, 224, 1, 1)
    assert cell["sessions"] % 8 == 0 and 8 <= cell["sessions"] <= 56


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_olmo).failed == 0
    # one call at the issue's 56 rows: the state once in and once out is 0.31 ms at 819 GB/s
    ops, nbytes = costs_olmo.delta_step(56, 30, 96, 192)
    assert abs(nbytes / 819e9 - 0.307e-3) < 2e-6 and ops / nbytes < 1.0


def test_every_olmo_metric_is_declared_and_lists_the_cell():
    from benchmark.tests.test_declarations import PER_LAYER, cell_metrics

    # found by the cell in a file's `workloads`, not by a suffix (PR 46): PR 33's
    # fourteen, the compile ledger's three, `compiles_in_window`
    files = cell_metrics("olmohybrid.decode.512")
    assert len(files) == 18 and set(files) <= set(PER_LAYER)
    assert sum(n.endswith(".olmo") for n in files) == 6
    shares = [s["params"]["components"] for s in files.values() if s["reader"] == "component_share"]
    named = [c for group in shares for c in group]
    assert len(shares) == 9 and len(named) == len(set(named))  # the nine shares add up
