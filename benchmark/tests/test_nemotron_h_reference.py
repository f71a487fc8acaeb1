"""CPU tests of the state-space hybrid generation cell's benchmark files on
the `_tiny-nemotron-h` / `_tiny.generate_nemotron_h` rehearsal files: the loop
end to end (rows of three lengths in one cache), the broken paths that must
read `correct: false`, both controls, and the reference's own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (the recurrence, the chunked form,
prefill then per-row cached steps, snapshot and restore, the kernel, the two
halves of the experts) are in `tests/test_lm_nemotron_h.py`,
`tests/test_lm_nemotron_h_turns.py` and `tests/test_ssm_step.py`, which the
repo's tier-1 command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.loops import generate_nemotron_h
from benchmark.reference import nemotron_h_ref as ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_nemotron_h

CELL = "_tiny.generate_nemotron_h"
REAL = "nemotron3.decode.8k"


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


def test_sound_run_is_correct_counts_whole_cycles_and_checks_every_number():
    run = a_run()
    values = generate_nemotron_h.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "logit_gap_median", "greedy_gap", "state_gap", "route_flip_share",
        "moe_dropped", "bad_batches", "compiles_in_window"}
    counted, done = run.counters["batches_counted"], run.record["batch_done_at"]
    assert counted % 2 == 0 and 0 <= run.counters["batches"] - counted < 2
    assert values["generate_tokens_per_s"] == counted * 6 * 9 / done[counted - 1]
    # 6 rows x 4 Mamba-2 layers x (16 x 64 + a ring of 3 x 128) float32, restored a turn
    assert run.counters["state_restored_bytes"] == 6 * 4 * (1024 + 384) * 4
    assert run.shapes["gmm_calls"] == 8 and run.shapes["ssm_layers"] == 4
    assert run.shapes["positions"] == (20 + 70 + 37) / 3 + 6.5


def test_a_turn_that_starts_from_the_last_turns_state_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import decode_cache

    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, None))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    run = a_run()
    generate_nemotron_h.run(run)
    assert not run.correct and {"state_gap", "logit_gap"} <= failed(run)


def test_a_token_step_that_forgets_to_decay_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = attention.ssm_step_operands
    monkeypatch.setattr(attention, "ssm_step_operands",
                        lambda x, dt, a, d: real(x, dt, jnp.zeros_like(a), d))
    run = a_run()
    generate_nemotron_h.run(run)
    assert not run.correct and "state_gap" in failed(run)


def test_experts_with_a_silu_where_the_square_belongs_are_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    monkeypatch.setattr(moe, "_relu2", lambda up: jax.nn.silu(up))
    run = a_run()
    generate_nemotron_h.run(run)
    assert not run.correct and "logit_gap_median" in failed(run)


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
    rows = list(generate_nemotron_h.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert all(row["control"][k] > limits[k] for k in ("logit_gap_median", "state_gap")), row
        assert row["control_state"]["state_gap"] > limits["state_gap"], row


def test_documents_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (generate_nemotron_h.Program(config, workload["job"]) for _ in range(2))
    assert [d.shape for d in a.documents] == [(2, 20), (2, 70), (2, 37)]
    assert all(np.array_equal(x, y) for x, y in zip(a.documents, b.documents))
    assert a.doc.tolist() == [20, 70, 37, 20, 70, 37] and a.max_len == 70 + 12
    assert np.array_equal(a.document(4), a.documents[1][1])
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert not np.array_equal(a.questions(1, 0), a.questions(1, 1))
    assert a.questions(3000000019, 0).max() < config["vocab_size"]


def test_the_reference_does_not_depend_on_its_blocks_or_on_how_rows_are_grouped(monkeypatch):
    cfg = harness.load("configs", "_tiny-nemotron-h")
    tokens = np.random.default_rng(0).integers(0, 64, (2, 24))
    want = ref.forward(cfg, 5, tokens, start=20)
    both = ref.forward(cfg, 5, [tokens[:1], tokens[1:, :17]], start=[20, 13])
    np.testing.assert_allclose(both["logits"][0][0], want["logits"][0], atol=1e-5)
    np.testing.assert_allclose(both["state"][0][0], want["state"][0], atol=1e-6)
    assert both["logits"][1].shape == (1, 4, 64) and both["choices"][1].shape == (1, 4, 2)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    ref._layer_rows.clear_cache()
    np.testing.assert_allclose(ref.forward(cfg, 5, tokens, start=20)["logits"], want["logits"],
                               atol=1e-4)
    ref._layer_rows.clear_cache()


def test_the_recurrence_is_the_equations_a_matrix_at_a_time():
    """S_t = exp(dt A) S + B (dt x)^T and y = S^T C + D x, with numpy, head by
    head, head i reading group i // (H / G)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    n, h, p, g, s = 7, 4, 3, 2, 5
    x, dt = jax.random.normal(ks[0], (n, h, p)), jax.random.uniform(ks[1], (n, h))
    b, c = jax.random.normal(ks[2], (n, g, s)), jax.random.normal(ks[3], (n, g, s))
    a, d = -jax.random.uniform(ks[4], (h,)), jax.random.normal(ks[5], (h,))
    y, state = ref.recurrence(x, dt, a, b, c, d)
    mat = np.zeros((h, s, p))
    for t in range(n):
        for j in range(h):
            mat[j] = np.exp(dt[t, j] * a[j]) * mat[j] + np.outer(b[t, j // 2], dt[t, j] * x[t, j])
            np.testing.assert_allclose(y[t, j], mat[j].T @ c[t, j // 2] + d[j] * x[t, j], atol=1e-5)
    np.testing.assert_allclose(state, mat, atol=1e-5)


def test_one_layers_weights_can_be_made_alone():
    cfg = harness.load("configs", "_tiny-nemotron-h")
    every = ref.init_params(cfg, 9)
    alone = ref.init_layer(cfg, 9, 2)
    for name, leaf in alone.items():
        np.testing.assert_array_equal(leaf, every["layers"][2][name])
    assert not np.array_equal(alone["out_w"], every["layers"][0]["out_w"])
    assert set(every["layers"][5]) == {"norm_g", "q_w", "k_w", "v_w", "o_w"}
    assert set(every["layers"][1]) == {"norm_g", "router_w", "router_b", "up_w", "down_w",
                                       "sh_up_w", "sh_down_w"}
    stored = dict(cfg, program=dict(cfg["program"], weights_dtype="bfloat16"))
    rounded = ref.init_layer(stored, 9, 2)
    for name in ("in_w", "out_w", "conv_w"):
        assert np.array_equal(rounded[name],
                              np.asarray(alone[name]).astype(jnp.bfloat16).astype(np.float32))
    for name in ("a_log", "dt_bias", "conv_b", "skip_g", "gate_norm_g"):  # float32 in both
        assert np.array_equal(rounded[name], alone[name])
    dt = np.log1p(np.exp(np.asarray(alone["dt_bias"], np.float64)))
    assert 0.001 - 1e-6 <= dt.min() and dt.max() <= 0.1 + 1e-6


def test_the_reference_imports_nothing_of_the_program():
    text = open(ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))
    assert 'default_matmul_precision("highest")' in text


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every key of the catalog's `config` under the same name; `reduced`
    lists the four that differ, the published values beside them."""
    cfg = harness.load("configs", "nemotron3-nano-30b-ep2")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(row for row in map(json.loads, f)
                       if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    differs = [k for k, v in catalog["config"].items() if cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"])
    assert cfg["published"] == {k: catalog["config"][k] for k in cfg["reduced"]}
    assert cfg["source"] == catalog["source_url"]
    assert cfg["hybrid_override_pattern"] == catalog["config"]["hybrid_override_pattern"][:9]
    assert (cfg["deployment"]["chips_per_layer"], cfg["deployment"]["experts_first"]) == (2, 0)
    assert cfg["program"]["weights_dtype"] == "bfloat16" and cfg["program"]["dtype"] == "bfloat16"
    # 4 x 38.74 M + 23.40 M + 4 x 658.9 M + 352.3 M; the published model: 31.58 B
    assert ref.n_params(cfg) == 3_166_244_352
    whole = dict(cfg, **cfg["published"], published={})
    assert ref.n_params(whole) == 31_577_940_288
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "nemotron3-nano-30b-ep2")
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    job = harness.load("workloads", REAL)["job"]
    assert (job["sessions"], sorted(job["document_tokens"]), job["question_tokens"],
            job["answer_tokens"], job["documents_seed"], job["weights_seed"]) == (
        192, [2048, 4096, 8192], 32, 224, 1, 1)
    assert job["document_tokens"][:2] == [2048, 8192]  # the checked rows: shortest, longest


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_nemotron_h).failed == 0
    # a step's state traffic at the cell's 192 rows: 4 layers x 819 MB, 4.0 ms at 819 GB/s
    ops, nbytes = costs_nemotron_h.ssm_step(192, 64, 64, 128, 8, 4)
    assert abs(nbytes / 819e9 - 4.0e-3) < 5e-5 and ops / nbytes < 1.0


def test_every_nemotron_metric_is_declared_and_lists_the_cell():
    from benchmark.tests.test_declarations import DECLARED as bench, PER_LAYER as declared, cell_metrics

    # found by the cell in a file's `workloads`, not by a suffix (PR 46): PR 43's
    # ten, the thirteen the cap kept out, `compiles_in_window`
    files = cell_metrics(REAL)
    assert len(files) == 24 and set(files) <= set(declared)
    assert sorted(n for n in files if n.endswith(".nemotron3")) == [
        "global_attend_roofline.nemotron3", "gmm_roofline.nemotron3", "mfu_token_step.nemotron3",
        "ssm_proj_pct.nemotron3", "ssm_step_pct.nemotron3", "ssm_step_roofline.nemotron3"]
    shares = [s["params"]["components"] for s in files.values() if s["reader"] == "component_share"]
    named = [c for group in shares for c in group]
    assert len(shares) == 11 and len(named) == len(set(named))  # the eleven shares add up
    # every component the cell's `[scopes]` line showed above 0 is in one of them (PR 43, call 2)
    assert set(named) >= {
        "attn_glue", "attn_proj", "cache_write", "embed", "global_attend", "head", "moe_dispatch",
        "moe_experts", "moe_router", "moe_shared", "norm_resid", "sample", "ssm_proj", "ssm_step",
        "unscoped"}
    rates = next(m for m in bench["end_to_end"] if m["name"] == "generate_tokens_per_s")
    assert rates["workloads"][-1] == REAL
    assert declared["compiles_in_window"]["workloads"][-1] == REAL  # every cell reports it
