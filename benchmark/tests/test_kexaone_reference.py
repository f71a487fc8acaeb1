"""CPU tests of the self-drafting generation cell's benchmark files on the
`_tiny-kexaone` / `_tiny.generate_kexaone` rehearsal files: the loop end to
end, a traced run that reports the new counters, the broken paths that must
read `correct: false`, the control, and the reference's own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (logits and drafts, prefill then verify
steps, the loop against its one-token form under three drafters, the share
test, the bias) are in `tests/test_lm_kexaone.py`, which the repo's tier-1
command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.loops import generate_kexaone
from benchmark.reference import kexaone_ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_kexaone

CELL = "_tiny.generate_kexaone"
COUNTERS = {"experts_touched.lm", "expert_load_max_over_mean.lm",
            "mtp_accept_rate.kexaone", "tokens_per_step.kexaone"}


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
    assert 1.0 <= line["metrics"]["tokens_per_step.kexaone"]["value"] < 1.2
    assert 0 < line["metrics"]["experts_touched.lm"]["value"] <= 4
    # no other cell's metric leaks in, and the device metrics of this one
    # need a device trace by HLO name, which the CPU has not: left out
    assert not [m for m in line["metrics"]
                if m.endswith((".train", ".gen", ".mellum", ".pangu", ".olmo"))]
    assert '"name": "draft_logit_gap"' in p.stdout and '"name": "route_flip_share"' in p.stdout


def test_sound_run_is_correct_and_checks_every_number():
    run = a_run()
    generate_kexaone.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "logit_gap_median", "draft_logit_gap", "greedy_gap", "route_flip_share", "moe_dropped",
        "bad_batches", "compiles_in_window"}
    assert run.counters["lm.verify_steps"] == run.counters["batches_counted"] * 12
    assert run.counters["ring_slots"] == 8 + 1 < 12  # window and a draft: a turn wraps it


def test_the_bias_left_out_of_the_choice_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda probs, k, held, rows, bias=None: real(
        probs, k, held, rows))
    run = a_run()
    generate_kexaone.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_a_window_one_position_too_wide_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import decode_cache

    real = decode_cache.ring_positions
    # every slot reads as one position newer than it is: the window's mask
    # then lets the position just outside it in
    monkeypatch.setattr(decode_cache, "ring_positions", lambda last, ring: real(last, ring) + 1)
    run = a_run()
    generate_kexaone.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_a_second_position_that_misses_its_own_key_is_caught(monkeypatch):
    """A verify step whose rings take its FIRST position alone: the next step
    writes that slot anyway, so a first position stays sound until a draft is
    kept, and it is the second position's logits, compared kept or not, that
    show it at every step (`tests/test_lm_kexaone.py` compares them with the
    reference one by one)."""
    from dalle_pytorch_tpu.models import decode_cache

    real = decode_cache.write_ring
    monkeypatch.setattr(decode_cache, "write_ring", lambda cache, vals, start: real(
        cache, vals if start else {k: v[:, :, :1] for k, v in vals.items()}, start))
    run = a_run()
    generate_kexaone.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_rings_that_are_not_restored_at_a_turns_start_are_caught(monkeypatch):
    """A turn longer than the rings overwrites them; a next turn that goes
    back by the index alone reads the last turn's keys in its first window."""
    from dalle_pytorch_tpu.models import decode_cache

    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, {}))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    run = a_run()
    generate_kexaone.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_a_module_that_forgets_the_prompts_last_state_is_caught(monkeypatch):
    """The module lags the trunk by one position; a turn that starts it from
    zeros in place of the state the prefill kept drafts from another model."""
    from dalle_pytorch_tpu.models import lm

    real = lm.CausalLM.draft_step

    def forgetful(self, next_tokens, hidden, layer_cache=None, start=False):
        if layer_cache is not None and next_tokens.shape[1] == 1:
            hidden = jnp.zeros_like(hidden)
        return real(self, next_tokens, hidden, layer_cache, start)

    monkeypatch.setattr(lm.CausalLM, "draft_step", forgetful)
    run = a_run()
    generate_kexaone.run(run)
    assert not run.correct and "draft_logit_gap" in failed(run)


def test_an_assignment_dropped_past_a_too_small_buffer_is_caught():
    run = a_run()
    run.workload["job"]["model"] = {"moe_buffer_rows": 8}  # a prefill makes ~40 a layer
    generate_kexaone.run(run)
    assert not run.correct and "moe_dropped" in failed(run)


def test_the_control_fails_where_the_program_passes():
    """The reference computed in fp8, the precision below the configuration's
    bf16, put in the program's place, is not correct under the cell's limits
    on any seed, while the program passes; `tests/chip_limits.py` makes the
    same reading on the chip at the cell's sizes."""
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # not a sampler that a broken-path test compiled
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(generate_kexaone.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row
        assert row["moe_dropped"] == 0


def test_documents_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (generate_kexaone.Program(config, workload["job"]) for _ in range(2))
    assert np.array_equal(a.documents, b.documents)
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert a.turn == 2 * 12 and a.max_len == 40 + 24


def test_step_outputs_are_laid_out_by_position():
    """Every step gives the position it stands at its first place's logits,
    its second place's and the draft it fed, kept or not; no step stands at a
    position a kept draft took; the module's are at the last that stayed."""
    lg = {"logits": np.arange(3 * 1 * 2 * 2, dtype=np.float32).reshape(3, 1, 2, 2) + 1,
          "draft": -np.arange(3 * 1 * 2, dtype=np.float32).reshape(3, 1, 2) - 1,
          "at": np.array([[10], [11], [13]]), "accepted": np.array([[0], [1], [0]]),
          "drafted": np.array([[7], [8], [9]])}
    got = generate_kexaone.by_position({"logits": lg}, [0], 10, 15)
    assert got["has_logits"][0].tolist() == [True, True, False, True, False]
    assert got["has_second"][0].tolist() == got["has_logits"][0].tolist()
    assert got["logits"][0, [0, 1, 3], 0].tolist() == [1.0, 5.0, 9.0]
    assert got["second"][0, [0, 1, 3], 0].tolist() == [3.0, 7.0, 11.0]
    assert got["drafted"][0].tolist() == [7, 8, 0, 9, 0]
    # the module's entries are one on: entry j is position doc - 1 + j
    assert got["has_draft"][0].tolist() == [False, True, False, True, True]
    assert got["draft"][0, [1, 3, 4], 0].tolist() == [-1.0, -3.0, -5.0]


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-kexaone")
    tokens = np.random.default_rng(0).integers(0, 64, (2, 40))
    params = kexaone_ref.init_params(cfg, 3)
    drafts = np.random.default_rng(1).integers(0, 64, (2, 20))
    whole = kexaone_ref.forward(cfg, 3, tokens, start=20, params=params, drafts=drafts)
    monkeypatch.setattr(kexaone_ref, "Q_BLOCK", 16)
    monkeypatch.setattr(kexaone_ref, "SIDE_BLOCK", 8)
    kexaone_ref._layer_rows.clear_cache()
    blocks = kexaone_ref.forward(cfg, 3, tokens, start=20, params=params, drafts=drafts)
    for name in ("logits", "draft", "second"):
        np.testing.assert_allclose(whole[name], blocks[name], atol=2e-5)
    # the second stream IS the sequence with the draft put in: its last position's logits
    swapped = tokens.copy()
    swapped[:, 31] = drafts[:, 10]  # entry 10 stands at position 20 + 10 + 1
    alone = kexaone_ref.forward(cfg, 3, swapped[:, :32], start=31, params=params)
    np.testing.assert_allclose(whole["second"][:, 10], alone["logits"][:, 0], atol=2e-5)
    # and a later `start` reads the same positions: only what nothing reads is skipped
    later = kexaone_ref.forward(cfg, 3, tokens, start=30, params=params)
    np.testing.assert_allclose(later["logits"], whole["logits"][:, 10:], atol=2e-5)
    np.testing.assert_allclose(later["draft"], whole["draft"][:, 10:], atol=2e-5)


def test_one_layers_weights_can_be_made_alone():
    cfg = harness.load("configs", "_tiny-kexaone")
    whole = kexaone_ref.init_params(cfg, 5)
    for i in (0, 3, 5):  # the dense layer, a routed one, the module's block
        alone = kexaone_ref.init_layer(cfg, 5, i)
        assert sorted(alone) == sorted(whole["layers"][i])
        for k, v in alone.items():
            np.testing.assert_array_equal(v, whole["layers"][i][k])
    assert float(jnp.abs(whole["layers"][1]["router_b"]).max()) > 0.01  # seeded non-zero


def test_the_reference_imports_nothing_of_the_program():
    text = open(kexaone_ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))
    assert 'default_matmul_precision("highest")' in text


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every number of the catalog's `config` under the same key; `reduced`
    lists exactly the three that differ, the published values beside them."""
    cfg = harness.load("configs", "k-exaone-236b-ep8")
    catalog = {"first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
               "hidden_size": 6144, "intermediate_size": 18432,
               "max_position_embeddings": 262144, "model_type": "exaone_moe",
               "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
               "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
               "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
               "num_hidden_layers": 48, "num_key_value_heads": 8,
               "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
               "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
               "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128,
               "sliding_window_pattern": "LLLG", "tie_word_embeddings": False, "topk_group": 1,
               "vocab_size": 153600}
    differs = [k for k, v in catalog.items() if cfg[k] != v]
    assert sorted(differs) == sorted(cfg["reduced"]) and len(differs) == 3
    assert cfg["published"] == {k: catalog[k] for k in cfg["reduced"]}
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200}
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["layer_types"] == period * 12 and cfg["sliding_windows"] == [128, 128, 128, 0] * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["deployment"]["chips_per_layer"] == 8 and cfg["deployment"]["experts_first"] == 0
    assert {"norm_placement", "qk_norm", "rotary", "router_bias", "mtp", "softmax_scale",
            "weights"} <= set(cfg["assumed"])
    assert cfg["program"]["weights_dtype"] == "bfloat16"
    assert kexaone_ref.n_params(cfg) == 4_543_318_144
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "k-exaone-236b-ep8")
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    cell = harness.load("workloads", "kexaone.decode.16k")["job"]
    assert (cell["document_tokens"], cell["question_tokens"], cell["steps"],
            cell["documents_seed"], cell["weights_seed"]) == (16384, 32, 288, 1, 1)
    # every assignment a verify step can make: rows x 2 positions x 8 choices
    assert cfg["program"]["moe_buffer_rows"] == cell["sessions"] * 2 * 8


def test_every_kexaone_metric_is_declared_and_lists_the_cell():
    """A metric is found by the cell in its `workloads`, not by a suffix of its
    name (PR 46); `test_declarations.py` holds every entry to its file."""
    import re

    from benchmark.tests.test_declarations import PER_LAYER, cell_metrics

    files = cell_metrics("kexaone.decode.16k")
    # the 23 of PR 37 and `compiles_in_window`; 8 of them the cell's own by name
    assert len(files) == 24 and set(files) <= set(PER_LAYER)
    assert sum(n.endswith(".kexaone") for n in files) == 8
    shares = [s["params"]["components"] for n, s in files.items()
              if s["reader"] == "component_share" and not n.startswith("mtp_pct")]
    named = [c for group in shares for c in group]
    assert len(shares) == 10 and len(named) == len(set(named))  # the ten shares add up
    # the three under `setup_s` find the cell's two programs in the compile ledger
    ledger = [s for s in files.values() if s["reader"] == "compile_ledger"]
    assert len(ledger) == 3
    for s in ledger:
        assert all(re.search(s["params"]["program"], p) for p in ("lm_sample", "lm_prefill"))


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_kexaone).failed == 0
    # the issue's arithmetic: 8 KB of full K/V a position a session over two layers
    ops, nbytes = costs_kexaone.global_attend(
        1, 64, 8, 128, 1.0, 2, ["window", "window", "window", "full", "window"], 1)
    assert nbytes == 8192 and ops == 2 * 4 * 2 * 64 * 128
