"""CPU tests of the learned-sparse-attention generation cell's benchmark files
on the `_tiny-deepseek-v32` / `_tiny.generate_deepseek_v32` rehearsal files:
the loop end to end, a traced run that reports the new counters, the broken
paths that must read `correct: false`, the control, and the reference's own
parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (logits of a chunked prefill and cached
steps, the selections, the router, YaRN, the share test) are in
`tests/test_lm_deepseek_v32.py`, which the repo's tier-1 command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.loops import generate_deepseek_v32 as loop
from benchmark.reference import deepseek_v32_ref as ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_deepseek_v32

CELL = "_tiny.generate_deepseek_v32"
COUNTERS = {"experts_touched.lm", "expert_load_max_over_mean.lm",
            "selected_per_row_step.deepseek32"}


def a_run(seconds=0.5, trace=False, seed=4):
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # a broken path is traced anew, and a sound one after it
    run = harness.Run(CELL, seed=seed, seconds=seconds, trace=trace, t0=time.perf_counter())
    run.claim_device()
    return run


def failed(run):
    return {c["name"] for c in run.checks if not c["ok"]}


def test_loop_end_to_end():
    line = last_line(run_cell(CELL, seed=3900000019))  # more than 32 signed bits hold
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "generate_tokens_per_s"}


def test_traced_run_reports_the_new_counters():
    p = run_cell(CELL, trace=1)
    line = last_line(p)
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert COUNTERS | {"compiles_in_window"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["selected_per_row_step.deepseek32"]["value"] == 8
    assert 0 < line["metrics"]["experts_touched.lm"]["value"] <= 8
    # no other cell's metric leaks in, and the device metrics of this one
    # need a device trace by HLO name, which the CPU has not: left out
    assert not [m for m in line["metrics"] if m.endswith((".pangu", ".kexaone", ".olmo"))]
    assert '"name": "select_flip_share"' in p.stdout and '"name": "selected_short"' in p.stdout


def test_sound_run_is_correct_and_checks_every_number(capsys):
    run = a_run()
    loop.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "logit_gap_p99", "logit_gap_median", "greedy_gap", "greedy_gap_worst",
        "route_flip_share", "select_flip_share", "selected_short", "copies_off", "moe_dropped",
        "bad_batches", "compiles_in_window"}
    # a kept row of the greedy batch and ANOTHER of the sampled one: two documents
    gaps = next(l for l in capsys.readouterr().out.splitlines() if l.startswith("[gaps] "))
    assert sorted(json.loads(gaps[len("[gaps] "):])["rows"]) == [0, 1]


def test_a_dense_attend_in_place_of_the_selection_is_caught(monkeypatch):
    """Every live position attended, as the model without its indexer would."""
    from dalle_pytorch_tpu.models import attention

    monkeypatch.setattr(
        attention, "selected_mask",
        lambda scores, lengths, k: (jnp.arange(scores.shape[-1]) < lengths[..., None],
                                    jnp.minimum(lengths, k).astype(jnp.int32)))
    run = a_run()
    loop.run(run)
    assert not run.correct and {"logit_gap", "selected_short"} <= failed(run)


def test_the_newest_positions_in_place_of_the_best_are_caught(monkeypatch):
    """A window of the last 8 positions: the right count, the wrong set."""
    from dalle_pytorch_tpu.models import attention

    def newest(scores, lengths, k):
        at = jnp.arange(scores.shape[-1])
        return ((at < lengths[..., None]) & (at >= lengths[..., None] - k),
                jnp.minimum(lengths, k).astype(jnp.int32))

    monkeypatch.setattr(attention, "selected_mask", newest)
    run = a_run()
    loop.run(run)
    assert not run.correct and "select_flip_share" in failed(run)
    assert "selected_short" not in failed(run)


def test_the_index_key_cached_unrotated_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    real = attention.apply_rotary_half
    # a layer rotates q_r, k_r, q_I and k_I, in that order: the index key's is every
    # fourth call (the shared rotary key has no heads axis either, so the shape does not tell)
    calls = {"n": 0}

    def unrotated(cos, sin, t):
        calls["n"] += 1
        return t if t.ndim == 3 and calls["n"] % 4 == 0 else real(cos, sin, t)

    monkeypatch.setattr(attention, "apply_rotary_half", unrotated)
    run = a_run()
    loop.run(run)
    assert not run.correct and "select_flip_share" in failed(run)


def test_a_choice_without_the_group_limit_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    real = moe.choose
    monkeypatch.setattr(moe, "choose", lambda probs, k, bias=None, groups=(1, 1): real(
        probs, k, bias))
    run = a_run()
    loop.run(run)
    assert not run.correct and "route_flip_share" in failed(run)


def test_the_control_fails_where_the_program_passes():
    """The reference computed in fp8, the precision below the configuration's
    bf16 (and the published indexer's own), put in the program's place, is not
    correct under the cell's limits on any seed, while the program passes;
    `tests/chip_limits.py` makes the same reading on the chip at the cell's
    sizes."""
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # no program a test above traced broken
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(loop.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert any(v > limits[k] for k, v in row["control"].items()), row
        assert row["moe_dropped"] == 0 and row["selected_short"] == 0
        assert row["control"]["select_flip_share"] > 0  # fp8 moves the indexer's order


def test_documents_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (loop.Program(config, workload["job"]) for _ in range(2))
    assert np.array_equal(a.documents, b.documents) and a.documents.shape == (2, 48)
    assert np.array_equal(a.document_of([0, 1, 2, 3]), a.documents[[0, 1, 0, 1]])
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert a.max_len == 56  # 48 + 8 in blocks of 8


def test_set_up_counts_the_copies_that_are_not_their_documents_prefill():
    """`copies_off`: a session's leaf that differs from the prefill it was
    copied from, anywhere over the document, is counted; whole copies read 0."""
    from dalle_pytorch_tpu.models.lm import place_rows

    workload = harness.load("workloads", CELL)
    prog = loop.Program(harness.load("configs", workload["config"]), workload["job"])
    rng = np.random.default_rng(0)
    fresh = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype) if x.ndim > 1 else x,
        prog.mdl.init_cache(1, prog.doc))
    cache = prog.mdl.init_cache(prog.sessions)
    for row in (1, 3):
        cache = place_rows(prog.mdl, cache, fresh, row)
    off = lambda *rows: int(loop._copies_off()(cache, fresh, jnp.asarray(rows, jnp.int32)))
    assert off(1, 3) == 0
    leaves = sum(x.ndim > 1 for x in jax.tree.leaves(fresh))  # two a layer: `rows`, `index_k`
    assert leaves == 2 * prog.d["depth"] and off(0, 1) == leaves  # row 0 was never written
    first = next(iter(cache))
    cache[first]["attn"]["index_k"] = cache[first]["attn"]["index_k"].at[3, prog.doc - 1, 0].add(1)
    assert off(1, 3) == 1


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-deepseek-v32")
    tokens = np.random.default_rng(0).integers(0, 64, (2, 30))
    want = ref.forward(cfg, 5, tokens)
    monkeypatch.setattr(ref, "PAIRS", 4 * 30 * 7)  # blocks of 7 queries
    monkeypatch.setattr(ref, "HEAD_GROUP", 2)
    ref._layer_row.clear_cache()
    got = ref.forward(cfg, 5, tokens)
    ref._layer_row.clear_cache()
    np.testing.assert_allclose(got["logits"], want["logits"], atol=2e-5)
    assert np.array_equal(np.sort(got["selected"], -1), np.sort(want["selected"], -1))


def test_a_short_selection_has_empty_slots_and_a_causal_mask():
    """Query t of a sequence attends min(topk, t + 1) positions, none after it."""
    cfg = harness.load("configs", "_tiny-deepseek-v32")
    tokens = np.random.default_rng(1).integers(0, 64, (1, 20))
    selected = ref.forward(cfg, 5, tokens)["selected"][0, 0]  # [n, topk]
    for t, picked in enumerate(selected):
        held = picked[picked >= 0]
        assert len(held) == min(8, t + 1) == len(set(held)) and held.max() <= t


def test_one_layers_weights_can_be_made_alone():
    cfg = harness.load("configs", "_tiny-deepseek-v32")
    every = ref.init_params(cfg, 9)
    alone = ref.init_layer(cfg, 9, 2)
    for name, leaf in alone.items():
        assert np.array_equal(leaf, every["layers"][2][name]), name
    assert float(jnp.abs(alone["router_b"]).max()) > 0 and "router_b" not in every["layers"][0]
    count = sum(x.size for x in jax.tree.leaves(every))
    assert count == ref.n_params(cfg)


def test_the_reference_imports_nothing_of_the_program():
    text = open(ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every number of the catalog's `config` under the same key; `reduced`
    lists exactly the five that differ, the published values beside them."""
    cfg = harness.load("configs", "deepseek-v32-exp-ep16")
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
        "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280}
    differ = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: catalog[k] for k in cfg["reduced"]} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (5, 1, 16, 16160, 0)
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    assert round(ref.n_params(cfg) / 1e6) == 4636


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_deepseek_v32).failed == 0
    shapes = dict(batch=16, heads=128, kv_rank=512, rope=64, index_heads=64, index_dim=128,
                  index_topk=2048, positions=32912.5, kinds=["dense"] + ["routed"] * 4)
    dense_ops = 5 * 2.0 * 16 * 128 * (2 * 512 + 64) * 32912.5
    ops, nbytes = costs_deepseek_v32.sparse_attend(**shapes)
    assert ops == dense_ops * 2048 / 32912.5 and nbytes == 5 * 16 * 2048 * 576 * 2
    assert costs_deepseek_v32.index_score(**shapes)[1] == 5 * 16 * 32912.5 * 260
