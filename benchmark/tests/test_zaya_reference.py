"""CPU tests of the convolved-latent generation cell's benchmark files on the
`_tiny-zaya` / `_tiny.generate_zaya` rehearsal files: the loop end to end, the
broken paths that must read `correct: false`, the control, and the reference's
own parts.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. The
program-against-reference comparisons (the full forward, prefill then per-row
cached steps, a prefill in chunks, snapshot and restore, every mechanism left
out, the experts' shares) are in `tests/test_lm_zaya.py` and
`tests/test_lm_zaya_turns.py`, which the repo's tier-1 command collects.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.loops import generate_zaya
from benchmark.reference import zaya_ref as ref
from benchmark.tests.test_harness import RESULT_KEYS, last_line, run_cell
from benchmark.trace import costs_zaya

CELL = "_tiny.generate_zaya"
REAL = "zaya1.decode.8k"


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
    values = generate_zaya.run(run)
    assert run.correct, run.checks
    assert {c["name"] for c in run.checks} >= {
        "logit_gap", "logit_gap_median", "greedy_gap", "route_flip_share", "moe_dropped",
        "bad_batches", "compiles_in_window"}
    counted, done = run.counters["batches_counted"], run.record["batch_done_at"]
    assert counted % 2 == 0 and 0 <= run.counters["batches"] - counted < 2
    assert values["generate_tokens_per_s"] == counted * 4 * 9 / done[counted - 1]
    # 4 rows x 3 layers x a tail of (2 x 6 + 1) x 16 float32, restored a turn
    assert run.counters["state_restored_bytes"] == 4 * 3 * 13 * 16 * 4
    assert run.shapes["gmm_calls"] == 9 and run.shapes["positions"] == 40 + 6.5
    assert run.counters["moe_rows_mean"] == 4.0  # ONE expert a row a layer a step


def test_a_turn_that_starts_from_the_last_turns_tail_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import decode_cache

    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, None))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    run = a_run()
    generate_zaya.run(run)
    assert not run.correct and "logit_gap" in failed(run)


def test_a_router_that_forgets_the_layer_before_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    real = moe.RoutedExperts.mlp_router_probs
    monkeypatch.setattr(moe.RoutedExperts, "mlp_router_probs",
                        lambda self, h2d, carried=None: real(self, h2d, None))
    run = a_run()
    generate_zaya.run(run)
    assert not run.correct and "logit_gap_median" in failed(run)


def test_values_that_are_not_shifted_are_caught(monkeypatch):
    from dalle_pytorch_tpu.models import attention

    monkeypatch.setattr(attention, "_shifted_values", lambda vf, last, now: vf)
    run = a_run()
    generate_zaya.run(run)
    assert not run.correct and "logit_gap_median" in failed(run)


def test_a_renormalised_gate_is_caught(monkeypatch):
    from dalle_pytorch_tpu.models import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda *a, **kw: real(*a, **{**kw, "renormalise": True}))
    run = a_run()
    generate_zaya.run(run)
    assert not run.correct and "logit_gap_median" in failed(run)


def test_the_control_fails_where_the_program_passes():
    """The reference computed in fp8, put in the program's place, is not
    correct under the cell's limits on any seed, while the program passes;
    `tests/chip_limits.py` makes the same reading on the chip at the cell's
    sizes."""
    from dalle_pytorch_tpu.models import dalle

    dalle._jitted_sampler.cache_clear()  # no program that an earlier test broke
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    limits = workload["check"]["limits"]
    rows = list(generate_zaya.readings(workload, config, [11, 12, 13], 3))
    assert len(rows) == 3
    for row in rows:
        assert all(v <= limits[k] for k, v in row["program"].items()), row
        assert row["control"]["logit_gap_median"] > limits["logit_gap_median"], row


def test_documents_and_weights_are_the_jobs_and_questions_the_seeds():
    workload = harness.load("workloads", CELL)
    config = harness.load("configs", workload["config"])
    a, b = (generate_zaya.Program(config, workload["job"]) for _ in range(2))
    assert a.documents.shape == (4, 40) and np.array_equal(a.documents, b.documents)
    assert not np.array_equal(a.questions(1, 0), a.questions(2, 0))
    assert not np.array_equal(a.questions(1, 0), a.questions(1, 1))
    assert a.questions(3000000019, 0).max() < config["vocab_size"]
    assert a.mdl.seq_len == 40 + 12 and a.mdl.tied_head


def test_the_reference_does_not_depend_on_its_blocks(monkeypatch):
    cfg = harness.load("configs", "_tiny-zaya")
    tokens = np.random.default_rng(0).integers(0, 96, (2, 24))
    want = ref.forward(cfg, 5, tokens, start=20)
    assert want["logits"].shape == (2, 4, 96) and want["choices"].shape == (2, 4, 1)
    monkeypatch.setattr(ref, "Q_BLOCK", 8)
    ref._layer_rows.clear_cache()
    np.testing.assert_allclose(ref.forward(cfg, 5, tokens, start=20)["logits"], want["logits"],
                               atol=1e-4)
    ref._layer_rows.clear_cache()


def test_the_latents_are_the_equations_a_position_at_a_time():
    """c' and c'' by loops over positions, heads and taps, the q-k mean and the
    unit norm with tau, with numpy."""
    cfg = harness.load("configs", "_tiny-zaya")
    d, lp = ref.dims(cfg), {k: np.asarray(v, np.float64) for k, v in ref.init_layer(cfg, 3, 0).items()}
    n, H, K, dh = 5, d["heads"], d["kv_heads"], d["head_dim"]
    u = np.random.default_rng(1).normal(size=(n, d["dim"]))
    q, k, v = (np.asarray(t) for t in ref.latents(
        jnp.asarray(u, jnp.float32), {k_: jnp.asarray(x, jnp.float32) for k_, x in lp.items()}, d))
    qt, kt, vf = u @ lp["q_w"], u @ lp["k_w"], (u @ lp["v_w"]).reshape(n, K, dh)
    c = np.concatenate([qt, kt], -1)
    zero = lambda t, i: t[i] if i >= 0 else np.zeros_like(t[0])
    for t in range(n):
        c1 = lambda i: (lp["conv0_b"] + lp["conv0_w"][1] * zero(c, i) + lp["conv0_w"][0] * zero(c, i - 1)
                        if i >= 0 else np.zeros_like(c[0]))
        for g in range(H + K):
            cut = slice(g * dh, (g + 1) * dh)
            c2 = (lp["conv1_b"][cut] + c1(t)[cut] @ lp["conv1_w"][1, g]
                  + c1(t - 1)[cut] @ lp["conv1_w"][0, g])
            if g < H:
                mean = (qt[t, cut] + kt[t, (g // (H // K)) * dh:(g // (H // K) + 1) * dh]) / 2
                got, scale = q[t, g], 1.0
            else:
                j = g - H
                heads = [qt[t, h * dh:(h + 1) * dh] for h in range(j * (H // K), (j + 1) * (H // K))]
                mean = (np.mean(heads, 0) + kt[t, j * dh:(j + 1) * dh]) / 2
                got, scale = k[t, j], lp["tau_g"][j]
            x = c2 + mean
            np.testing.assert_allclose(got, scale * x / np.sqrt(np.mean(x * x) + d["eps"]), atol=2e-5)
        np.testing.assert_allclose(v[t, 0], vf[t, 0], atol=1e-5)  # this position's
        np.testing.assert_allclose(v[t, 1], zero(vf, t - 1)[1] if t else 0 * vf[0, 1], atol=1e-5)


def test_one_layers_weights_can_be_made_alone_and_none_is_neutral():
    cfg = harness.load("configs", "_tiny-zaya")
    every = ref.init_params(cfg, 9)
    alone = ref.init_layer(cfg, 9, 2)
    for name, leaf in alone.items():
        np.testing.assert_array_equal(leaf, every["layers"][2][name])
    assert not np.array_equal(alone["o_w"], every["layers"][0]["o_w"])
    assert set(every["top"]) == {"emb", "final_norm_g"}  # the head is the embedding
    stored = dict(cfg, program=dict(cfg["program"], weights_dtype="bfloat16"))
    rounded = ref.init_layer(stored, 9, 2)
    for name in ("q_w", "o_w", "conv0_w", "conv1_w", "gate_w"):
        assert np.array_equal(rounded[name],
                              np.asarray(alone[name]).astype(jnp.bfloat16).astype(np.float32))
    for name in ("tau_g", "gamma", "beta", "attn_res", "rd_w", "r3_w", "conv1_b"):  # float32 in both
        assert np.array_equal(rounded[name], alone[name])
    for name, neutral in (("tau_g", 1.0), ("gamma", 0.0), ("beta", 0.0), ("conv0_b", 0.0),
                          ("conv1_b", 0.0), ("rd_b", 0.0)):
        assert np.abs(np.asarray(alone[name]) - neutral).min() > 0, name
    a, b, c, e = np.asarray(alone["ff_res"])
    assert np.abs(a - 1).max() < 0.6 and np.abs(c - 1).max() < 0.6 and np.abs(b).max() < 0.06
    assert min(np.abs(a - 1).min(), np.abs(b).min(), np.abs(c - 1).min(), np.abs(e).min()) > 0


def test_the_reference_imports_nothing_of_the_program():
    text = open(ref.__file__).read()
    assert "dalle_pytorch_tpu" not in "".join(
        line for line in text.splitlines() if line.startswith(("import", "from")))
    assert 'default_matmul_precision("highest")' in text


def test_the_configuration_file_holds_the_published_config_but_for_the_cut():
    """Every key of the catalog's `config` under the same name; `reduced` lists
    the one that differs, the published value beside it."""
    cfg = harness.load("configs", "zaya1-8b-pp2")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(row for row in map(json.loads, f) if row["name"] == "ZAYA1-8B")
    differs = [k for k, v in catalog["config"].items() if cfg[k] != v]
    assert differs == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": catalog["config"]["num_hidden_layers"]}
    assert cfg["source"] == catalog["source_url"] and cfg["num_hidden_layers"] == 20
    assert (cfg["deployment"]["pipeline_stages"], cfg["deployment"]["layers"]) == (2, [0, 19])
    assert "final norm and the head" in cfg["deployment"]["how"]
    assert cfg["program"]["weights_dtype"] == "bfloat16" and cfg["program"]["dtype"] == "bfloat16"
    for item in ("convolutions", "qk_mean", "qk_norm", "value_shift", "router", "choice_and_gate",
                 "residual", "no_skip_choice"):
        assert item in cfg["assumed"], item
    # 20 x 207.58 M + 537.1 M here; the published 40 layers: 8.30 B, 8.84 B with the embedding
    assert ref.n_params(cfg) == 4_688_805_224
    assert ref.n_params(cfg, 40, embedding=False) == 8_303_340_240
    assert ref.n_params(cfg, 40) == 8_840_475_344
    with open(harness.ROOT.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "zaya1-8b-pp2")
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    job = harness.load("workloads", REAL)["job"]
    assert (job["sessions"], job["document_tokens"], job["question_tokens"], job["answer_tokens"],
            job["documents_seed"], job["weights_seed"]) == (24, 8192, 32, 224, 1, 1)
    assert cfg["program"]["moe_buffer_rows"] == job["prefill_tokens"] * cfg["num_experts_per_tok"]


def test_the_cost_functions_count_what_their_docstrings_say():
    import doctest

    assert doctest.testmod(costs_zaya).failed == 0
    # a step's K/V traffic at the cell's 24 rows x 8,320 live positions x 20 layers: 4.09 GB
    ops, nbytes = costs_zaya.cca_attend(24, 8, 2, 128, 8320.5, 20)
    assert abs(nbytes - 4.09e9) < 2e7 and ops / nbytes < 20
    # the head alone is 24 x 2048 x 262272 x 2 of a step's operations
    flops = costs_zaya.token_step_flops(24, 2048, 20, 8, 2, 128, 262272, 2048, 16, 256, 8320.5, 24.0)
    assert 0.4 < 2.0 * 24 * 2048 * 262272 / flops < 0.6


def test_every_zaya_metric_is_declared_and_the_shares_add_up():
    from benchmark.tests.test_declarations import PER_LAYER as declared, cell_metrics

    files = cell_metrics(REAL)
    assert len(files) == 23 and set(files) <= set(declared)  # 22 of its own and `compiles_in_window`
    assert sorted(n for n in files if not n.endswith(".zaya1")) == ["compiles_in_window"]
    shares = [s["params"]["components"] for s in files.values() if s["reader"] == "component_share"]
    named = [c for group in shares for c in group]
    assert len(shares) == 11 and len(named) == len(set(named))  # the eleven shares add up
    # every component the family's sampler runs (tests/test_scopes.py) is in one of them
    assert set(named) >= {"global_attend", "cca_mix", "attn_proj", "attn_glue", "router_mlp",
                          "moe_experts", "moe_dispatch", "head", "ff", "cache_write",
                          "state_restore", "sample", "norm_resid", "embed", "unscoped"}
    own = sorted(n for n, s in files.items() if "copy_of" not in s.get("params", {}))
    assert own == ["cca_attend_roofline.zaya1", "cca_mix_pct.zaya1", "compiles_in_window",
                   "gmm_roofline.zaya1", "mfu_token_step.zaya1", "program_cache_load_s.zaya1",
                   "program_compile_s.zaya1", "program_trace_lower_s.zaya1",
                   "router_mlp_pct.zaya1"]
