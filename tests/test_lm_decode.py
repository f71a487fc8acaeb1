"""Generation with the latent-attention language model, at a small size on
the CPU (`benchmark/configs/_tiny-pangu.json`: hidden 64, 4 heads, q rank 24,
kv rank 16, nope 8 + rope 8, v 8, one dense layer + two routed, 8 experts of
which 4 are held with 2 a token + 1 shared, vocabulary 64), float32, against
the plain reference (`benchmark/reference/pangu_ref.py`). Kernels interpreted."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_pangu
from benchmark.reference import pangu_ref
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 32, 7


def _cfg(name="_tiny-pangu"):
    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, N + 8)
    return mdl, build_pangu.seeded_variables(cfg, mdl, SEED)


def _tokens(rows=2, seed=0, n=N, vocab=64):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


@pytest.mark.parametrize("attn_impl", ["dense", "flash"])
def test_logits_match_the_reference(cfg, attn_impl):
    mdl = CausalLM.from_config(cfg, N, attn_impl=attn_impl)
    variables = build_pangu.seeded_variables(cfg, mdl, SEED)
    tokens = _tokens()
    want = pangu_ref.forward(cfg, SEED, tokens)
    np.testing.assert_allclose(mdl.apply(variables, tokens), want["logits"], atol=3e-5)
    layer = pangu_ref.dims(cfg)["kinds"].index("routed")
    got = mdl.apply(variables, tokens, layer, method=CausalLM.route_choices)
    assert np.array_equal(np.sort(got, -1), np.sort(want["choices"], -1))


@pytest.mark.parametrize("prefilled", [24, 1])
def test_prefill_then_cached_steps_match_the_uncached_forward(cfg, pair, prefilled):
    """The absorbed form over the latent cache against the expanded one."""
    mdl, variables = pair
    tokens = _tokens(seed=1)
    full = mdl.apply(variables, tokens)
    cache, counts = prefill_cached(mdl, variables, tokens[:, :prefilled], mdl.init_cache(2))
    assert int(counts["moe_dropped"].sum()) == 0
    steps = N - prefilled
    toks, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, tokens[:, prefilled:], steps,
        filter_thres=1.0, logit_rows=2, start=prefilled)
    np.testing.assert_allclose(
        np.asarray(logits).transpose(1, 0, 2), full[:, prefilled:], atol=3e-5)
    assert np.array_equal(toks, np.asarray(full[:, prefilled:]).argmax(-1))  # greedy
    assert all(int(layer["attn"]["index"]) == N for layer in cache.values())
    assert counts["moe_load"].shape == (2, 4) and counts["moe_touched"].shape == (2,)
    assert np.array_equal(counts["moe_load"].sum(-1), counts["moe_rows"])
    assert (counts["moe_touched"] <= 4 * steps).all() and int(counts["moe_dropped"].sum()) == 0


def test_the_sampler_feeds_the_forced_tokens_then_its_own(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=2)
    cache, _ = prefill_cached(mdl, variables, tokens[:, :20], mdl.init_cache(2))
    forced = tokens[:, 20:23]
    toks, logits, _, _ = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(3), cache, forced, 8, filter_thres=0.9,
        temperature=1.0, logit_rows=1, start=20)
    toks = np.asarray(toks)
    assert toks.shape == (2, 8) and logits.shape == (8, 1, 64)
    assert toks.min() >= 0 and toks.max() < 64
    # teacher forcing: what the steps were fed reproduces the logits uncached
    fed = np.concatenate([np.asarray(forced), toks[:, 2:-1]], 1)
    seq = np.concatenate([np.asarray(tokens[:, :20]), fed], 1)
    full = CausalLM.from_config(cfg, seq.shape[1]).apply(variables, jnp.asarray(seq))
    np.testing.assert_allclose(np.asarray(logits)[:, 0], full[0, 20:], atol=3e-5)
    # top-k 0.9 keeps 6 of 64 logits: every sample is among the step's 6 largest
    top = np.argsort(-np.asarray(full[:, 20:]), -1)[..., :6]
    assert (top == toks[..., None]).any(-1).all()


def test_a_further_turn_over_the_same_prompt_copies_nothing_and_repeats(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache, _ = prefill_cached(mdl, variables, tokens[:, :24], mdl.init_cache(2))
    turns = []
    for _ in range(2):
        toks, _, _, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 24:26], 6,
            filter_thres=1.0, start=24)
        turns.append(np.asarray(toks))
    assert np.array_equal(*turns)


def test_prefill_writes_the_rows_it_is_given(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(rows=4, seed=4)
    whole, _ = prefill_cached(mdl, variables, tokens[:, :16], mdl.init_cache(4))
    parts = mdl.init_cache(4)
    for row in (2, 0):
        parts, _ = prefill_cached(mdl, variables, tokens[row:row + 2, :16], parts, row)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        np.testing.assert_allclose(a, b, atol=2e-5)
