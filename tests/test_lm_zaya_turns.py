"""Turns with the convolved-latent family (`model_type: zaya`): the tail's
snapshot and its restore, the rows a prefill is given, the cache's leaves;
`tests/test_lm_zaya.py`'s model at a small size on the CPU
(`benchmark/configs/_tiny-zaya.json`), float32. Kernels interpreted."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_zaya
from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.lm import (
    CausalLM, generate_tokens_cached, prefill_cached, prefill_chunks)

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 60, 7
TAIL = (2 * (4 + 2) + 1) * 16  # c and c' of 4 + 2 heads of 16, and one shifted K/V head


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-zaya.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pair(cfg):
    mdl = CausalLM.from_config(cfg, N + 8)
    return mdl, build_zaya.seeded_variables(cfg, mdl, SEED)


def _tokens(rows=2, seed=0, n=N, vocab=96):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


def test_a_turn_after_restore_repeats_the_first_bit_for_bit(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache = mdl.init_cache(2)
    cache, _ = prefill_cached(mdl, variables, tokens[:1, :50], cache, 0)
    cache, _ = prefill_cached(mdl, variables, tokens[1:, :23], cache, 1)
    turns = []
    for _ in range(2):
        toks, logits, counts, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 50:52], 6,
            filter_thres=0.9, logit_rows=2, start=jnp.asarray([50, 23]))
        turns.append((np.asarray(toks), np.asarray(logits["logits"]),
                      np.asarray(cache["layer_1"]["attn"]["tail"])))
    for a, b in zip(*turns):
        assert np.array_equal(a, b)
    # the counters the sampler returns: the tails, running and kept, of 3 layers x 2 rows
    held = 3 * 2 * 2 * TAIL * 4
    assert counts["state_bytes"] == held == decode_cache.state_bytes(cache)
    assert counts["state_restored_bytes"] == held // 2
    assert counts["kv_bytes"] == decode_cache.kv_bytes(cache) == 3 * 2 * 2 * 2 * (N + 8) * 16 * 4


def test_a_turn_without_the_restore_does_not_repeat(cfg, pair, monkeypatch):
    """The K/V go back by their index; the tail does not: with `restore` put
    out of action the second turn's first step convolves with the first turn's
    last position."""
    from dalle_pytorch_tpu.models import dalle

    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache, _ = prefill_cached(mdl, variables, tokens[:, :50], mdl.init_cache(2))
    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, None))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    dalle._jitted_sampler.cache_clear()
    turns = []
    for _ in range(2):
        _, logits, _, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 50:52], 6,
            filter_thres=1.0, logit_rows=2, start=50)
        turns.append(np.asarray(logits["logits"]))
    dalle._jitted_sampler.cache_clear()
    assert np.abs(turns[0][0] - turns[1][0]).max() > 1e-3  # the first step's, at once


def test_prefill_writes_the_rows_it_is_given_and_their_snapshot(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(rows=4, seed=4)
    whole, _ = prefill_cached(mdl, variables, tokens[:, :40], mdl.init_cache(4))
    parts = mdl.init_cache(4)
    for rows in ([3, 1], [0, 2]):  # rows named one by one, in any order
        parts, _ = prefill_cached(mdl, variables, tokens[jnp.asarray(rows), :40], parts,
                                  jnp.asarray(rows))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    attn = whole["layer_0"]["attn"]
    assert np.array_equal(attn["tail"], attn["tail_at"]) and float(jnp.abs(attn["tail"]).max()) > 0


def test_a_chunk_onto_a_cache_goes_on_from_each_rows_own_tail(cfg, pair):
    """`extend` at two rows' own indices: every chunk attends what its row
    holds; a trunk of another kind is still refused in words."""
    mdl, variables = pair
    tokens = _tokens(seed=6)
    fresh, counts = prefill_chunks(mdl, variables, tokens[:, :48], 16)
    assert [layer["attn"]["index"].tolist() for layer in fresh.values()] == [[48, 48]] * 3
    assert int(counts["moe_dropped"].sum()) == 0 and int(counts["moe_rows"].sum()) == 3 * 2 * 48
    with open(ROOT / "benchmark" / "configs" / "_tiny-nemotron-h.json") as f:
        other = CausalLM.from_config(json.load(f), 32)
    with pytest.raises(NotImplementedError, match="latent layers, or of convolved ones"):
        prefill_chunks(other, None, tokens[:, :32], 16)


def test_a_cache_of_convolved_layers(cfg, pair):
    """K/V of the K/V heads alone, a tail and its snapshot a layer, every index
    per row; `restore` puts the kept tail back and leaves K/V alone."""
    mdl, _ = pair
    cache = mdl.init_cache(3, 20)
    assert sorted(cache) == ["layer_0", "layer_1", "layer_2"]
    attn = cache["layer_0"]["attn"]
    assert set(attn) == {"k", "v", "tail", "tail_at", "index"}
    assert attn["k"].shape == (3, 2, 20, 16) and attn["tail"].shape == (3, TAIL)
    assert attn["index"].shape == (3,) and attn["tail"].dtype == jnp.float32
    assert decode_cache.state_bytes(cache) == 3 * 2 * 3 * TAIL * 4
    moved = jax.tree_util.tree_map_with_path(
        lambda path, x: x if decode_cache.leaf_name(path).endswith("_at") else x + 3,
        decode_cache.snapshot(jax.tree.map(lambda x: x + 1, cache)))
    back, taken = decode_cache.restore(moved)
    assert set(taken) == set(cache) and "tail_at" not in back["layer_0"]["attn"]
    assert float(back["layer_0"]["attn"]["tail"].max()) == 1.0  # kept, not the moved 4
    assert float(back["layer_0"]["attn"]["k"].max()) == 4.0  # K/V are left alone
    again = decode_cache.snapshot(back, taken)
    assert float(again["layer_2"]["attn"]["tail_at"].max()) == 1.0
