"""Turns with the state-space hybrid whose layers are ONE sublayer each
(`nemotron_h`): the snapshot and its restore, the rows a prefill is given, the
cache's leaves; `tests/test_lm_nemotron_h.py`'s model, at a small size on the CPU (`benchmark/configs/
_tiny-nemotron-h.json`: hidden 48, the published period `MEMEM*EME`, 8 Mamba-2
heads of 8 over 2 groups of state 16, 4 query heads over 2 K/V heads, 4 of 8
ungated relu2 experts held, vocabulary 64), float32, against the plain
reference (`benchmark/reference/nemotron_h_ref.py`). Kernels interpreted."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_nemotron_h
from benchmark.reference import nemotron_h_ref as ref
from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 90, 7  # 90 tokens: five whole chunks of 16 and a tail of 10
# float32 noise through nine layers reads 5e-6 on logits of size 3; bfloat16 in
# the reference's place reads 3e-2, a
# broken path O(1)
ATOL = 2e-4


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-nemotron-h.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, N + 8)
    return mdl, build_nemotron_h.seeded_variables(cfg, mdl, SEED)


def _tokens(rows=2, seed=0, n=N, vocab=64):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


def _state(cache, layer=0, heads=8):
    return np.asarray(decode_cache.running_state(cache, layer, heads))


def test_a_turn_after_restore_repeats_the_first_bit_for_bit(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache = mdl.init_cache(2)
    cache, _ = prefill_cached(mdl, variables, tokens[:1, :70], cache, 0)
    cache, _ = prefill_cached(mdl, variables, tokens[1:, :33], cache, 1)
    turns = []
    for _ in range(2):
        toks, logits, counts, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 70:72], 6,
            filter_thres=0.9, logit_rows=2, start=jnp.asarray([70, 33]))
        turns.append((np.asarray(toks), np.asarray(logits["logits"]), _state(cache)))
    for a, b in zip(*turns):
        assert np.array_equal(a, b)
    # the counters the sampler returns: two float32 copies of 4 states and rings
    held = 4 * 2 * 2 * (16 * 8 * 8 + 3 * (8 * 8 + 2 * 2 * 16)) * 4
    assert counts["state_bytes"] == held == decode_cache.state_bytes(cache)
    assert counts["state_restored_bytes"] == held // 2
    assert counts["kv_bytes"] == decode_cache.kv_bytes(cache) == 2 * 2 * 2 * (N + 8) * 16 * 4


def test_a_turn_without_the_restore_does_not_repeat(cfg, pair, monkeypatch):
    """A state cannot be rewound by its index: with `restore` put out of
    action the second turn starts from the first turn's end."""
    from dalle_pytorch_tpu.models import dalle

    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache, _ = prefill_cached(mdl, variables, tokens[:, :70], mdl.init_cache(2))
    monkeypatch.setattr(decode_cache, "restore", lambda cache: (cache, None))
    monkeypatch.setattr(decode_cache, "snapshot", lambda cache, kept=None: cache)
    dalle._jitted_sampler.cache_clear()
    turns = []
    for _ in range(2):
        _, logits, _, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 70:72], 6,
            filter_thres=1.0, logit_rows=2, start=70)
        turns.append(np.asarray(logits["logits"]))
    dalle._jitted_sampler.cache_clear()
    assert np.abs(turns[0] - turns[1]).max() > 1e-3


def test_prefill_writes_the_rows_it_is_given_and_their_snapshot(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(rows=4, seed=4)
    whole, _ = prefill_cached(mdl, variables, tokens[:, :70], mdl.init_cache(4))
    parts = mdl.init_cache(4)
    for rows in ([3, 1], [0, 2]):  # rows named one by one, in any order
        parts, _ = prefill_cached(mdl, variables, tokens[jnp.asarray(rows), :70], parts,
                                  jnp.asarray(rows))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    attn = whole["layer_0"]["attn"]
    assert np.array_equal(attn["state"], attn["state_at"])
    assert np.array_equal(attn["conv"], attn["conv_at"]) and float(jnp.abs(attn["conv"]).max()) > 0


def test_a_cache_of_one_sublayer_layers(cfg, pair):
    """A Mamba-2 layer's leaves are sized from the mixer, a routed layer holds
    nothing, and every index is per row."""
    mdl, _ = pair
    cache = mdl.init_cache(3, 20)
    assert sorted(cache) == ["layer_0", "layer_2", "layer_4", "layer_5", "layer_7"]
    attn = cache["layer_0"]["attn"]
    assert attn["state"].shape == (3, 16, 8 * 8) and attn["state"].dtype == jnp.float32
    assert attn["conv"].shape == (3, 3, 8 * 8 + 2 * 2 * 16) and attn["index"].shape == (3,)
    assert cache["layer_5"]["attn"]["k"].shape == (3, 2, 20, 16)
    assert decode_cache.state_bytes(cache) == 4 * 2 * 3 * (16 * 64 + 3 * 128) * 4
    moved = jax.tree_util.tree_map_with_path(
        lambda path, x: x if decode_cache.leaf_name(path).endswith("_at") else x + 3,
        decode_cache.snapshot(jax.tree.map(lambda x: x + 1, cache)))
    back, taken = decode_cache.restore(moved)
    assert set(taken) == {"layer_0", "layer_2", "layer_4", "layer_7"}
    assert float(back["layer_0"]["attn"]["state"].max()) == 1.0  # kept, not the moved 4
    assert float(back["layer_5"]["attn"]["k"].max()) == 4.0  # a K/V layer is left alone
