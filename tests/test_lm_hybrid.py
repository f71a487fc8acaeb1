"""Generation with the linear-and-full language model, at a small size on the
CPU (`benchmark/configs/_tiny-olmo.json`: hidden 64, 4 heads, two periods of
three gated delta-rule layers and a full one, d_k 8 != d_v 24, convolution of
4 taps, vocabulary 96), float32, against the plain reference
(`benchmark/reference/olmo_hybrid_ref.py`). Kernels interpreted."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_olmo
from benchmark.reference import olmo_hybrid_ref as ref
from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.attention import delta_rule_chunked
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached
from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.ops.delta_step import delta_step, delta_step_reference

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 150, 7  # 150 tokens: two whole chunks of 64 and a tail of 22
ATOL = 5e-3  # float32 noise through eight output norms; a broken path reads O(1)


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-olmo.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, N + 8)
    return mdl, build_olmo.seeded_variables(cfg, mdl, SEED)


@pytest.fixture(scope="module")
def inputs(cfg):
    """A linear layer's (q, k, v, alpha, beta) on two sequences, from the
    reference: [2, N, H, d] and [2, N, H]."""
    d = ref.dims(cfg)
    lp = ref.init_layer(cfg, SEED, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, N, d["dim"]))
    return jax.vmap(lambda row: ref.linear_inputs(row, lp, d))(x)


def _tokens(rows=2, seed=0, n=N, vocab=96):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


def _state(cache, layer=0, heads=4):
    return np.asarray(decode_cache.running_state(cache, layer, heads))


def test_logits_match_the_reference(cfg, pair):
    mdl, variables = pair
    tokens = _tokens()
    want = ref.forward(cfg, SEED, tokens)
    np.testing.assert_allclose(mdl.apply(variables, tokens), want["logits"], atol=ATOL)


def test_the_kernel_steps_as_the_recurrence_does(inputs):
    """`delta_step` (interpreted) a token at a time against the reference's scan."""
    q, k, v, alpha, beta = (t[0] for t in inputs)  # one sequence: [N, H, d]
    want_o, want_s = ref.recurrence(q, k, v, alpha, beta)
    state = jnp.zeros((1, q.shape[2], q.shape[1] * v.shape[2]))
    outs = []
    for t in range(24):
        o, state = delta_step(state, q[t][None], k[t][None], v[t][None], alpha[t][None],
                              beta[t][None], block=2)
        outs.append(o[0])
    np.testing.assert_allclose(np.stack(outs), want_o[:24], atol=1e-6)
    _, want_s = ref.recurrence(q[:24], k[:24], v[:24], alpha[:24], beta[:24])
    h, dk, dv = want_s.shape
    np.testing.assert_allclose(
        np.asarray(state).reshape(dk, h, dv).transpose(1, 0, 2), want_s, atol=1e-6)


@pytest.mark.parametrize("block", [1, 2, 4])
def test_delta_step_matches_its_equations_at_every_head_block(block):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    b, h, dk, dv = 3, 4, 8, 24
    state = jax.random.normal(ks[0], (b, h, dk, dv))
    q, k = jax.random.normal(ks[1], (b, h, dk)), jax.random.normal(ks[2], (b, h, dk))
    v = jax.random.normal(ks[3], (b, h, dv))
    alpha, beta = jax.random.uniform(ks[4], (b, h)), 2 * jax.random.uniform(ks[5], (b, h))
    want_o, want_s = delta_step_reference(state, q, k, v, alpha, beta)
    o, new = delta_step(decode_cache.pack_state(state), q, k, v, alpha, beta, block=block)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new).reshape(b, dk, h, dv).transpose(0, 2, 1, 3), want_s, atol=1e-5)


def test_the_chunked_form_is_the_recurrence(inputs):
    """At 150 tokens, no multiple of 64: outputs and the state it leaves."""
    q, k, v, alpha, beta = inputs
    o, state = delta_rule_chunked(q, k, v, jnp.log(alpha), beta, chunk=64)
    want_o, want_s = jax.vmap(ref.recurrence)(q, k, v, alpha, beta)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=1e-4, atol=2e-5)


def test_beta_reaches_past_one(cfg, inputs):
    """`linear_allow_neg_eigval`: beta = 2 sigmoid(.), so some steps reflect
    (beta > 1); with the factor dropped the states differ."""
    q, k, v, alpha, beta = inputs
    assert float(beta.max()) > 1.0 and float(beta.min()) > 0.0 and float(beta.max()) < 2.0
    _, state = delta_rule_chunked(q, k, v, jnp.log(alpha), beta)
    _, halved = delta_rule_chunked(q, k, v, jnp.log(alpha), beta / 2)
    assert float(jnp.abs(state - halved).max()) > 1e-3


@pytest.mark.parametrize("prefilled", [131, 3, 1])
def test_prefill_then_cached_steps_match_the_uncached_forward(cfg, pair, prefilled):
    """The recurrence against the cache after the chunked form left its state
    and its ring there (a prefill shorter than the ring's 3 inputs included),
    through linear and full layers 3:1."""
    mdl, variables = pair
    tokens = _tokens(seed=1)
    steps = min(N - prefilled, 24)
    full = mdl.apply(variables, tokens[:, :prefilled + steps])
    cache, _ = prefill_cached(mdl, variables, tokens[:, :prefilled], mdl.init_cache(2))
    want = ref.forward(cfg, SEED, tokens[:, :prefilled])["state"]
    np.testing.assert_allclose(_state(cache), want, atol=1e-6)
    toks, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, tokens[:, prefilled:prefilled + steps],
        steps, filter_thres=1.0, logit_rows=2, start=prefilled)
    np.testing.assert_allclose(
        np.asarray(logits).transpose(1, 0, 2), full[:, prefilled:], atol=ATOL)
    assert all(int(layer["attn"]["index"]) == prefilled + steps for layer in cache.values())
    want = ref.forward(cfg, SEED, tokens[:, :prefilled + steps])["state"]
    np.testing.assert_allclose(_state(cache), want, atol=1e-6)


def test_a_turn_after_restore_repeats_the_first_bit_for_bit(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(seed=3)
    cache, _ = prefill_cached(mdl, variables, tokens[:, :70], mdl.init_cache(2))
    turns = []
    for _ in range(2):
        toks, logits, counts, cache = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(1), cache, tokens[:, 70:72], 6,
            filter_thres=1.0, logit_rows=2, start=70)
        turns.append((np.asarray(toks), np.asarray(logits), _state(cache)))
    for a, b in zip(*turns):
        assert np.array_equal(a, b)
    # the counters the sampler returns: two float32 copies of 12 states and rings
    held = 6 * 2 * 4 * (4 * 8 * 24 + 3 * 4 * (2 * 8 + 24)) * 2
    assert counts["state_bytes"] == held == decode_cache.state_bytes(cache)
    assert counts["state_restored_bytes"] == held // 2
    assert counts["kv_bytes"] == decode_cache.kv_bytes(cache) == 2 * 2 * 2 * 4 * (N + 8) * 16 * 4


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
        turns.append(np.asarray(logits))
    dalle._jitted_sampler.cache_clear()
    assert np.abs(turns[0] - turns[1]).max() > 1e-3


def test_prefill_writes_the_rows_it_is_given_and_their_snapshot(cfg, pair):
    mdl, variables = pair
    tokens = _tokens(rows=4, seed=4)
    whole, _ = prefill_cached(mdl, variables, tokens[:, :70], mdl.init_cache(4))
    parts = mdl.init_cache(4)
    for row in (2, 0):
        parts, _ = prefill_cached(mdl, variables, tokens[row:row + 2, :70], parts, row)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(parts)):
        np.testing.assert_allclose(a, b, atol=2e-5)
    attn = whole["layer_0"]["attn"]
    assert np.array_equal(attn["state"], attn["state_at"])
    assert np.array_equal(attn["conv"], attn["conv_at"]) and float(jnp.abs(attn["conv"]).max()) > 0


def test_a_cache_of_mixed_kinds(cfg, pair):
    mdl, _ = pair
    cache = mdl.init_cache(3, 20)
    kinds = ["recurrent" if "state" in cache[f"layer_{i}"]["attn"] else "heads" for i in range(8)]
    assert kinds == ["recurrent"] * 3 + ["heads"] + ["recurrent"] * 3 + ["heads"]
    attn = cache["layer_0"]["attn"]
    assert attn["state"].shape == (3, 8, 4 * 24) and attn["state"].dtype == jnp.float32
    assert attn["conv"].shape == (3, 3, 4 * (2 * 8 + 24)) and attn["index"].shape == ()
    assert cache["layer_3"]["attn"]["k"].shape == (3, 4, 20, 16)
    assert decode_cache.kv_bytes(cache) == 2 * 2 * 3 * 4 * 20 * 16 * 4
    assert decode_cache.state_bytes(cache) == 6 * 2 * 3 * 4 * (4 * 8 * 24 + 3 * 4 * 40)
    direct = decode_cache.make(
        decode_cache.PER_LAYER, 2, kinds=["recurrent", "heads"], batch=3, max_len=20, heads=4,
        dim_head=16, dim=64, linear_heads=4, key_dim=8, value_dim=24, conv_taps=4)
    assert jax.tree.structure(direct) == jax.tree.structure(
        {"layer_0": cache["layer_0"], "layer_1": cache["layer_3"]})
    with pytest.raises(AssertionError, match="per layer"):
        decode_cache.make(decode_cache.STACKED, 2, kinds=["recurrent", "heads"], batch=3,
                          max_len=20, heads=4, dim_head=16, dim=64, linear_heads=4, key_dim=8,
                          value_dim=24, conv_taps=4)


def test_set_index_rewinds_and_snapshot_restore_copy(cfg, pair):
    mdl, _ = pair
    cache = mdl.init_cache(2, 20)
    cache = jax.tree.map(lambda x: x + 1, cache)  # every leaf: ones
    kept = decode_cache.snapshot(cache)
    # a turn moves every leaf but the snapshot's
    moved = jax.tree_util.tree_map_with_path(
        lambda path, x: x if decode_cache.leaf_name(path).endswith("_at") else x * 3, kept)
    back, taken = decode_cache.restore(moved)
    assert "state_at" not in back["layer_0"]["attn"] and set(taken) == {
        f"layer_{i}" for i in (0, 1, 2, 4, 5, 6)}
    assert float(back["layer_0"]["attn"]["state"].max()) == 1.0  # kept, not the moved 3
    assert float(back["layer_3"]["attn"]["k"].max()) == 3.0  # a K/V layer is left alone
    again = decode_cache.snapshot(back, taken)
    assert jax.tree.structure(again) == jax.tree.structure(cache)
    assert int(decode_cache.set_index(again, jnp.asarray(5))["layer_1"]["attn"]["index"]) == 5


@pytest.mark.parametrize("change, message", [
    ({"rope_parameters": {"rope_theta": 10000.0}}, "rotary"),
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "untied"),
    ({"linear_num_value_heads": 8}, "shared"),
    ({"linear_allow_neg_eigval": False}, "beta"),
    ({"num_key_value_heads": 2}, "K/V head"),
])
def test_from_config_refuses_what_is_not_built(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        CausalLM.from_config({**cfg, **change}, 16)


def test_from_config_reads_the_published_keys(cfg):
    mdl = CausalLM.from_config(cfg, 16, weights_dtype="bfloat16", dtype="bfloat16")
    trunk = dict(mdl.trunk)
    assert trunk["attn_types"] == ("linear", "linear", "linear", "full") * 2
    assert (trunk["linear_key_dim"], trunk["linear_value_dim"], trunk["linear_conv"]) == (8, 24, 4)
    assert trunk["qk_norm"] == "whole" and not trunk["prenorm"] and trunk["sandwich_norm"]
    assert mdl.dim_head == 16 and mdl.param_dtype == jnp.bfloat16
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    t = shapes["params"]["transformer"]
    assert t["attn_0"]["to_qkv"].dtype == jnp.bfloat16 and t["attn_0"]["A_log"].dtype == jnp.float32
    assert t["attn_3"]["q_norm"]["scale"].shape == (64,)  # one gain over all the columns
    assert "attn_norms_0" not in t and "attn_norms_out_0" in t  # no norm on a sublayer's input
    assert CausalLM.from_config(cfg, 16).param_dtype == jnp.float32


def test_the_narrowed_refusals_say_what_is_left(cfg):
    from dalle_pytorch_tpu.models.attention import Attention

    attn = Attention(dim=32, seq_len=8, heads=4, dim_head=8, kv_heads=2, use_bias=False)
    x = jnp.zeros((1, 1, 32))
    variables = attn.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))
    cache = decode_cache.make(decode_cache.PER_LAYER, 1, batch=1, max_len=8, heads=2,
                              dim_head=8, dim=32)["layer_0"]["attn"]
    # K/V heads shared by query heads decode through a cache since PR 37, every
    # row at its own index: a lockstep cache (scalar index) is not theirs
    with pytest.raises(ValueError, match="grouped_rows path.*per row"):
        attn.apply(variables, x, cache=cache)
    cache = decode_cache.make(decode_cache.PER_LAYER, 1, batch=1, max_len=8, heads=2,
                              dim_head=8, dim=32, per_row=True)["layer_0"]["attn"]
    assert attn.apply(variables, x, cache=cache)[1]["index"].tolist() == [1]
    with pytest.raises(NotImplementedError, match="linear and full"):
        CausalLM.from_config(cfg, 16).generate()


def test_the_new_spans_are_the_programs_own(cfg, pair):
    """`delta_step`, `delta_proj`, `delta_chunk` and `state_restore` name the
    new work in the lowered programs, and the rules place them."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables = pair
    cache = mdl.init_cache(2)
    sample = jax.jit(lm._sampler_builder(mdl, (2, 1.0, 1.0, 0))).lower(
        variables, jax.random.PRNGKey(0), cache, jnp.zeros((2, 1), jnp.int32),
        jnp.asarray(4, jnp.int32)).as_text(debug_info=True)
    prefill = jax.jit(lm._prefill_builder(mdl, ())).lower(
        variables, jnp.zeros((2, 70), jnp.int32), cache,
        jnp.asarray(0, jnp.int32)).as_text(debug_info=True)
    for name in ("delta_step", "delta_proj", "state_restore"):
        assert f"/{name}/" in sample or f"/{name}\"" in sample, name
    assert "/delta_chunk/" in prefill and "/delta_chunk/" not in sample
    path = "jit(lm_sample)/while/body/CausalLM.decode_step/transformer/attn_1/"
    assert scopes.component(path + "delta_proj/to_out/dot_general") == ("delta_proj", "fwd")
    assert scopes.component(path + "delta_step/pallas_call") == ("delta_step", "fwd")
    assert scopes.component(None, "custom-call", "%delta_step.12") == ("delta_step", "fwd")
    assert scopes.component("jit(lm_prefill)/x/attn_0/delta_chunk/mul") == ("delta_chunk", "fwd")
    assert scopes.component("jit(lm_sample)/state_restore/dynamic_update_slice") == (
        "state_restore", "fwd")
    assert scopes.component(path.replace("attn_1", "attn_3") + "to_out/dot_general") == (
        "attn_proj", "fwd")
    assert {"delta_step", "delta_proj", "delta_chunk", "state_restore"} <= set(scopes.COMPONENTS)
    # the token step's head product is the `head` component, not nobody's
    assert "/logits_head/" in sample
    assert scopes.component(
        "jit(lm_sample)/while/body/CausalLM.decode_step/logits_head/dot_general") == ("head", "fwd")
