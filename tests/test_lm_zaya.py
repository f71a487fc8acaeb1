"""Generation with the convolved-latent family (`model_type: zaya`), at a small
size on the CPU (`benchmark/configs/_tiny-zaya.json`: hidden 64, 3 layers, 4
query heads over 2 K/V heads of 16 behind two causal convolutions, 8 SwiGLU
experts ONE a token behind an MLP router of width 16 that carries its state down
the depth, a scaled residual, a head that is the embedding over 96 ids),
float32, against the plain reference (`benchmark/reference/zaya_ref.py`).
Kernels interpreted."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_zaya
from benchmark.reference import zaya_ref as ref
from dalle_pytorch_tpu.models import attention
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached
from dalle_pytorch_tpu.models.moe import RoutedExperts

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 60, 7
# float32 noise through three layers reads 1e-6 on logits of size 1; bfloat16 in
# the program's place reads 2e-2, a mechanism left out 5e-2 or more
ATOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-zaya.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, N + 8)
    return mdl, build_zaya.seeded_variables(cfg, mdl, SEED)


@pytest.fixture(scope="module")
def forward(pair):
    """The model's uncached forward, compiled once for every set of weights."""
    return jax.jit(pair[0].apply)


def _tokens(rows=2, seed=0, n=N, vocab=96):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, n)), jnp.int32)


@pytest.fixture(scope="module")
def wanted(cfg):
    """(tokens, the reference's forward over them), made once for the ablations."""
    tokens = _tokens(seed=5)
    return tokens, ref.forward(cfg, SEED, tokens)


def test_logits_match_the_reference_and_bfloat16_does_not(cfg, pair):
    mdl, variables = pair
    tokens = _tokens()
    want = ref.forward(cfg, SEED, tokens)
    np.testing.assert_allclose(mdl.apply(variables, tokens), want["logits"], atol=ATOL)
    choices = mdl.apply(variables, tokens, 0, method=CausalLM.route_choices)
    assert np.array_equal(choices, want["choices"]) and len(np.unique(choices)) > 2
    low = CausalLM.from_config(cfg, N + 8, dtype="bfloat16")
    assert np.abs(np.asarray(low.apply(variables, tokens)) - want["logits"]).max() > 10 * ATOL


def test_prefill_then_per_row_steps_match_the_reference_at_two_lengths(cfg, pair):
    """Rows of two lengths in ONE cache, each at its own position: the prefill
    leaves K/V and the tail, `restore` and the per-row index start the turn,
    and the cached steps (the convolutions against the tail, the kernel over
    each row's live K/V, the router's state down the depth, the top-1 experts)
    give the reference's full forward's logits."""
    mdl, variables = pair
    tokens = _tokens(rows=3, seed=1)
    steps, lengths = 10, (45, 45, 21)
    cache = mdl.init_cache(3)
    cache, _ = prefill_cached(mdl, variables, tokens[:2, :45], cache, 0)
    cache, _ = prefill_cached(mdl, variables, tokens[2:, :21], cache, jnp.asarray([2]))
    forced = jnp.stack([tokens[r, n:n + steps] for r, n in enumerate(lengths)])
    toks, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, forced, steps, filter_thres=1.0,
        logit_rows=3, start=jnp.asarray(lengths))
    assert np.array_equal(np.asarray(toks)[:, :steps - 1], forced[:, 1:])  # teacher forced
    got = np.asarray(logits["logits"])[:, :, 0].transpose(1, 0, 2)  # [rows, steps, V]
    np.testing.assert_allclose(
        got[:2], ref.forward(cfg, SEED, tokens[:2, :45 + steps], start=45)["logits"], atol=ATOL)
    np.testing.assert_allclose(
        got[2:], ref.forward(cfg, SEED, tokens[2:, :21 + steps], start=21)["logits"], atol=ATOL)
    assert [layer["attn"]["index"].tolist() for layer in cache.values()] == [
        [n + steps for n in lengths]] * 3
    assert int(counts["moe_dropped"].sum()) == 0 and counts["moe_load"].shape == (3, 8)
    assert int(counts["moe_rows"].sum()) == 3 * 3 * steps  # ONE expert a token a layer


def test_a_prefill_in_two_chunks_equals_one(cfg, pair):
    """The second chunk's first position convolves with the first chunk's
    last (the tail) and attends what the cache holds."""
    mdl, variables = pair
    tokens = _tokens(seed=2)
    whole, _ = prefill_cached(mdl, variables, tokens[:, :50], mdl.init_cache(2))
    parts, _ = prefill_cached(mdl, variables, tokens[:, :50], mdl.init_cache(2), chunk=25)
    for name in ("k", "v", "tail", "tail_at"):
        for layer in whole:
            a, b = whole[layer]["attn"][name], parts[layer]["attn"][name]
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=f"{layer} {name}")
    assert float(jnp.abs(whole["layer_2"]["attn"]["tail"]).max()) > 0


def _neutral(params, layer_name, leaf, value):
    t = dict(params["transformer"])
    t[layer_name] = {**t[layer_name], leaf: value(t[layer_name][leaf])}
    return {"params": {**params, "transformer": t}}


IDENTITY_TAPS = lambda w: jnp.zeros_like(w).at[1].set(jnp.broadcast_to(jnp.eye(16), w.shape[1:]))
ROW = lambda r, x: (lambda w: w.at[r].set(x))
LEFT_OUT = {  # mechanism -> (where, leaf, its neutral value), in every layer
    "tau": ("attn_{i}", "tau", jnp.ones_like),
    "conv0": ("attn_{i}", "conv0", lambda w: jnp.zeros_like(w).at[1].set(1.0)),
    "conv0_bias": ("attn_{i}", "conv0_bias", jnp.zeros_like),
    "conv1": ("attn_{i}", "conv1", IDENTITY_TAPS),
    "conv1_bias": ("attn_{i}", "conv1_bias", jnp.zeros_like),
    "gamma": ("ff_{i}", "router_gamma", jnp.zeros_like),
    "beta": ("ff_{i}", "router_bias", jnp.zeros_like),
    "attn_a": ("attn_res_{i}", "vectors", ROW(0, 1.0)), "attn_b": ("attn_res_{i}", "vectors", ROW(1, 0.0)),
    "attn_c": ("attn_res_{i}", "vectors", ROW(2, 1.0)), "attn_d": ("attn_res_{i}", "vectors", ROW(3, 0.0)),
    "ff_a": ("ff_res_{i}", "vectors", ROW(0, 1.0)), "ff_b": ("ff_res_{i}", "vectors", ROW(1, 0.0)),
    "ff_c": ("ff_res_{i}", "vectors", ROW(2, 1.0)), "ff_d": ("ff_res_{i}", "vectors", ROW(3, 0.0)),
}
# what has no parameter is left out of the mixer's code
PATCHED = {
    "qk_mean": ("_qk_mean", lambda qh, kh: (jnp.zeros_like(qh), jnp.zeros_like(kh))),
    "value_shift": ("_shifted_values", lambda vf, last, now: vf),
}


@pytest.mark.parametrize("mechanism", [*LEFT_OUT, *PATCHED])
def test_a_mechanism_left_out_fails_the_comparison(pair, forward, wanted, mechanism, monkeypatch):
    """Every learned vector is seeded OFF its neutral value and every step of
    the layer is in the reference: set to neutral, or left out, the logits (or,
    for the bias of the choice, the experts chosen) are no longer the
    reference's."""
    mdl, variables = pair
    tokens, want = wanted
    if mechanism in PATCHED:
        monkeypatch.setattr(attention, *PATCHED[mechanism])
        forward = mdl.apply  # traced anew, with the patch
    else:
        where, leaf, value = LEFT_OUT[mechanism]
        for i in range(mdl.depth):
            variables = _neutral(variables["params"], where.format(i=i), leaf, value)
    if mechanism == "beta":  # it moves the choice alone, and a choice only where two scores are near
        chosen = [mdl.apply(variables, tokens, i, method=CausalLM.route_choices)
                  for i in range(mdl.depth)]
        kept = [mdl.apply(pair[1], tokens, i, method=CausalLM.route_choices)
                for i in range(mdl.depth)]
        assert any(not np.array_equal(a, b) for a, b in zip(chosen, kept))
        return
    got = np.asarray(forward(variables, tokens))
    assert np.abs(got - want["logits"]).max() > 10 * ATOL


def test_sixteen_one_expert_shares_add_up_to_the_whole_layer():
    """A routed sublayer that holds ONE of its sixteen experts computes that
    expert's part, and the sixteen parts are the layer: the MLP router, its
    state and the unrenormalised gate are whole on every share."""
    options = dict(dim=32, expert_dim=16, experts_total=16, experts_per_token=1, buffer_rows=64,
                   score_bias=True, renormalise=False, router_dim=8, norm_eps=1e-5)
    whole = RoutedExperts(experts_held=(0, 16), **options)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    carried = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 8))
    params = whole.init(jax.random.PRNGKey(2), x, carried=carried)["params"]
    params = {**params, "router_bias": 0.02 * jax.random.normal(jax.random.PRNGKey(3), (16,)),
              "router_gamma": jnp.full((8,), 0.5)}
    (y, state), stats = whole.apply({"params": params}, x, carried=carried, mutable=["stats"])
    assert len(np.unique(whole.apply({"params": params}, x, carried, method="choices"))) > 2
    total = jnp.zeros_like(y)
    for e in range(16):
        share = {**params, **{w: params[w][e:e + 1] for w in ("w_gate", "w_up", "w_out")}}
        (part, s), _ = RoutedExperts(experts_held=(e, 1), **options).apply(
            {"params": share}, x, carried=carried, mutable=["stats"])
        assert np.array_equal(s, state)
        total = total + part
    np.testing.assert_allclose(total, y, atol=1e-5)
    assert float(jnp.abs(y).max()) > 0 and int(stats["stats"]["moe_rows"]) == 24
    # the gate is the chosen score itself: under 1, where a renormalised one is 1
    renormed = RoutedExperts(experts_held=(0, 16), **{**options, "renormalise": True})
    (y1, _), _ = renormed.apply({"params": params}, x, carried=carried, mutable=["stats"])
    assert float(jnp.abs(y1).max()) > 2 * float(jnp.abs(y).max())


@pytest.mark.parametrize("change, message", [
    ({"cca_time1": 4}, "2 taps"),
    ({"num_experts_per_tok": 2}, "ONE expert"),
    ({"layer_types": ["hybrid", "hybrid_sliding", "hybrid"]}, "`hybrid` layers alone"),
    ({"lm_head_bias": True}, "no biases"),
    ({"hidden_act": "gelu"}, "SiLU"),
    ({"num_nextn_predict_layers": 1}, "multi-token module"),
])
def test_from_config_refuses_what_is_not_built(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        CausalLM.from_config({**cfg, **change}, 16)


def test_from_config_reads_the_published_keys(cfg):
    mdl = CausalLM.from_config(cfg, 16, weights_dtype="bfloat16", dtype="bfloat16")
    trunk = dict(mdl.trunk)
    assert trunk["attn_types"] == ("cca",) and trunk["kv_heads"] == 2 and mdl.dim_head == 16
    assert dict(trunk["rotary_specs"]["cca"]) == {"type": "default", "dim": 8, "theta": 5000000}
    assert trunk["residual"] == "affine" and trunk["router_dim"] == 16 and mdl.tied_head
    assert trunk["moe_score_bias"] and not trunk["moe_renormalise"] and mdl.per_row
    assert trunk["experts_total"] == 8 and trunk["experts_held"] == (0, 8)
    assert trunk["experts_per_token"] == 1 and trunk["norm_eps"] == 1e-5
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(shapes["params"]) == {"token_emb", "transformer", "logits_norm"}  # no head
    t = shapes["params"]["transformer"]
    assert t["attn_0"]["to_qkv"]["kernel"].shape == (64, (4 + 2 + 2) * 16)
    assert t["attn_0"]["conv1"].shape == (2, 6, 16, 16) and t["attn_0"]["conv1"].dtype == jnp.bfloat16
    assert t["attn_0"]["tau"].dtype == t["attn_res_0"]["vectors"].dtype == jnp.float32
    assert t["ff_0"]["router_down"].dtype == jnp.float32 and "router" not in t["ff_0"]
    assert t["attn_res_2"]["vectors"].shape == t["ff_res_2"]["vectors"].shape == (4, 64)


@pytest.mark.parametrize("how", ["train_lm", "make_lm_train_step"])
def test_training_is_refused_by_name(cfg, how):
    words = "zaya family.*forward only.*no gradient of it has been held to the reference"
    if how == "make_lm_train_step":
        from dalle_pytorch_tpu.training.steps import make_lm_train_step

        with pytest.raises(NotImplementedError, match=words):
            make_lm_train_step(CausalLM.from_config(cfg, 16))
        return
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, str(ROOT / "train_lm.py"), "--config",
         str(ROOT / "benchmark/configs/_tiny-zaya.json"), "--tokens", "seeded:1.0",
         "--steps", "1"], capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu", "PATH": ""})
    assert done.returncode != 0
    assert re.search(words, done.stderr.replace("\n", " ")), done.stderr[-400:]
