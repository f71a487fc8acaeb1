"""Generation with the state-space hybrid whose layers are ONE sublayer each
(`nemotron_h`), at a small size on the CPU (`benchmark/configs/
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


def test_logits_match_the_reference_and_bfloat16_does_not(cfg, pair):
    """The uncached forward (the chunked form over five chunks and a tail) is
    the reference's; the same weights computed in bfloat16 land outside the
    tolerance."""
    mdl, variables = pair
    tokens = _tokens()
    want = ref.forward(cfg, SEED, tokens)["logits"]
    np.testing.assert_allclose(mdl.apply(variables, tokens), want, atol=ATOL)
    low = CausalLM.from_config(cfg, N + 8, dtype="bfloat16")
    assert np.abs(np.asarray(low.apply(variables, tokens)) - want).max() > 10 * ATOL


def test_prefill_then_per_row_steps_match_the_reference_at_two_lengths(cfg, pair):
    """Rows of two lengths in ONE cache, each at its own position: the
    chunked prefill leaves state, ring and K/V, `restore` and the per-row
    index start the turn, and the cached steps through `MEMEM*EME` (the
    kernel's state update, the grouped K/V step, the ungated experts) give
    the reference's logits, its state and its router's choices."""
    mdl, variables = pair
    tokens = _tokens(rows=3, seed=1)
    steps, lengths = 12, (75, 75, 37)
    cache = mdl.init_cache(3)
    cache, _ = prefill_cached(mdl, variables, tokens[:2, :75], cache, 0)
    cache, _ = prefill_cached(mdl, variables, tokens[2:, :37], cache, jnp.asarray([2]))
    forced = jnp.stack([tokens[r, n:n + steps] for r, n in enumerate(lengths)])
    toks, logits, counts, cache = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, forced, steps, filter_thres=1.0,
        logit_rows=3, start=jnp.asarray(lengths))
    assert np.array_equal(np.asarray(toks)[:, :steps - 1], forced[:, 1:])  # teacher forced
    want = ref.forward(cfg, SEED, [tokens[:2, :75 + steps], tokens[2:, :37 + steps]],
                       start=[75, 37])
    got = np.asarray(logits["logits"])[:, :, 0].transpose(1, 0, 2)  # [rows, steps, V]
    np.testing.assert_allclose(got[:2], want["logits"][0], atol=ATOL)
    np.testing.assert_allclose(got[2:], want["logits"][1], atol=ATOL)
    np.testing.assert_allclose(_state(cache)[:2], want["state"][0], atol=1e-6)
    np.testing.assert_allclose(_state(cache)[2:], want["state"][1], atol=1e-6)
    assert np.asarray(logits["at"]).T.tolist() == [list(range(n, n + steps)) for n in lengths]
    assert [layer["attn"]["index"].tolist() for layer in cache.values()] == [
        [n + steps for n in lengths]] * 5
    # the router of layer 1 chooses as the reference's does
    choices = mdl.apply(variables, tokens[:2, :75 + steps], 1, method=CausalLM.route_choices)
    assert np.array_equal(np.sort(choices[:, 75:], -1), np.sort(want["choices"][0], -1))
    assert int(counts["moe_dropped"].sum()) == 0 and counts["moe_load"].shape == (4, 4)


@pytest.mark.parametrize("change, message", [
    ({"mlp_hidden_act": "silu"}, "relu2"),
    ({"mamba_proj_bias": True}, "no other bias.*mamba_proj_bias"),
    ({"use_conv_bias": False}, "convolution bias"),
    ({"tie_word_embeddings": True}, "untied"),
    ({"hybrid_override_pattern": "MEMEM-EME"}, "layers of M, \\* and E"),
    ({"num_hidden_layers": 12}, "12 layers"),
    ({"norm_topk_prob": False}, "renormalises"),
])
def test_from_config_refuses_what_is_not_built(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        CausalLM.from_config({**cfg, **change}, 16)


def test_from_config_reads_the_published_keys(cfg):
    mdl = CausalLM.from_config(cfg, 16, weights_dtype="bfloat16", dtype="bfloat16")
    trunk = dict(mdl.trunk)
    assert trunk["attn_types"] == ("ssm", "none", "ssm", "none", "ssm", "full", "none", "ssm",
                                   "none")
    assert trunk["ff_kinds"][1] == "relu2_experts" and trunk["ff_kinds"][0] == "none"
    assert (trunk["ssm_heads"], trunk["ssm_head_dim"], trunk["ssm_groups"], trunk["ssm_state"],
            trunk["ssm_conv"], trunk["ssm_chunk"]) == (8, 8, 2, 16, 4, 16)
    assert trunk["norm_eps"] == 1e-5 and trunk["moe_score_bias"] and trunk["shared_dim"] == 64
    assert trunk["experts_total"] == 8 and trunk["experts_held"] == (0, 4)
    assert "rotary_specs" not in trunk and mdl.dim_head == 16 and mdl.per_row
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    t = shapes["params"]["transformer"]
    assert t["attn_0"]["to_in"].dtype == jnp.bfloat16 and t["attn_0"]["A_log"].dtype == jnp.float32
    assert t["attn_0"]["to_in"].shape == (48, 64 + 128 + 8)  # z | xBC | dt: d_inner is H x P
    # one norm a layer, under the name of the sublayer it has
    assert "ff_norms_0" not in t and "attn_norms_1" not in t and "ff_1" in t and "attn_1" not in t
    assert set(t["ff_1"]) == {"router", "router_bias", "w_up", "w_out", "shared_up", "shared_out"}


@pytest.mark.parametrize("how", ["train_lm", "make_lm_train_step"])
def test_training_is_refused_by_name(cfg, how, tmp_path):
    """No backward for the chunked scan or the ungated experts: the trainer
    says so before anything is built."""
    words = "nemotron_h.*no backward.*chunked state-space scan.*ungated experts"
    if how == "make_lm_train_step":
        from dalle_pytorch_tpu.training.steps import make_lm_train_step

        with pytest.raises(NotImplementedError, match=words):
            make_lm_train_step(CausalLM.from_config(cfg, 16))
        return
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, str(ROOT / "train_lm.py"), "--config",
         str(ROOT / "benchmark/configs/_tiny-nemotron-h.json"), "--tokens", "seeded:1.0",
         "--steps", "1"], capture_output=True, text=True, env={"JAX_PLATFORMS": "cpu", "PATH": ""})
    assert done.returncode != 0
    import re

    assert re.search(words, done.stderr.replace("\n", " ")), done.stderr[-400:]
