"""Generation with the learned-sparse-attention language model at a small size
on the CPU (`benchmark/configs/_tiny-deepseek-v32.json`: hidden 64, 4 heads,
latent ranks 24 / 16, a lightning indexer of 4 heads of 16 that selects 8
positions a query, YaRN over an original context of 16, one dense layer + two
routed, 32 experts in 4 groups of 8 of which 2 groups stay and 8 experts are
held, 4 a token + 1 shared, a score-correction bias, vocabulary 64), float32,
against the plain reference (`benchmark/reference/deepseek_v32_ref.py`)."""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_deepseek_v32
from benchmark.reference import deepseek_v32_ref as ref
from dalle_pytorch_tpu.models import decode_cache, lm, moe
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached
from dalle_pytorch_tpu.ops import index_score, index_select, rotary, sparse_latent_decode

ROOT = Path(__file__).resolve().parent.parent
SEED, DOC, STEPS, TOPK = 5, 48, 6, 8


def _cfg(name="_tiny-deepseek-v32"):
    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables)."""
    mdl = CausalLM.from_config(cfg, DOC + STEPS)
    return mdl, build_deepseek_v32.seeded_variables(cfg, mdl, SEED)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 64, (2, DOC + STEPS)).astype(np.int32)


@pytest.fixture(scope="module")
def want(cfg, tokens):
    return ref.forward(cfg, SEED, tokens)


@pytest.fixture(scope="module")
def served(pair, tokens):
    """A prefill in 3 chunks, then 6 forced token steps, through the sampler:
    (logits [rows, steps, V], counts)."""
    mdl, variables = pair
    cache, _ = prefill_cached(mdl, variables, jnp.asarray(tokens[:, :DOC]), mdl.init_cache(2),
                              chunk=16)
    _, logits, counts, _ = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, jnp.asarray(tokens[:, DOC:]), STEPS,
        filter_thres=1.0, logit_rows=2, start=DOC)
    return np.asarray(logits).transpose(1, 0, 2), jax.device_get(counts)


def test_from_config_reads_the_published_file():
    """Every width, the indexer's, the router's groups and YaRN's numbers as
    published; the share's parameters are the file's `parameters_here`,
    counted by the program's own init and by the reference."""
    cfg = _cfg("deepseek-v32-exp-ep16")
    mdl = CausalLM.from_config(cfg, 64)
    trunk = dict(mdl.trunk)
    assert (mdl.dim, mdl.heads, mdl.dim_head, mdl.depth, mdl.draft_layers) == (7168, 128, 192, 5, 0)
    assert trunk["ff_kinds"] == ("swiglu",) + ("swiglu_experts",) * 4
    assert (trunk["q_lora_rank"], trunk["kv_lora_rank"], trunk["v_dim"]) == (1536, 512, 128)
    assert (trunk["index_heads"], trunk["index_dim"], trunk["index_topk"]) == (64, 128, 2048)
    assert (trunk["experts_total"], trunk["experts_per_token"], trunk["moe_groups"]) == (256, 8, (8, 4))
    assert trunk["moe_score_bias"] and (trunk["moe_score"], trunk["routed_scale"]) == ("sigmoid", 2.5)
    assert not trunk["sandwich_norm"] and trunk["shared_dim"] == 2048
    spec = dict(trunk["rotary_specs"]["latent"])
    assert (spec["type"], spec["factor"], spec["original_max_position_embeddings"],
            spec["beta_fast"], spec["beta_slow"], spec["attention_factor"]) == (
                "yarn", 40, 4096, 32, 1, 1.0)
    assert trunk["softmax_mult"] == pytest.approx((0.1 * math.log(40) + 1) ** 2)
    assert mdl.param_dtype == jnp.bfloat16 and not mdl.per_row
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    count = sum(x.size for x in jax.tree.leaves(shapes["params"]))
    assert count == ref.n_params(cfg) and round(count / 1e6) == 4636
    layer = jax.eval_shape(lambda: mdl.init_cache(2, 512))["layer_0"]["attn"]
    # a position's latent and rotary key in one row of five whole tiles of lanes
    assert {k: v.shape for k, v in layer.items()} == {
        "rows": (2, 512, 640), "index_k": (2, 512, 128), "index": ()}
    assert decode_cache.kv_bytes({"layer_0": {"attn": layer}}) == 2 * 512 * (640 + 128) * 2
    published = {k: v for k, v in cfg.items() if k in cfg["published"]}
    assert set(published) == set(cfg["reduced"]) and cfg["published"]["n_routed_experts"] == 256


def test_a_latent_config_without_an_indexer_builds_what_it_built():
    """`pangu-ultra-moe-ep16.json` has no `index_topk`, `rope_scaling` or
    `n_group`: none of the new trunk options appears, so the model (which
    keys its compiled programs) is the one the parent built."""
    mdl = CausalLM.from_config(_cfg("pangu-ultra-moe-ep16"), 64)
    trunk = dict(mdl.trunk)
    assert not {"index_topk", "index_heads", "softmax_mult", "moe_groups",
                "moe_score_bias"} & set(trunk)
    layer = jax.eval_shape(lambda: mdl.init_cache(2))["layer_0"]["attn"]
    assert {k: v.shape for k, v in layer.items()} == {
        "latent": (2, 64, 512), "rope": (2, 64, 64), "index": ()}
    assert dict(trunk["rotary_specs"]["latent"]) == {"type": "default", "dim": 64,
                                                     "theta": 25600000}


def test_uncached_logits_match_the_reference(pair, tokens, want):
    """The whole sequence at once: 54 queries of which all but the first 8
    attend fewer positions than they see (the chunked form, no cache)."""
    mdl, variables = pair
    np.testing.assert_allclose(mdl.apply(variables, jnp.asarray(tokens)), want["logits"],
                               atol=3e-5)


def test_chunked_prefill_then_token_steps_match_the_references_full_forward(served, want):
    logits, counts = served
    np.testing.assert_allclose(logits, want["logits"][:, DOC:], atol=3e-5)
    # every layer scored every live position and attended exactly 8, each row-step
    live = sum(DOC + i + 1 for i in range(STEPS))
    assert counts["dsa_scored"].tolist() == [2 * live] * 3
    assert counts["dsa_selected"].tolist() == [2 * STEPS * TOPK] * 3


def test_the_selection_is_the_references_set(served, want):
    """Each layer's selected positions at each token step, as the timed
    program itself gives them for the rows whose logits it keeps, and the
    first routed layer's choice of experts."""
    picks = served[1]["picks"]
    assert picks["selected"].shape == (STEPS, 3, 2, TOPK)
    assert (picks["selected_count"] == TOPK).all()
    for step in range(STEPS):
        for layer in range(3):
            for row in range(2):
                assert set(picks["selected"][step, layer, row]) == set(
                    want["selected"][row, layer, DOC + step]), (step, layer, row)
    np.testing.assert_array_equal(np.sort(picks["experts"].transpose(1, 0, 2), -1),
                                  np.sort(want["choices"][:, DOC:], -1))
    # the selection is not the newest 8, nor does it always hold the query itself
    newest = np.arange(DOC - TOPK + 1, DOC + 1)
    assert any(set(want["selected"][0, 0, DOC]) != set(newest + s) for s in range(STEPS))
    assert any(DOC + s not in want["selected"][0, l, DOC + s]
               for l in range(3) for s in range(STEPS))


def test_a_prefill_at_once_and_a_short_sequence_take_the_dense_forms(cfg, pair):
    """A prompt of at most `index_topk` tokens selects everything: the chunk
    that starts the rows' sequences runs the expanded form, the token steps
    over a cache of 8 positions the dense kernel, and both agree with the
    reference (whose mask is then the causal one)."""
    mdl = CausalLM.from_config(cfg, TOPK)
    variables = pair[1]
    toks = np.random.default_rng(1).integers(0, 64, (2, TOPK)).astype(np.int32)
    cache, _ = prefill_cached(mdl, variables, jnp.asarray(toks[:, :5]), mdl.init_cache(2))
    cache = decode_cache.set_index(cache, jnp.asarray(5, jnp.int32))
    _, logits, counts, _ = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(0), cache, jnp.asarray(toks[:, 5:]), 3,
        filter_thres=1.0, logit_rows=2)
    np.testing.assert_allclose(np.asarray(logits).transpose(1, 0, 2),
                               ref.forward(cfg, SEED, toks)["logits"][:, 5:], atol=3e-5)
    assert "dsa_selected" not in counts and "selected" not in counts["picks"]


def test_a_prefill_in_chunks_refuses_a_trunk_that_is_not_latent():
    """Before anything runs: `generate_lm.py --prefill_chunk` on another
    family's config says why, and no cache is half written."""
    from dalle_pytorch_tpu.models.lm import prefill_chunks

    mdl = CausalLM.from_config(_cfg("_tiny-olmo"), 24)
    with pytest.raises(NotImplementedError, match="latent"):
        prefill_chunks(mdl, None, jnp.zeros((1, 16), jnp.int32), 8)


def test_ties_go_to_the_lower_position_and_a_short_row_selects_what_it_has():
    scores = jnp.asarray([[1.0, 3.0, 3.0, 0.5, 3.0, 3.0, 2.0, 9.0, 9.0, 9.0],
                          [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                          [5.0, 4.0, 7.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0]], jnp.float32)
    lengths = jnp.asarray([7, 10, 2], jnp.int32)  # row 0 sees 7 positions, row 2 two
    mask, count = index_select.selected_mask(scores, lengths, 4)
    assert count.tolist() == [4, 4, 2]
    picked = np.asarray(index_select.selected_indices(mask, 4))
    # row 0: 3.0 four times and a 2.0: the four 3.0; with k = 3 the first three of them
    assert picked[0].tolist() == [1, 2, 4, 5] and picked[1].tolist() == [0, 1, 2, 3]
    assert picked[2].tolist() == [0, 1, 0, 0] and mask[2].tolist() == [True, True] + [False] * 8
    three = index_select.selected_mask(scores, lengths, 3)[0]
    assert np.flatnonzero(three[0]).tolist() == [1, 2, 4] and int(three.sum()) == 3 + 3 + 2
    # as lax.top_k orders equal scores
    live = jnp.where(jnp.arange(10) < lengths[:, None], scores, -jnp.inf)
    assert set(np.asarray(jax.lax.top_k(live, 3)[1])[0]) == {1, 2, 4}


@pytest.mark.parametrize("n,k", [(300, 17), (128, 128), (131, 1)])
def test_compaction_gives_the_masks_positions_in_order(n, k):
    rng = np.random.default_rng(n)
    mask = np.zeros((3, n), bool)
    for row, count in enumerate((k, k // 2, 0)):
        mask[row, rng.choice(n, count, replace=False)] = True
    got = np.asarray(index_select.selected_indices(jnp.asarray(mask), k))
    for row in range(3):
        held = np.flatnonzero(mask[row])
        assert got[row, :len(held)].tolist() == held.tolist() and (got[row, len(held):] == 0).all()


def test_index_score_kernel_matches_the_dense_form():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (3, 4, 16))
    w = jax.random.normal(ks[1], (3, 4))
    keys = jax.random.normal(ks[2], (3, 50, 16))
    lengths = jnp.asarray([50, 17, 1], jnp.int32)
    want = jnp.einsum("bh,bhl->bl", w, jax.nn.relu(jnp.einsum("bhd,bld->bhl", q, keys)))
    for block in (16, 50):  # blocks that overhang the cache, and one block for all of it
        got = np.asarray(index_score._emit(q, w, keys, lengths, block=block, interpret=True))
        for row, n in enumerate(lengths.tolist()):
            np.testing.assert_allclose(got[row, :n], want[row, :n], atol=1e-5)
            assert (got[row, n:] <= -1e29).all()


def test_sparse_attend_reads_the_selected_positions_alone():
    """Poison in every position left out changes nothing."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q_c, q_r = jax.random.normal(ks[0], (2, 4, 16)), jax.random.normal(ks[1], (2, 4, 8))
    latent, rope = jax.random.normal(ks[2], (2, 40, 16)), jax.random.normal(ks[3], (2, 8, 40))
    picked = jnp.asarray([[3, 7, 8, 20, 39, 0], [1, 2, 0, 0, 0, 0]], jnp.int32)
    count = jnp.asarray([5, 2], jnp.int32)
    attend = lambda l, r: sparse_latent_decode.sparse_latent_decode_attention(
        q_c, q_r, l, r, picked, count, sm_scale=0.3)
    seen = jnp.zeros((2, 40), bool).at[0, picked[0, :5]].set(True).at[1, picked[1, :2]].set(True)
    s = (jnp.einsum("bhr,blr->bhl", q_c, latent) + jnp.einsum("bhd,bdl->bhl", q_r, rope)) * 0.3
    want = jnp.einsum("bhl,blr->bhr", jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), -1),
                      latent)
    np.testing.assert_allclose(attend(latent, rope), want, atol=1e-5)
    poisoned = attend(jnp.where(seen[..., None], latent, jnp.nan),
                      jnp.where(seen[:, None], rope, jnp.nan))
    np.testing.assert_allclose(poisoned, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [24, 128])
def test_one_fetch_of_rows_is_the_two_leaves_two_fetches_bit_for_bit(width, dtype):
    """`fetch_selected` out of an indexed layer's rows (latent 16 | rotary 8
    | padding to `width`) returns what the two gathers out of `latent` and
    `rope` return on the same values, and the attend over either is one."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    latent = jax.random.normal(ks[0], (3, 50, 16), dtype)
    rope = jax.random.normal(ks[1], (3, 8, 50), dtype)
    rows = jnp.concatenate([latent, rope.transpose(0, 2, 1),
                            jnp.full((3, 50, width - 24), jnp.nan, dtype)], axis=-1)
    picked = jnp.asarray(np.stack([np.sort(np.random.default_rng(r).choice(50, 12, replace=False))
                                   for r in range(3)]), jnp.int32)
    two = sparse_latent_decode.fetch_selected(latent, rope, picked)
    one = sparse_latent_decode.fetch_selected(rows, None, picked, (16, 8))
    assert [t.shape for t in one] == [(3, 12, 16), (3, 8, 12)]
    for got, want in zip(one, two):
        assert got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    q_c, q_r = jax.random.normal(ks[2], (3, 4, 16), dtype), jax.random.normal(ks[3], (3, 4, 8), dtype)
    count = jnp.asarray([12, 5, 1], jnp.int32)
    attend = lambda *leaves: np.asarray(sparse_latent_decode.sparse_latent_decode_attention(
        q_c, q_r, *leaves, picked, count, sm_scale=0.3), np.float32)
    np.testing.assert_array_equal(attend(rows, None), attend(latent, rope))


# sha-256 (16 hex digits) of what PR 40's two-leaf program gave for `replayed`'s
# drive, computed from that tree before the row leaf was built
PINNED = {"tokens": "e360d19e4ee78727", "logits": "961428d671b74704",
          "selected": "5ad5fd9226e921c3"}


def _replay(mdl, variables, tokens):
    """Two documents prefilled in chunks of 16, the first copied to sessions 0
    and 2 and the second to session 1 (`place_rows`), then a turn of 2 forced
    and 4 SAMPLED token steps (top 10%, temperature 1): the tokens [3, 6], the
    three rows' logits [6, 3, V] and every layer's selection [6, 3, 3, 8]."""
    cache = mdl.init_cache(3)
    for doc, sessions in ((0, (0, 2)), (1, (1,))):
        fresh, _ = lm.prefill_chunks(mdl, variables, jnp.asarray(tokens[doc:doc + 1, :DOC]), 16)
        for row in sessions:
            cache = lm.place_rows(mdl, cache, fresh, row)
    forced = jnp.asarray(tokens[[0, 1, 0], DOC:DOC + 2])
    toks, logits, counts, _ = generate_tokens_cached(
        mdl, variables, jax.random.PRNGKey(7), cache, forced, STEPS, filter_thres=0.9,
        temperature=1.0, logit_rows=3, start=DOC)
    return {"tokens": np.asarray(toks), "logits": np.asarray(logits),
            "selected": np.asarray(counts["picks"]["selected"])}


@pytest.fixture(scope="module")
def replayed(pair, tokens):
    assert "rows" in pair[0].init_cache(1)["layer_0"]["attn"]
    return _replay(*pair, tokens)


@pytest.mark.parametrize("what", sorted(PINNED))
def test_chunks_copies_and_token_steps_give_the_two_leaf_programs_bits(replayed, what):
    import hashlib

    got = replayed[what]
    assert got.dtype == (np.float32 if what == "logits" else np.int32)
    assert hashlib.sha256(np.ascontiguousarray(got).tobytes()).hexdigest()[:16] == PINNED[what]


def test_a_two_leaf_cache_under_an_indexer_still_gives_the_same_bits(pair, tokens, replayed,
                                                                     monkeypatch):
    """The layer reads its cache's layout off the leaves: handed the two
    leaves beside an index key (what `layer_spec` made until PR 41), the same
    drive gives the row leaf's tokens, logits and selections, bit for bit."""
    real = decode_cache.layer_spec

    def two_leaves(index_dim=None, **geometry):
        layer = real(**geometry)
        spec = jax.ShapeDtypeStruct((geometry["batch"], geometry["max_len"], index_dim),
                                    geometry["dtype"])
        layer["attn"] = {**layer["attn"], "index_k": spec}
        return layer

    monkeypatch.setattr(decode_cache, "layer_spec", two_leaves)
    assert set(pair[0].init_cache(1)["layer_0"]["attn"]) == {"latent", "rope", "index", "index_k"}
    for what, got in _replay(*pair, tokens).items():
        np.testing.assert_array_equal(got, replayed[what])


def test_the_router_limits_its_choice_to_the_best_groups_by_the_biased_score(cfg):
    """Against the reference's router on the rehearsal's 32 experts in 4
    groups of which 2 stay: the bias changes choices, the group limit keeps
    out experts that are among a token's best four overall."""
    d = ref.dims(cfg)
    lp = ref.init_layer(cfg, SEED, 1)
    b = jax.random.normal(jax.random.PRNGKey(3), (64, d["dim"]))
    weights, chosen = ref.route(b, lp["router_w"], lp["router_b"], d)
    probs = jax.nn.sigmoid(jnp.dot(b, lp["router_w"], precision="highest"))
    top, experts = moe.choose(probs, 4, lp["router_b"], (4, 2))
    np.testing.assert_array_equal(experts, chosen)
    np.testing.assert_allclose(top, jnp.take_along_axis(probs, chosen, -1))
    assert (np.unique(np.asarray(experts) // 8, axis=None).size > 2  # over the tokens: every group
            and all(len(set(row // 8)) <= 2 for row in np.asarray(experts)))  # a token: two
    free = jax.lax.top_k(probs + lp["router_b"], 4)[1]  # the biased choice without the limit
    assert (np.sort(free, -1) != np.sort(experts, -1)).any()
    unbiased = moe.choose(probs, 4, None, (4, 2))[1]
    assert (np.sort(unbiased, -1) != np.sort(experts, -1)).any()
    # one group, with and without a bias, is what it was
    np.testing.assert_array_equal(moe.choose(probs, 4, None)[1], jax.lax.top_k(probs, 4)[1])
    np.testing.assert_array_equal(moe.choose(probs, 4, lp["router_b"])[1], free)
    assert float(weights.sum(-1).max()) == pytest.approx(2.5, rel=1e-5)


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(cfg):
    """Expert parallelism's contract (model-configs guide, section 4): the
    parts that the four shares of the rehearsal's 32 experts give (8 experts
    each; the published model: 16 shares of 16), with what every chip computes
    alike, the shared expert, counted once, are the uncut reference's layer
    under the group-limited, bias-corrected choice, which every chip makes
    alike over all 32. Through the PROGRAM's routed layer."""
    whole = dict(cfg, n_routed_experts=32)  # the reference holds every expert
    d = ref.dims(whole)
    lp = ref.init_layer(whole, SEED, 1)
    y = jax.random.normal(jax.random.PRNGKey(3), (32, d["dim"]))
    b = ref._rms(y, lp["norm_ff_g"], d["eps"])
    weights, _ = ref.route(b, lp["router_w"], lp["router_b"], d)
    uncut = ref.shared_expert(b, lp) + ref.routed_experts(b, weights, lp, d, held=(0, 32))
    shared = {"shared_gate": lp["sh_gate_w"], "shared_up": lp["sh_up_w"],
              "shared_out": lp["sh_down_w"]}
    routed, with_shared = [], []
    for first in (0, 8, 16, 24):
        params = {"router": lp["router_w"], "router_bias": lp["router_b"],
                  "w_gate": lp["gate_w"][first:first + 8], "w_up": lp["up_w"][first:first + 8],
                  "w_out": lp["down_w"][first:first + 8]}
        kw = dict(dim=d["dim"], expert_dim=d["expert_dim"], experts_total=32,
                  experts_per_token=4, experts_held=(first, 8), buffer_rows=128, score="sigmoid",
                  routed_scale=2.5, score_bias=True, groups=(4, 2))
        routed.append(moe.RoutedExperts(**kw).apply({"params": params}, b[None])[0])
        with_shared.append(moe.RoutedExperts(**kw, shared_dim=d["shared_dim"]).apply(
            {"params": {**params, **shared}}, b[None])[0])
    the_shared = with_shared[0] - routed[0]  # what every chip computes alike
    np.testing.assert_allclose(the_shared, ref.shared_expert(b, lp), atol=2e-5)
    np.testing.assert_allclose(sum(routed) + the_shared, uncut, atol=3e-5)
    # a share whose groups a token's choice leaves out gives that token nothing
    assert float(jnp.abs(routed[0]).sum(-1).min()) == 0.0


def test_yarn_table_and_softmax_scale(cfg):
    """The program's rotary table under the config's `rope_scaling` is the
    reference's, and is not the plain one; the softmax scale carries m^2."""
    d = ref.dims(cfg)
    trunk = dict(CausalLM.from_config(cfg, 64).trunk)
    spec = dict(trunk["rotary_specs"]["latent"])
    np.testing.assert_allclose(rotary.rotary_inv_freq(spec), ref.yarn_inv_freq(d), rtol=1e-6)
    cos, sin = rotary.rotary_cos_sin(np.arange(60), spec)
    want = ref.cos_sin(d, 60)
    np.testing.assert_allclose(cos, want[0], atol=1e-6)
    np.testing.assert_allclose(sin, want[1], atol=1e-6)
    plain = rotary.rotary_inv_freq({"type": "default", "dim": 8, "theta": 10000})
    assert plain[0] == ref.yarn_inv_freq(d)[0] and plain[-1] == pytest.approx(
        40 * ref.yarn_inv_freq(d)[-1])
    m = 0.1 * math.log(40) + 1
    assert trunk["softmax_mult"] == pytest.approx(m * m) == pytest.approx(d["softmax_mult"])
    assert d["rotary_scale"] == 1.0


def test_the_loop_rehearses_on_the_cpu():
    """`benchmark/run.py --workload _tiny.generate_deepseek_v32`: the loop of
    `deepseek32.decode.32k` at the rehearsal size, every check within its
    limit."""
    from benchmark import harness
    from benchmark.loops import generate_deepseek_v32 as loop

    run = harness.Run("_tiny.generate_deepseek_v32", 3900000001, 0.5, False, 0.0)
    run.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    values = loop.run(run)
    assert run.correct and values["generate_tokens_per_s"] > 0, run.checks
    names = {c["name"] for c in run.checks}
    assert {"logit_gap", "logit_gap_p99", "greedy_gap", "greedy_gap_worst", "route_flip_share",
            "select_flip_share", "selected_short", "copies_off", "moe_dropped",
            "bad_batches"} <= names
    assert run.counters["selected_per_row_step"] == TOPK
    assert run.counters["batches_counted"] % 2 == 0
    # a selection one position short, or one that repeats a position, is counted
    picked = np.asarray([[0, 1, 2, 3], [0, 1, 1, 3], [0, 1, 2, 9]])
    assert loop.selected_short(picked, np.asarray([4, 4, 4]), np.asarray([5, 5, 5]), 4) == 2
    assert loop.select_flip_share(np.asarray([[0, 1, 2, -1]]), np.asarray([[2, 1, 5, 7]])) == (
        pytest.approx(1 / 3))
