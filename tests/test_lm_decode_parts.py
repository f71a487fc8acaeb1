"""The parts that generation with the latent-attention language model is made
of, each against a plain statement of what it computes, at small sizes on
the CPU: the latent decode kernel (interpreted) on ragged rows, the routed
layer's sigmoid scores, scale and shared expert, the share test, the
bf16-stored parameter tree the configuration states, the latent kind of cache
layer, the new scopes' components, the grouped product's row tile, a token
step's routed layer over a buffer whose unowned rows are NaN, and the
`generate_lm.py` command. The model end to end is `tests/test_lm_decode.py`."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import pangu_ref
from dalle_pytorch_tpu.models import decode_cache, moe
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.models.moe import RoutedExperts
from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.ops import grouped_matmul
from dalle_pytorch_tpu.ops.latent_decode import latent_decode_attention

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 32, 7


def _cfg(name="_tiny-pangu"):
    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.mark.parametrize("block,leaf,lengths", [
    (8, 50, (50, 17, 1)), (16, 50, (50, 17, 1)), (64, 50, (50, 17, 1)),  # none a multiple of a block
    (16, 50, (16, 32, 48)),  # a length on a block's edge: the row's last live block is whole
    (16, 48, (48, 1, 33)),  # length 1, length = leaf = whole blocks, one position into a block
    (128, 300, (300, 256, 257)),  # the leaf cuts the last block short: junk past it, in reach
    (None, 300, (300, 129, 1)),  # the rule's block for the leaf: one, under the cap
    (None, 50, (50, 17, 0)),  # a row with nothing live reads nothing and gives zeros
])
def test_the_latent_decode_kernel_matches_dense_on_ragged_rows(block, leaf, lengths):
    B, H, R, dr, L = 3, 4, 16, 8, leaf
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q_c, q_r = jax.random.normal(ks[0], (B, H, R)), jax.random.normal(ks[1], (B, H, dr))
    latent, rope = jax.random.normal(ks[2], (B, L, R)), jax.random.normal(ks[3], (B, dr, L))
    lengths = jnp.asarray(lengths)
    got = latent_decode_attention(q_c, q_r, latent, rope, lengths, sm_scale=0.2, block=block)
    s = (jnp.einsum("bhr,blr->bhl", q_c, latent) + jnp.einsum("bhd,bdl->bhl", q_r, rope)) * 0.2
    live = jnp.arange(L)[None, None] < lengths[:, None, None]
    weights = jnp.where(live, jax.nn.softmax(jnp.where(live, s, -1e30), -1), 0)
    want = jnp.einsum("bhl,blr->bhr", weights, latent)
    np.testing.assert_allclose(got, want, atol=2e-6)
    # positions past a row's length are never read: garbage there changes nothing
    junk = jnp.where(jnp.arange(L)[None, :, None] < lengths[:, None, None], latent, jnp.nan)
    wild = jnp.where(jnp.arange(L)[None, None] < lengths[:, None, None], rope, jnp.nan)
    again = latent_decode_attention(q_c, q_r, junk, wild, lengths, sm_scale=0.2, block=block)
    np.testing.assert_array_equal(got, again)


@pytest.mark.parametrize("leaf,block,steps", [
    (8480, 2944, 3),  # `pangu.decode.8k`: 2 x 2,944 and 2,592, not 8 x 1,024 and 288
    (2048, 2048, 1),  # `deepseek32.decode.32k`'s fetched positions
    (2944, 2944, 1), (2945, 1536, 2), (33792, 2816, 12), (50, 50, 1),
])
def test_the_latent_kernels_blocks_are_the_one_rules_under_its_own_cap(leaf, block, steps):
    """What the sweep chose (ops/latent_decode.py's comment), from the rule
    `decode_grouped` takes its blocks from: one function, a cap a kernel."""
    from dalle_pytorch_tpu.ops import grouped_decode, latent_decode, pallas_attention

    assert latent_decode.BLOCK_POSITIONS == 2944
    got = pallas_attention.even_block(leaf, latent_decode.BLOCK_POSITIONS)
    assert (got, -(-leaf // got)) == (block, steps)
    assert latent_decode.even_block is grouped_decode.even_block is pallas_attention.even_block
    assert grouped_decode._block(leaf) == pallas_attention.even_block(leaf, grouped_decode.BLOCK_POSITIONS)
    # and the call with no `block=` walks the leaf in those
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: latent_decode.latent_decode_attention(*a, sm_scale=1.0))(
        f32(2, 4, 16), f32(2, 4, 8), f32(2, leaf, 16), f32(2, 8, leaf), jnp.zeros(2, jnp.int32))
    (call,) = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, steps)
    assert call.params["grid_mapping"].block_mappings[2].block_shape[1].block_size == block


def _layer_by_loop(params, x, k, held, scale):
    """[T, dim] float64: the routed layer of tokens [T, dim] as a loop over
    tokens and their chosen experts: sigmoid scores, the `k` largest
    renormalised and scaled, the experts `held = (first, count)` alone, the
    shared expert beside them."""
    p = {n: np.asarray(v, np.float64) for n, v in params.items()}
    silu = lambda t: t / (1 + np.exp(-t))
    first, count = held
    out = []
    for h in np.asarray(x, np.float64):
        s = 1 / (1 + np.exp(-(h @ p["router"])))
        chosen = np.argsort(-s)[:k]
        want = (silu(h @ p["shared_gate"]) * (h @ p["shared_up"])) @ p["shared_out"]
        for e in chosen:
            if first <= e < first + count:
                w = scale * s[e] / s[chosen].sum()
                g = e - first
                want += w * (silu(h @ p["w_gate"][g]) * (h @ p["w_up"][g])) @ p["w_out"][g]
        out.append(want)
    return np.stack(out)


def test_the_router_scores_scaling_and_shared_expert_match_a_per_token_loop():
    dim, width, total, k = 16, 8, 8, 2
    layer = RoutedExperts(dim=dim, expert_dim=width, experts_total=total, experts_per_token=k,
                          experts_held=(2, 4), buffer_rows=64, score="sigmoid",
                          routed_scale=2.5, shared_dim=12)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, dim))
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    got = np.asarray(layer.apply({"params": params}, x))[0]
    np.testing.assert_allclose(got, _layer_by_loop(params, x[0], k, (2, 4), 2.5), atol=2e-5)


def test_the_routed_layers_defaults_are_the_softmax_router_alone():
    layer = RoutedExperts(dim=16, expert_dim=8, experts_total=4, experts_per_token=2,
                          experts_held=(0, 4), buffer_rows=16)
    params = layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))["params"]
    assert sorted(params) == ["router", "w_gate", "w_out", "w_up"]
    assert {str(v.dtype) for v in params.values()} == {"float32"}


def test_the_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer(cfg):
    """Expert parallelism's contract (model-configs guide, section 4): the
    parts that the four shares give (experts 0-1, 2-3, 4-5, 6-7), with what
    every chip computes alike, the shared expert, and the residual counted
    once, are the uncut reference's layer. Through the PROGRAM's routed layer."""
    whole = dict(cfg, n_routed_experts=8)  # the reference holds every expert
    d = pangu_ref.dims(whole)
    lp = pangu_ref.init_layer(whole, SEED, 1)
    y = jax.random.normal(jax.random.PRNGKey(3), (N, d["dim"]))
    b = pangu_ref._rms(y, lp["norm_ff_g"], d["eps"])
    weights, _ = pangu_ref.route(b, lp["router_w"], d)
    uncut = pangu_ref.shared_expert(b, lp) + pangu_ref.routed_experts(
        b, weights, lp, d, held=(0, 8))
    shared = {"shared_gate": lp["sh_gate_w"], "shared_up": lp["sh_up_w"],
              "shared_out": lp["sh_down_w"]}
    routed, with_shared = [], []
    for first in (0, 2, 4, 6):
        params = {"router": lp["router_w"], "w_gate": lp["gate_w"][first:first + 2],
                  "w_up": lp["up_w"][first:first + 2], "w_out": lp["down_w"][first:first + 2]}
        kw = dict(dim=d["dim"], expert_dim=d["expert_dim"], experts_total=8, experts_per_token=2,
                  experts_held=(first, 2), buffer_rows=2 * N, score="sigmoid", routed_scale=2.5)
        routed.append(RoutedExperts(**kw).apply({"params": params}, b[None])[0])
        with_shared.append(RoutedExperts(**kw, shared_dim=d["shared_dim"]).apply(
            {"params": {**params, **shared}}, b[None])[0])
    the_shared = with_shared[0] - routed[0]  # what every chip computes alike
    np.testing.assert_allclose(the_shared, pangu_ref.shared_expert(b, lp), atol=2e-5)
    np.testing.assert_allclose(sum(routed) + the_shared, uncut, atol=3e-5)
    # and one share alone is what the cut reference computes
    cut = pangu_ref.shared_expert(b, lp) + pangu_ref.routed_experts(
        b, weights, {k: v[:2] for k, v in lp.items() if v.ndim == 3}, d, held=(0, 2))
    np.testing.assert_allclose(with_shared[0], cut, atol=2e-5)


def test_parameters_stored_in_bf16_give_the_tree_the_configuration_states():
    cfg = _cfg("pangu-ultra-moe-ep16")
    mdl = CausalLM.from_config(cfg, 64)
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    name = lambda path: "/".join(str(getattr(k, "key", k)) for k in path)
    float32 = {name(p) for p, x in leaves if x.dtype == jnp.float32}
    assert all(n.endswith("/scale") or n.endswith("/router") for n in float32)
    assert all(x.dtype in (jnp.float32, jnp.bfloat16) for _, x in leaves)
    count = sum(int(np.prod(x.shape)) for _, x in leaves)
    assert count == pangu_ref.n_params(cfg) == 4_919_139_840
    assert "4919 M" in cfg["deployment"]["parameters_here"]
    stored = sum(int(np.prod(x.shape)) * x.dtype.itemsize for _, x in leaves)
    assert 9.83e9 < stored < 9.86e9  # "9.84 GB in bf16"
    # every published width is the file's own
    for key, value in dict(hidden_size=7680, q_lora_rank=1536, kv_lora_rank=512,
                           qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                           num_attention_heads=128, intermediate_size=18432,
                           moe_intermediate_size=2048, num_experts_per_tok=8,
                           routed_scaling_factor=2.5, rms_norm_eps=1e-5,
                           rope_theta=25600000).items():
        assert cfg[key] == value
    assert cfg["published"] == dict(num_hidden_layers=61, first_k_dense_replace=3,
                                    n_routed_experts=256, vocab_size=153600,
                                    num_nextn_predict_layers=1)
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert mdl.trunk["experts_total"] == 256 and mdl.trunk["experts_held"] == (0, 16)


def test_the_latent_cache_has_no_heads_axis_and_the_bytes_the_issue_states():
    cfg = _cfg("pangu-ultra-moe-ep16")
    mdl = CausalLM.from_config(cfg, 8480)
    cache = jax.eval_shape(lambda: mdl.init_cache(64))
    assert decode_cache.layout_of(cache) == decode_cache.PER_LAYER and len(cache) == 5
    attn = cache["layer_0"]["attn"]
    assert attn["latent"].shape == (64, 8480, 512) and attn["rope"].shape == (64, 64, 8480)
    assert attn["index"].shape == () and attn["latent"].dtype == jnp.bfloat16
    assert decode_cache.kv_bytes(cache) == 64 * 8480 * 5 * 1152  # 3.13 GB


def test_the_cache_module_writes_and_stamps_a_latent_layer():
    cache = decode_cache.make(decode_cache.PER_LAYER, 2, kind="latent", batch=2, max_len=6,
                              heads=4, dim_head=16, dim=64, latent_dim=3, rope_dim=2)
    cache = decode_cache.set_index(cache, jnp.asarray(4))
    attn = cache["layer_1"]["attn"]
    assert int(attn["index"]) == 4
    written, length = decode_cache.write(
        attn, {"latent": jnp.ones((2, 2, 3)), "rope": 2 * jnp.ones((2, 2, 2))}, None)
    assert length == 6
    assert np.array_equal(np.asarray(written["latent"][0, :, 0]), [0, 0, 0, 0, 1, 1])
    assert np.array_equal(np.asarray(written["rope"][1, 0]), [0, 0, 0, 0, 2, 2])
    with pytest.raises(AssertionError, match="per layer"):
        decode_cache.make(decode_cache.STACKED, 2, kind="latent", batch=2, max_len=6, heads=4,
                          dim_head=16, dim=64, latent_dim=3, rope_dim=2)


@pytest.mark.parametrize("path,want", [
    ("jit(lm_sample)/while/body/CausalLM.decode_step/transformer/attn_1/mla_proj/to_q/dot_general", "mla_proj"),
    ("jit(lm_sample)/while/body/transformer/attn_1/mla_proj/q_norm/rsqrt", "mla_proj"),
    ("jit(lm_sample)/while/body/transformer/attn_1/mla_attend/decode_latent", "mla_attend"),
    ("jit(lm_sample)/while/body/transformer/attn_1/cache_write/dynamic_update_slice", "cache_write"),
    ("jit(lm_sample)/while/body/transformer/ff_2/moe_shared/dot_general", "moe_shared"),
    ("jit(lm_sample)/while/body/transformer/ff_2/moe_router/dot_general", "moe_router"),
    ("jit(lm_sample)/while/body/transformer/ff_0/w_gate/dot_general", "ff"),
    ("jit(lm_sample)/while/body/sample/top_k", "sample"),
    ("jit(lm_sample)/while/body/transformer/ff_norms_out_3/rsqrt", "norm_resid"),
    ("jit(lm_sample)/while/body/CausalLM.decode_step/logits_dense/dot_general", "head"),
])
def test_the_new_scopes_fall_under_their_own_components(path, want):
    assert scopes.component(path)[0] == want
    assert want in scopes.COMPONENTS


def test_the_latent_kernel_is_known_by_its_instruction_name():
    assert scopes.component(None, "custom-call", "%decode_latent.70") == ("mla_attend", "fwd")


@pytest.mark.parametrize("rows,groups,tile", [
    (512, 16, 128),  # a token step: 2 rows an expert, a tile no taller than the MXU
    (262144, 16, 512), (32768, 16, 512),  # training and prefill buffers keep the whole tile
    (192, 4, 128), (64, 16, 64),  # the rule asks the share, not the buffer's size
    (384, 16, 128), (256, 16, 128), (192, 16, 128),  # a verify step of 24 or 16 sessions, a one-position step
    (40, 16, 40),  # all the rows of a buffer shorter than the MXU
    (384, 1, 384),  # `gmm_drhs` asks as one group and keeps the whole buffer
])
def test_the_grouped_products_row_tile_follows_a_groups_share(rows, groups, tile):
    assert grouped_matmul._row_tile(rows, groups) == tile


@pytest.mark.parametrize("sizes", [
    (9, 0, 3, 7, 0, 0, 5, 0, 6, 0, 4, 0, 0, 5, 0, 4),  # 43 rows on 8 groups: all on tile 0
    (40, 0, 35, 0, 0, 30, 25, 0, 0, 0, 12, 0, 0, 0, 8, 0),  # 150: group 5 straddles the edge at 128
    (0,) * 16,
], ids=["43_on_8", "150_straddling", "no_row"])
def test_a_verify_steps_rows_product_walks_tiles_a_group_may_straddle(sizes):
    """`gmm_fwd` at a verify step's form (24 sessions x 2 positions x 8
    choices = 384 rows, 16 groups; small K and N): every owned row is its own
    group's product whichever tile it lies on, and the tile the product got
    is on the module's record."""
    rows, k, n, tile = 384, 128, 256, 128
    grouped_matmul.forget()
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (16, k, n), jnp.float32) / np.sqrt(k)
    live = sum(sizes)
    if live > tile:
        ends = np.cumsum(sizes)
        assert any(a < tile < b for a, b in zip(ends - np.asarray(sizes), ends))
    got = grouped_matmul.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    owner = np.repeat(np.arange(16), sizes)
    want = jnp.einsum("rk,rkn->rn", lhs[:live], rhs[owner], preferred_element_type=jnp.float32)
    assert got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got[:live]), np.asarray(want), atol=1e-5)
    assert grouped_matmul.row_tiles == {("gmm_fwd", 384, 16): tile}
    grouped_matmul.forget()
    assert grouped_matmul.row_tiles == {}


@pytest.mark.parametrize("tokens,touched", [
    (64, ()), (64, (2, 5, 6, 11, 15)), (48, (0, 2, 5, 6, 9, 11, 13, 15)),
], ids=["no_expert", "5_of_16", "verify_8_of_16"])
def test_a_token_steps_routed_layer_takes_nothing_from_rows_no_group_owns(monkeypatch, tokens, touched):
    """A token step's shapes (64 rows, 8 choices each, 16 experts held, a
    buffer of 512 rows in tiles of 128) with the router steered: no held
    expert gets a row (the layer is then its shared expert alone), or 5 of
    the 16 do (58 rows, as in the cell); and a verify step's (24 sessions x 2
    positions: 48 rows, a buffer of 384, 43 rows on 8 experts). A group
    without rows has no pair in the forward product's work list, so whole
    tiles of the buffer are never written; here every row that no group owns
    comes out of every grouped product as NaN, and none may reach a token."""
    dim, width, total, k, held = 32, 16, 32, 8, 16
    present = {64: 58, 48: 43}[tokens] if touched else 0  # the tokens t with t % 11 send a row
    assert grouped_matmul._row_tile(tokens * k, held) == 128

    def poisoned(lhs, rhs, group_sizes):
        out = grouped_matmul.grouped_matmul(lhs, rhs, group_sizes)
        owned = jnp.arange(out.shape[0])[:, None] < jnp.sum(group_sizes)
        return jnp.where(owned, out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    layer = RoutedExperts(dim=dim, expert_dim=width, experts_total=total, experts_per_token=k,
                          experts_held=(0, held), buffer_rows=tokens * k, score="sigmoid",
                          routed_scale=2.5, shared_dim=24)
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(tokens, total)).astype(np.float32) * 0.1
    t = np.arange(tokens)
    for j in range(k):  # every choice an expert held elsewhere ...
        logits[t, held + (t + j) % (total - held)] = 4.0 + j
    if touched:  # ... but the first of 58 tokens, which is one of the touched
        sends = t[t % 11 != 0]
        logits[sends, held + sends % (total - held)] = 0.0
        logits[sends, np.asarray(touched)[sends % len(touched)]] = 4.0
    x = rng.normal(size=(tokens, dim)).astype(np.float32)
    x[:, :total] = logits  # a token's first features are its router logits
    params = layer.init(jax.random.PRNGKey(2), jnp.zeros((1, 8, dim)))["params"]
    params = {**params, "router": jnp.eye(dim, total)}
    grouped_matmul.forget()
    got, aux = layer.apply({"params": params}, jnp.asarray(x)[None], mutable=["stats"])
    got, load = np.asarray(got)[0], np.asarray(aux["stats"]["moe_load"])
    assert np.flatnonzero(load).tolist() == list(touched)
    assert int(aux["stats"]["moe_rows"]) == present
    assert int(aux["stats"]["moe_dropped"]) == 0 and np.isfinite(got).all()
    assert grouped_matmul.row_tiles == {("gmm_fwd", tokens * k, held): 128}
    if touched:
        np.testing.assert_allclose(got, _layer_by_loop(params, x, k, (0, held), 2.5), atol=2e-5)
    else:
        np.testing.assert_array_equal(got, np.asarray(layer.apply(
            {"params": params}, jnp.asarray(x), method=RoutedExperts.shared)))


def test_the_cli_generates_token_ids_from_seeded_prompts(tmp_path):
    out = tmp_path / "ids.json"
    tiny = ("hidden_size=64 num_attention_heads=4 q_lora_rank=24 kv_lora_rank=16 "
            "qk_nope_head_dim=8 qk_rope_head_dim=8 v_head_dim=8 intermediate_size=96 "
            "moe_intermediate_size=32 n_routed_experts=4 num_hidden_layers=3 vocab_size=64 "
            "dtype=float32 weights_dtype=float32").split()
    p = subprocess.run(
        [sys.executable, str(ROOT / "generate_lm.py"), "--prompts", "seeded:3", "--batch", "2",
         "--prompt_len", "12", "--max_new_tokens", "6", "--filter_thres", "1.0",
         "--out", str(out), "--set", *tiny],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(out.read_text())
    assert np.asarray(got["tokens"]).shape == (2, 6)
    assert 0 <= np.min(got["tokens"]) and np.max(got["tokens"]) < 64
    assert "benchmark" not in "".join(
        line for line in open(ROOT / "generate_lm.py") if line.startswith(("import", "from")))
