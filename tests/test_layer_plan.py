"""`Transformer.plan`: what each layer of a stack IS (its mixer and that
mixer's cached path, its cache kind and index rank, its rotary table, its
feed-forward) is decided once, from the trunk's options, and everything else
reads it. Held here for the tiny model of every family the suite builds: the
cache `init_cache` builds has the plan's kinds and index rank, every bound
mixer is on the plan's path, and the plan is the same bound or not."""

import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.models import attention, decode_cache
from dalle_pytorch_tpu.models.attention import (
    CCA, DALLE, LANES, LATENT, LINEAR, ROWS, SSM, Attention, ConvLatentAttention,
    GatedDeltaAttention, LatentAttention, Mamba2Mixer)
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.models.moe import RoutedExperts
from dalle_pytorch_tpu.models.transformer import (
    NO_MIXER, ROUTED_KINDS, Transformer, routed_layers)

ROOT = pathlib.Path(__file__).resolve().parent.parent
SEQ = 24
DALLE_TRUNK = dict(dim=32, depth=4, seq_len=SEQ, heads=2, dim_head=16, image_fmap_size=4,
                   shift_tokens=True)
# family -> a language model's rehearsal configuration, or the DALL-E trunk's options
FAMILIES = {
    "dalle_patterned": dict(DALLE_TRUNK, attn_types=("full", "axial_row", "conv_like"),
                            shared_attn_ids=(0, 1, 2, 0), shared_ff_ids=(0, 1, 2, 0)),
    "dalle_scan": dict(DALLE_TRUNK, attn_types=("full", "axial_col"), executor="scan"),
    "window_full_shared_kv": "_tiny-mellum",
    "window_full_mtp": "_tiny-kexaone",
    "linear_full": "_tiny-olmo",
    "latent": "_tiny-pangu",
    "latent_indexed": "_tiny-deepseek-v32",
    "one_sublayer_ssm": "_tiny-nemotron-h",
    "convolved_latent": "_tiny-zaya",
}
MIXERS = {LATENT: LatentAttention, LINEAR: GatedDeltaAttention, SSM: Mamba2Mixer,
          CCA: ConvLatentAttention, NO_MIXER: type(None)}


@functools.lru_cache(maxsize=None)
def _trunk(family):
    """`(the trunk unbound, the same trunk bound)` of a family."""
    spec = FAMILIES[family]
    if isinstance(spec, dict):
        trunk = Transformer(**spec)
        variables = trunk.init(jax.random.PRNGKey(0), jnp.zeros((1, SEQ, spec["dim"])))
        return trunk, trunk.bind(variables)
    with open(ROOT / "benchmark" / "configs" / f"{spec}.json") as f:
        model = CausalLM.from_config(json.load(f), SEQ)
    # an LM's trunk asks for no parameter while it is set up
    return model._trunk(), model.bind({}).transformer


def _kind_of(layer: dict) -> str:
    """The kind of a cache's layer, read off its leaves."""
    attn = layer[decode_cache.ATTN]
    if decode_cache.STATE in attn:
        return "recurrent"
    if decode_cache.LATENT in attn or decode_cache.ROWS in attn:
        return "latent"
    if decode_cache.TAIL in attn:
        return "cca"
    return "window" if "k_at" in attn else "heads"


@pytest.mark.parametrize("family", FAMILIES)
def test_the_cache_has_exactly_the_plans_kinds_and_index_rank(family):
    trunk, _ = _trunk(family)
    plan = trunk.plan()
    cache = trunk.init_cache(2, SEQ)
    assert len(plan) == trunk.depth
    if trunk.executor == "scan":
        assert decode_cache.layout_of(cache) == decode_cache.STACKED
        assert {layer.cache_kind for layer in plan} == {"heads"} and not plan[0].per_row
        assert cache[decode_cache.ATTN][decode_cache.K].shape[0] == trunk.depth
        return
    for i, layer in enumerate(plan):
        if layer.cache_kind == "none":  # a feed-forward alone holds nothing
            assert layer.kind == NO_MIXER and decode_cache.layer_key(i) not in cache
            continue
        held = cache[decode_cache.layer_key(i)]
        attn = held[decode_cache.ATTN]
        assert _kind_of(held) == layer.cache_kind
        assert jnp.ndim(attn[decode_cache.INDEX]) == int(layer.per_row)
        assert (decode_cache.INDEX_K in attn) == bool(layer.selects)
        # the token-shift rings lie beside the DALL-E block's lanes alone
        assert ("shift_attn" in held) == (layer.path == DALLE)


@pytest.mark.parametrize("family", FAMILIES)
def test_every_bound_mixer_is_on_the_plans_path(family):
    trunk, bound = _trunk(family)
    plan = trunk.plan()
    if trunk.executor == "scan":  # one scanned body: the DALL-E block's
        assert {layer.path for layer in plan} == {DALLE}
        return
    assert len(bound.attn_layers) == len(plan)
    for layer, mixer, ff in zip(plan, bound.attn_layers, bound.ff_layers):
        assert type(mixer) is MIXERS.get(layer.path, Attention)
        # a layer of ONE sublayer has nothing in the other's place
        assert (mixer is None) == (layer.kind == NO_MIXER) and (ff is None) == (
            layer.ff_kind == "none") and not (mixer is None and ff is None)
        assert mixer is None or mixer.name == f"attn_{layer.attn_id}"
        assert ff is None or ff.name == f"ff_{layer.ff_id}"
        if isinstance(mixer, Attention):
            assert mixer.path == layer.path
            assert (mixer.window is not None) == (layer.kind == "window")
        assert isinstance(ff, RoutedExperts) == (layer.ff_kind in ROUTED_KINDS)
        assert not isinstance(ff, RoutedExperts) or ff.act == ROUTED_KINDS[layer.ff_kind]
        # what a layer carries down the depth is its router's to say
        assert (layer.carries == "router_state") == bool(getattr(ff, "router_dim", 0))
        assert layer.takes_start == (layer.path not in (LINEAR, SSM, NO_MIXER))
        assert layer.rotary in (None, layer.kind) and (layer.rotary is None) == (
            layer.kind not in dict(trunk.rotary_specs or {}))
    assert routed_layers(plan) == sum(isinstance(ff, RoutedExperts) for ff in bound.ff_layers)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_plan_unbound_is_the_plan_bound_and_is_made_once(family):
    trunk, bound = _trunk(family)
    assert Transformer.plan(trunk) == bound.plan()
    assert Transformer.plan(trunk) is bound.plan()  # cached on the options, not made a call


@pytest.mark.parametrize("family,path,per_row", [
    ("linear_full", LANES, False), ("window_full_shared_kv", ROWS, True),
    ("window_full_mtp", ROWS, True)])
def test_the_two_full_layers_that_look_alike_are_on_different_paths(family, path, per_row):
    """The hybrid's full layers (a q/k norm over as many K/V heads as query
    heads, no rotary) project through the grouped matrix and decode over the
    DALL-E lanes at a scalar index; the window-and-full trunk's (K/V heads
    shared) decode per row."""
    full = [layer for layer in _trunk(family)[0].plan() if layer.kind == "full"]
    assert full and {layer.path for layer in full} == {path}
    assert {layer.cache_kind for layer in full} == {"heads"}
    assert {layer.per_row for layer in full} == {per_row}


def test_the_published_pattern_of_52_layers_is_one_sublayer_a_layer():
    """`hybrid_override_pattern` as published: 23 Mamba-2 mixers, 6 attention
    layers and 23 routed layers, each ONE sublayer; the attention layers' 2
    K/V heads under 32 query heads put them on ROWS, so the WHOLE plan is per
    row, the recurrent layers' index too (the smaller change: `per_row` stays
    one decision a stack, and `decode_cache.layer_spec(kind="recurrent")`
    takes it), and a routed layer has no cache entry."""
    with open(ROOT / "benchmark" / "configs" / "nemotron3-nano-30b-ep2.json") as f:
        cfg = json.load(f)
    pattern = cfg["published"]["hybrid_override_pattern"]
    assert len(pattern) == 52
    model = CausalLM.from_config(dict(cfg, num_hidden_layers=52, hybrid_override_pattern=pattern),
                                 64)
    plan = model.plan()
    kinds = {"M": ("ssm", SSM, "recurrent", "none"), "*": ("full", ROWS, "heads", "none"),
             "E": (NO_MIXER, NO_MIXER, "none", "relu2_experts")}
    assert [(p.kind, p.path, p.cache_kind, p.ff_kind) for p in plan] == [kinds[c] for c in pattern]
    assert [sum(p.kind == k for p in plan) for k in ("ssm", "full", NO_MIXER)] == [23, 6, 23]
    assert all(p.per_row and p.rotary is None and not p.selects for p in plan)
    assert routed_layers(plan) == 23
    cache = jax.eval_shape(lambda: model.init_cache(2, 64))
    assert len(cache) == 29 and all(
        (decode_cache.layer_key(i) in cache) == (c != "E") for i, c in enumerate(pattern))
    attn = cache["layer_0"][decode_cache.ATTN]
    assert attn[decode_cache.STATE].shape == (2, 128, 64 * 64)  # [rows, state, heads x width]
    assert attn[decode_cache.CONV].shape == (2, 3, 6144) and attn[decode_cache.INDEX].shape == (2,)
    assert cache["layer_5"][decode_cache.ATTN][decode_cache.K].shape == (2, 2, 64, 128)


def test_the_published_40_layers_carry_a_router_state_and_no_other_executor_takes_them():
    """`model_type: zaya` as published: 40 layers, each convolved latent
    attention (2 K/V heads of 128 and a tail a row in a per-row cache, a rotary
    over half of each head) and top-1 experts whose router hands its state to
    the next layer's; the scan and the reversible executors pass x (and the
    cache) from layer to layer and nothing else, and say so."""
    with open(ROOT / "benchmark" / "configs" / "zaya1-8b-pp2.json") as f:
        cfg = json.load(f)
    assert cfg["published"]["num_hidden_layers"] == 40
    full = dict(cfg, num_hidden_layers=40, layer_types=["hybrid"] * 40)
    model = CausalLM.from_config(full, 64)
    plan = model.plan()
    assert len(plan) == 40 and {
        (p.kind, p.path, p.cache_kind, p.ff_kind, p.carries, p.rotary, p.per_row, p.takes_start)
        for p in plan} == {("cca", CCA, "cca", "swiglu_experts", "router_state", "cca", True, True)}
    assert routed_layers(plan) == 40 and dict(model.trunk["rotary_specs"]["cca"])["dim"] == 64
    cache = jax.eval_shape(lambda: model.init_cache(2, 64))
    attn = cache["layer_39"][decode_cache.ATTN]
    assert attn[decode_cache.K].shape == (2, 2, 64, 128) and attn[decode_cache.K].dtype == jnp.bfloat16
    # c and c' of 8 + 2 heads of 128 and one shifted K/V head: 21 whole lane tiles
    assert attn[decode_cache.TAIL].shape == attn["tail_at"].shape == (2, 21 * 128)
    with pytest.raises(ValueError, match='executor="scan" does not support a router that carries'):
        CausalLM.from_config(cfg, 64, executor="scan").bind({}).transformer.rotary_table
    with pytest.raises(ValueError, match="carries its state.*plain unrolled executor"):
        CausalLM.from_config(cfg, 64, reversible=True).bind({}).transformer.rotary_table
    # a plan whose router is one matrix carries nothing
    assert {p.carries for p in _trunk("window_full_mtp")[0].plan()} == {None}


def test_a_cache_of_the_wrong_index_rank_is_refused_with_a_sentence():
    attn = Attention(dim=32, seq_len=8, heads=4, dim_head=8, kv_heads=2, use_bias=False)
    variables = attn.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 32)))
    lockstep = decode_cache.make(decode_cache.PER_LAYER, 1, batch=1, max_len=8, heads=2,
                                 dim_head=8, dim=32)["layer_0"]["attn"]
    with pytest.raises(ValueError, match="index is a scalar.*grouped_rows path.*per row"):
        attn.apply(variables, jnp.zeros((1, 1, 32)), cache=lockstep)


def test_a_trunks_lockstep_cache_is_refused_by_its_rows_layers():
    trunk, _ = _trunk("window_full_shared_kv")
    kinds = [layer.cache_kind for layer in trunk.plan()]
    variables = jax.eval_shape(trunk.init, jax.random.PRNGKey(0), jnp.zeros((1, SEQ, trunk.dim)))
    lockstep = decode_cache.make(  # what `init_cache` would not build: the full layers' kind alone
        decode_cache.PER_LAYER, trunk.depth, kinds=["heads"] * len(kinds), batch=1, max_len=SEQ,
        heads=trunk.kv_heads, dim_head=trunk.dim_head, dim=trunk.dim)
    with pytest.raises(ValueError, match="handed to a layer on the grouped_rows path"):
        jax.eval_shape(lambda v, x: trunk.apply(v, x, cache=lockstep), variables,
                       jnp.zeros((1, 1, trunk.dim)))


@pytest.mark.parametrize("options,path", [
    (dict(), DALLE), (dict(qk_norm="whole"), LANES), (dict(kv_heads=4, qk_norm=True), LANES),
    (dict(kv_heads=2), ROWS), (dict(window=4), ROWS), (dict(qk_norm=True, path=ROWS), ROWS)])
def test_a_standalone_attention_is_on_the_path_its_options_describe(options, path):
    """The defaults describe the DALL-E block; a module is told its path
    (`Transformer` fills it from the plan) or takes `attention_path` of its
    own options, and what it is CALLED with moves it nowhere."""
    attn = Attention(dim=32, seq_len=8, heads=4, dim_head=8, **options)
    assert (attn.path or attention.attention_path(4, attn.kv_heads, attn.qk_norm, attn.window)
            ) == path
    x = jnp.zeros((1, 8, 32))
    tables = (jnp.ones((8, 8)), jnp.zeros((8, 8)))
    if path == ROWS:
        assert attn.init_with_output(jax.random.PRNGKey(0), x, rotary_cs=tables)[0][0].shape == x.shape
    else:
        with pytest.raises(ValueError, match=f"rotary_cs.*on the {path} path"):
            attn.init(jax.random.PRNGKey(0), x, rotary_cs=tables)


@pytest.mark.parametrize("mixer", [
    lambda **kw: Attention(dim=32, seq_len=8, **kw),
    lambda **kw: LatentAttention(dim=32, seq_len=8, heads=2, q_lora_rank=8, kv_lora_rank=8,
                                 qk_nope_dim=8, qk_rope_dim=8, v_dim=8, **kw)],
    ids=["attention", "latent"])
def test_an_unknown_attn_impl_is_refused_by_name_at_construction(mixer):
    mixer(attn_impl="flash")
    with pytest.raises(ValueError, match="unknown attn_impl 'triton'"):
        mixer(attn_impl="triton")


@pytest.mark.parametrize("options,words", [
    (dict(attn_types=("latent", "full"), rotary_specs={"latent": {}}),
     "latent layers beside K/V or recurrent ones in one cache are not built"),
    (dict(attn_types=("axial_row",), image_fmap_size=4, kv_heads=1),
     "a patterned layer over shared K/V heads"),
])
def test_init_cache_refuses_from_the_plan(options, words):
    trunk = Transformer(dim=32, depth=2, seq_len=SEQ, heads=2, dim_head=16, rotary_emb=False,
                        **options)
    with pytest.raises(NotImplementedError, match=words):
        Transformer.init_cache(trunk, 1, SEQ)


def test_layers_that_share_an_id_share_a_kind():
    trunk = Transformer(dim=32, depth=2, seq_len=SEQ, heads=2, dim_head=16, image_fmap_size=4,
                        attn_types=("full", "axial_row"), shared_attn_ids=(0, 0))
    with pytest.raises(ValueError, match="attn_types do not match shared_attn_ids"):
        trunk.plan()
