"""Device time by the program's own names (`obs/scopes.py`): the text parser,
the component rules against the programs' own compiled text, the kernels'
and programs' names, what remembering costs, and the `dalle:` host spans.
"""

import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.models import dalle as D
from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.obs.tracing import HOST_SPAN_PREFIX, host_span

REPO = Path(__file__).resolve().parents[1]
TEXT_SEQ, FMAP = 8, 4
IMG_SEQ = FMAP * FMAP
TRAIN_IMG_VOCAB = 40  # the train step's image ids: no other width of its model

# ------------------------------------------------------------------ parse

SNIPPET = '''HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(step)/inside/add"}
}

%region_0.5 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.2 = f32[] add(%a.1, %b.1)
}

%body.3 (p.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%p.1), index=1
  %fusion.7 = f32[4]{0:T(128)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(DALLE)/transformer/attn_0/to_qkv/dot_general" stack_frame_id=3}
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%gte.1, %fusion.7)
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="state.params"}
  %fwd_flash.3 = (bf16[2,4,128,64]{3,2,1,0:T(8,128)(2,1)}, /*index=1*/f32[2,4,128,1]{3,2,1,0:T(8,128)}) custom-call(%Arg_0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(DALLE)/transformer/attn_0/attn_0._flash/fwd_flash/pallas_call"}
  %copy.4 = bf16[64,2,16,1281,64]{2,1,4,3,0:T(2,128)(2,1)} copy(%Arg_0.1)
  %while.1 = (s32[], f32[4]{0}) while(%Arg_0.1), condition=%cond.2, body=%body.3, metadata={op_name="jit(step)/while"}
  ROOT %reduce.1 = f32[] reduce(%copy.4, %Arg_0.1), dimensions={0}, to_apply=%region_0.5, metadata={op_name="jit(step)/transpose(jvp(DALLE))/loss/reduce_sum"}
}
'''


def test_parse_reads_names_shapes_and_paths_and_skips_fused_computations():
    got = scopes.parse(SNIPPET)
    # the inside of a fusion and a reducer are not operations a trace shows
    assert "add.9" not in got and "add.2" not in got
    assert got["fusion.7"] == (
        "fusion", "f32[4]",
        "jit(step)/jvp(DALLE)/transformer/attn_0/to_qkv/dot_general",
    )
    assert got["fwd_flash.3"][:2] == (
        "custom-call", "(bf16[2,4,128,64],f32[2,4,128,1])")
    assert got["copy.4"] == ("copy", "bf16[64,2,16,1281,64]", None)
    assert got["while.1"][0] == "while" and got["reduce.1"][0] == "reduce"


def test_a_trace_names_an_operation_by_its_whole_line():
    line = ("%copy.399 = bf16[64,2,16,1281,64]{2,1,4,3,0:T(2,128)(2,1)} "
            "copy(bf16[64,2,16,1281,64]{2,1,4,3,0:T(2,128)(2,1)} %get-tuple-element.3146)")
    assert scopes.instruction(line) == ("copy.399", "copy", "bf16[64,2,16,1281,64]")
    assert scopes.instruction("ThunkExecutor::Execute") is None
    assert scopes.instruction("%while.52 = (s32[]{:T(128)}, bf16[2,1,1024]{2,0,") is None


@pytest.mark.parametrize("op_name,opcode,name,want", [
    ("jit(step)/jvp(DALLE)/transformer/attn_0/attn_0._flash/fwd_flash/pallas_call",
     "custom-call", "fwd_flash.3", ("attn_kernel", "fwd")),
    (None, "custom-call", "%dkv_flash.7", ("attn_kernel", "fwd")),
    ("jit(step)/transpose(jvp(DALLE))/transformer/jvp(DALLE)/transformer/checkpoint/"
     "rematted_computation/transformer.layer_fn/attn_3/attn_3._flash/pad",
     "fusion", "pad_maximum_fusion.81", ("attn_glue", "remat")),
    ("jit(step)/transpose(jvp(DALLE))/transformer/ff_2/Dense_0/dot_general",
     "fusion", "fusion.9", ("ff", "bwd")),
    ("jit(step)/optimizer/mul", "fusion", "fusion.1", ("optimizer", "fwd")),
    ("jit(step)/jvp(DALLE)/loss/logits_chunk/dot_general", "fusion", "fusion.2",
     ("head", "fwd")),
    ("jit(step)/jvp(DALLE)/DALLE._split_loss/loss/reduce_sum", "fusion", "f.3",
     ("loss", "fwd")),
    ("jit(step)/transpose(jvp(DALLE))/DALLE._split_loss/logits_image/DALLE._logits_block/"
     "dot_general", "fusion", "f.5", ("head", "bwd")),
    ("jit(sample_cached)/while/body/closed_call/sample/jit(_gumbel)/add", "fusion",
     "f.4", ("sample", "fwd")),
    ("jit(f)/transformer/scan_stack/cached_scan/while/body/closed_call/layers/cache_read/"
     "dynamic_slice", "dynamic-slice", "dynamic_slice.521", ("cache_read", "fwd")),
    ("jit(f)/transformer/scan_stack/cached_scan/while/body/closed_call/layers/attn/"
     "cache_write/dynamic_update_slice", "fusion", "fusion.360", ("cache_write", "fwd")),
    ("jit(f)/transformer/scan_stack/cached_scan/while/body/closed_call/layers/"
     "cache_write/dynamic_update_slice", "dynamic-update-slice",
     "dynamic_update_slice.103", ("cache_write", "fwd")),
    # the cached loop's own slicing (parameters, the layer index) is nobody's
    ("jit(f)/transformer/scan_stack/cached_scan/while/body/dynamic_slice", "fusion",
     "constant_dynamic-slice_fusion.36", ("unscoped", "fwd")),
    ("jit(f)/transformer/scan_stack/while/body/dynamic_slice", "fusion", "f.6",
     ("unscoped", "fwd")),
    ("jit(f)/transformer/scan_stack/cached_scan/while", "while", "while.52",
     ("unscoped", "fwd")),
    ("jit(f)/DiscreteVAE.decode/dec_head/conv", "convolution", "c.1", ("pixels", "fwd")),
    (None, "copy", "copy.399", ("unscoped", "fwd")),
    ("jit(f)/something_else/mul", "fusion", "f.7", ("unscoped", "fwd")),
    # a verify step's attention by kind of layer, and the multi-token module as a phase
    ("jit(lm_sample)/while/body/closed_call/transformer/attn_3/global_attend/dot_general",
     "fusion", "f.8", ("global_attend", "fwd")),
    ("jit(lm_sample)/while/body/closed_call/transformer/attn_0/window_attend/dot_general",
     "fusion", "f.9", ("window_attend", "fwd")),
    ("jit(lm_sample)/while/body/closed_call/mtp/mtp_block/attn_0/global_attend/dot_general",
     "fusion", "f.10", ("global_attend", "mtp")),
    ("jit(lm_sample)/while/body/closed_call/mtp/mtp_proj/dot_general", "fusion", "f.11",
     ("ff", "mtp")),
    ("jit(lm_sample)/while/body/mtp/verify/select_n", "fusion", "f.12", ("sample", "mtp")),
    (None, "custom-call", "%gmm_fwd.3", ("moe_experts", "fwd")),
    # a full layer's kernel: by its instruction name where the metadata is gone,
    # under the scope it is called from, and in the module's phase by its path
    (None, "custom-call", "%decode_grouped.7", ("global_attend", "fwd")),
    ("jit(lm_sample)/while/body/closed_call/transformer/attn_3/global_attend/decode_grouped",
     "custom-call", "decode_grouped.30", ("global_attend", "fwd")),
    ("jit(lm_sample)/while/body/closed_call/mtp/mtp_block/attn_0/global_attend/decode_grouped",
     "custom-call", "decode_grouped.31", ("global_attend", "mtp")),
    ("jit(lm_sample)/mtp/mtp_block/attn_0/decode_grouped", "fusion", "f.13",
     ("global_attend", "mtp")),
])
def test_component_rules(op_name, opcode, name, want):
    assert scopes.component(op_name, opcode, name) == want


def test_a_state_space_mixers_parts_are_placed_before_the_attention_rules():
    """A Mamba-2 mixer lives in an `attn_{i}` module and has a `to_out`: its
    rules stand before `attn_proj` and the module's glue, the kernel is known
    by its instruction name, and the chunked prefill form is its own."""
    path = "jit(lm_sample)/while/body/closed_call/transformer/attn_2/"
    for op_name, opcode, instruction, want in (
            (path + "ssm_proj/to_out/dot_general", "fusion", "f.1", ("ssm_proj", "fwd")),
            (path + "ssm_proj/dot_general", "fusion", "f.2", ("ssm_proj", "fwd")),
            (path + "ssm_step/ssm_step", "custom-call", "ssm_step.7", ("ssm_step", "fwd")),
            (path + "ssm_step/transpose", "fusion", "f.3", ("ssm_step", "fwd")),
            (None, "custom-call", "%ssm_step.12", ("ssm_step", "fwd")),
            ("jit(lm_prefill)/CausalLM.prefill/transformer/attn_0/ssm_chunk/while/body/mul",
             "fusion", "f.4", ("ssm_chunk", "fwd")),
            (path.replace("attn_2", "attn_5") + "to_out/dot_general", "fusion", "f.5",
             ("attn_proj", "fwd")),
            (path.replace("attn_2", "ff_1") + "moe_experts/gmm_fwd", "custom-call", "gmm_fwd.3",
             ("moe_experts", "fwd"))):
        assert scopes.component(op_name, opcode, instruction) == want, (op_name, instruction)
    assert {"ssm_step", "ssm_proj", "ssm_chunk"} <= set(scopes.COMPONENTS)
    order = [name for name, _ in scopes.RULES]
    assert max(order.index(n) for n in ("ssm_step", "ssm_chunk", "ssm_proj")) < min(
        order.index("attend"), order.index("attn_proj"))
    assert scopes.SSM_KERNEL == "ssm_step" and scopes.SSM_KERNEL not in scopes.KERNELS


def test_a_convolved_layers_parts_and_the_mlp_router_are_placed():
    """The convolutions, the q-k mean and the norms of a convolved layer are
    `cca_mix`, ahead of the glue its `attn_{i}` module would make them; its
    projections are `attn_proj`, its cached attend `global_attend` (the kernel
    by its name), its rotary glue, the tail's restore `state_restore`; a router
    that is an MLP is `router_mlp`, ahead of `ff`, and the routing after it
    `moe_dispatch` as ever."""
    path = "jit(lm_sample)/while/body/closed_call/transformer/attn_2/"
    for op_name, opcode, instruction, want in (
            (path + "cca_mix/mul", "fusion", "f.1", ("cca_mix", "fwd")),
            (path + "cca_mix/bngi,gio->bngo/dot_general", "fusion", "f.2", ("cca_mix", "fwd")),
            (path + "to_qkv/dot_general", "fusion", "f.3", ("attn_proj", "fwd")),
            (path + "to_out/dot_general", "fusion", "f.4", ("attn_proj", "fwd")),
            (path + "rotary/mul", "fusion", "f.5", ("attn_glue", "fwd")),
            (path + "global_attend/decode_grouped", "custom-call", "decode_grouped.3",
             ("global_attend", "fwd")),
            (path + "cache_write/dynamic_update_slice", "fusion", "f.6", ("cache_write", "fwd")),
            ("jit(lm_sample)/state_restore/select_n", "fusion", "f.7", ("state_restore", "fwd")),
            (path.replace("attn_2", "ff_2") + "router_mlp/dot_general", "fusion", "f.8",
             ("router_mlp", "fwd")),
            (path.replace("attn_2", "ff_2") + "moe_dispatch/sort", "sort", "sort.1",
             ("moe_dispatch", "fwd"))):
        assert scopes.component(op_name, opcode, instruction) == want, (op_name, instruction)
    assert {"cca_mix", "router_mlp"} <= set(scopes.COMPONENTS)
    order = [name for name, _ in scopes.RULES]
    assert order.index("cca_mix") < min(order.index("attend"), order.index("attn_proj"))
    assert order.index("router_mlp") < order.index("ff")


def test_the_grouped_kernels_rule_stands_before_attend_and_after_the_kernels():
    """`global_attend` (with `decode_grouped` by name) is asked before the
    plain `attend`, which its name contains as a word of a path would, and
    after `attn_kernel`, whose seven names it is not among: a flash kernel
    under a `global_attend` scope would still be the kernel's."""
    order = [name for name, _ in scopes.RULES]
    assert order.index("attn_kernel") < order.index("window_attend") < order.index(
        "global_attend") < order.index("attend") < order.index("attn_proj")
    assert scopes.GROUPED_KERNEL == "decode_grouped" and scopes.GROUPED_KERNEL not in scopes.KERNELS
    assert dict(scopes.RULES)["global_attend"].search("a/decode_grouped/b")
    assert scopes.component("jit(f)/attn_3/global_attend/decode_slots/x", "custom-call",
                            "decode_slots.2") == ("attn_kernel", "fwd")
    assert scopes.component("jit(f)/attn_3/attend/dot_general", "fusion", "f.1") == (
        "attend", "fwd")


# ------------------------------------------------- the programs' own text


def _tiny(executor="unrolled", **kw):
    cfg = dict(
        dim=32, depth=2, heads=2, dim_head=8, num_image_tokens=32,
        image_fmap_size=FMAP, num_text_tokens=64, text_seq_len=TEXT_SEQ,
        shift_tokens=True, rotary_emb=True, executor=executor,
    )
    cfg.update(kw)
    model = DALLE(**cfg)
    text = jnp.zeros((2, TEXT_SEQ), jnp.int32)
    toks = jnp.zeros((2, IMG_SEQ), jnp.int32)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0), text, toks), text, toks


def _op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def program_texts():
    """Compiled text of a tiny train step (unrolled, flash kernels
    interpreted, remat), a tiny cached sampler on the scan executor with the
    dVAE's pixels fused in, and cached attention over an int8 cache."""
    from dalle_pytorch_tpu.models.attention import Attention
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.training import TrainState, make_dalle_train_step, make_optimizer

    model, variables, text, toks = _tiny(
        attn_impl="flash", reversible=True, reversible_impl="remat",
        num_image_tokens=TRAIN_IMG_VOCAB)
    state = TrainState.create(
        apply_fn=model.apply, params=variables["params"],
        tx=make_optimizer(3e-4, clip_grad_norm=0.5))
    step = jax.jit(make_dalle_train_step(model), donate_argnums=0)
    batch = {"text": text, "image_tokens": toks}
    train = step.lower(state, batch, jax.random.PRNGKey(1)).compile().as_text()

    smodel, svars, stext, _ = _tiny(
        executor="scan", attn_types=("full", "axial_row"))
    vae = DiscreteVAE(image_size=16, num_layers=2, num_tokens=32,
                      codebook_dim=16, hidden_dim=8)
    vparams = jax.jit(vae.init)(
        jax.random.PRNGKey(2), jnp.zeros((1, 16, 16, 3)))["params"]
    sampler = D._jitted_sampler(
        D._cached_sampler_builder, smodel, (0.9, 1.0, 1.0, None, vae))
    sample = sampler.lower(
        svars, jax.random.PRNGKey(3), stext, None, vparams).compile().as_text()

    attn = Attention(dim=32, seq_len=24, heads=2, dim_head=8, attn_impl="dense")
    x = jnp.zeros((2, 1, 32))
    cache = {"k": jnp.zeros((2, 2, 25, 8), jnp.int8), "v": jnp.zeros((2, 2, 25, 8), jnp.int8),
             "k_scale": jnp.zeros((2, 2, 25)), "v_scale": jnp.zeros((2, 2, 25)),
             "index": jnp.zeros((), jnp.int32)}
    avars = attn.init(jax.random.PRNGKey(4), x)
    quant = jax.jit(lambda v, x, c: attn.apply(v, x, cache=c)).lower(
        avars, x, cache).compile().as_text()
    return {"train": train, "sample": sample, "quant": quant, "lm": _lm_step_text(),
            "lm_sample": _lm_sample_text(), "verify_sample": _verify_sample_text(),
            "dsa_sample": _lm_sample_text(index_heads=2, index_dim=16, index_topk=4,
                                          moe_groups=(2, 1)),
            **_hybrid_texts(), **_ssm_texts(), **_cca_texts()}


def _lm_step_text() -> str:
    """Compiled text of a tiny language-model train step: routed layers, a
    window and a full layer, remat."""
    from dalle_pytorch_tpu.models.lm import CausalLM
    from dalle_pytorch_tpu.training import TrainState, make_lm_train_step, make_optimizer

    rope = {"type": "default", "dim": 8, "theta": 1e4}
    lm = CausalLM(
        num_tokens=40, dim=32, depth=2, seq_len=16, heads=2, dim_head=8, reversible=True,
        trunk=dict(norm="rms", ff_kind="swiglu_experts", use_bias=False, layerscale=False,
                   kv_heads=1, qk_norm=True, window=4, attn_types=("window", "full"),
                   rotary_specs={"window": rope, "full": rope}, experts_total=4,
                   experts_per_token=2, experts_held=(0, 2), expert_dim=16,
                   moe_buffer_rows=64, attn_impl="flash"))
    tokens = jnp.zeros((2, 16), jnp.int32)
    state = TrainState.create(
        apply_fn=lm.apply, params=jax.jit(lm.init)(jax.random.PRNGKey(0), tokens)["params"],
        tx=make_optimizer(3e-4, clip_grad_norm=0.5))
    step = jax.jit(make_lm_train_step(lm), donate_argnums=0)
    return step.lower(state, {"tokens": tokens}, jax.random.PRNGKey(1)).compile().as_text()


def _hybrid_texts() -> dict:
    """Compiled texts of a tiny linear-and-full language model's sampler and
    prefill: a gated delta-rule layer and a full one over a cache of both
    kinds, a turn restored from the snapshot."""
    from dalle_pytorch_tpu.models import lm

    mdl = lm.CausalLM(
        num_tokens=40, dim=32, depth=2, seq_len=16, heads=2, dim_head=16,
        trunk=dict(norm="rms", use_bias=False, layerscale=False, sandwich_norm=True,
                   prenorm=False, qk_norm="whole", attn_types=("linear", "full"),
                   ff_kind="swiglu", ff_dim=48, linear_heads=2, linear_key_dim=8,
                   linear_value_dim=16, attn_impl="dense"))
    variables = jax.jit(mdl.init)(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    sampler = jax.jit(lm._sampler_builder(mdl, (4, 0.9, 1.0, 1)), donate_argnums=(2,))
    prefill = jax.jit(lm._prefill_builder(mdl, ()), donate_argnums=(2,))
    zero = jnp.asarray(0, jnp.int32)
    return {
        "hybrid_sample": sampler.lower(
            variables, jax.random.PRNGKey(1), mdl.init_cache(2), jnp.zeros((2, 2), jnp.int32),
            zero + 8).compile().as_text(),
        "hybrid_prefill": prefill.lower(
            variables, jnp.zeros((2, 8), jnp.int32), mdl.init_cache(2), zero).compile().as_text(),
    }


def _ssm_texts() -> dict:
    """Compiled texts of a tiny state-space hybrid's sampler and prefill:
    layers of ONE sublayer (a Mamba-2 mixer, ungated routed experts, an
    attention over shared K/V heads) over a per-row cache."""
    from dalle_pytorch_tpu.models import lm

    mdl = lm.CausalLM(
        num_tokens=40, dim=32, depth=3, seq_len=24, heads=4, dim_head=8,
        trunk=dict(norm="rms", use_bias=False, layerscale=False, kv_heads=2,
                   attn_types=("ssm", "none", "full"),
                   ff_kinds=("none", "relu2_experts", "none"), ssm_heads=4, ssm_head_dim=8,
                   ssm_groups=2, ssm_state=8, ssm_chunk=4, experts_total=4, experts_per_token=2,
                   experts_held=(0, 2), expert_dim=16, moe_buffer_rows=64, moe_score="sigmoid",
                   moe_score_bias=True, shared_dim=16))
    variables = jax.jit(mdl.init)(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    sampler = jax.jit(lm._verify_sampler_builder(mdl, (4, 0.9, 1.0, 1, None)),
                      donate_argnums=(2,))
    prefill = jax.jit(lm._prefill_builder(mdl, ()), donate_argnums=(2,))
    return {
        "ssm_sample": sampler.lower(
            variables, jax.random.PRNGKey(1), mdl.init_cache(2), jnp.zeros((2, 2), jnp.int32),
            jnp.full((2,), 8, jnp.int32)).compile().as_text(),
        "ssm_prefill": prefill.lower(
            variables, jnp.zeros((2, 8), jnp.int32), mdl.init_cache(2),
            jnp.asarray(0, jnp.int32)).compile().as_text(),
    }


def _cca_texts() -> dict:
    """Compiled texts of a tiny convolved-latent sampler and prefill: attention
    behind two causal convolutions over a per-row cache with a tail, an MLP
    router that carries its state, a scaled residual, a tied head."""
    from dalle_pytorch_tpu.models import lm

    mdl = lm.CausalLM(
        num_tokens=40, dim=32, depth=2, seq_len=24, heads=4, dim_head=8, tied_head=True,
        trunk=dict(norm="rms", use_bias=False, layerscale=False, kv_heads=2, attn_types=("cca",),
                   rotary_specs={"cca": {"type": "default", "dim": 4, "theta": 1e4}},
                   ff_kind="swiglu_experts", experts_total=4, experts_per_token=1,
                   experts_held=(0, 4), expert_dim=16, moe_buffer_rows=64, moe_score_bias=True,
                   moe_renormalise=False, router_dim=8, residual="affine"))
    variables = jax.jit(mdl.init)(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    sampler = jax.jit(lm._verify_sampler_builder(mdl, (4, 0.9, 1.0, 1, None)),
                      donate_argnums=(2,))
    prefill = jax.jit(lm._prefill_builder(mdl, ()), donate_argnums=(2,))
    return {
        "cca_sample": sampler.lower(
            variables, jax.random.PRNGKey(1), mdl.init_cache(2), jnp.zeros((2, 2), jnp.int32),
            jnp.full((2,), 8, jnp.int32)).compile().as_text(),
        "cca_prefill": prefill.lower(
            variables, jnp.zeros((2, 8), jnp.int32), mdl.init_cache(2),
            jnp.asarray(0, jnp.int32)).compile().as_text(),
    }


def _verify_sample_text() -> str:
    """Compiled text of a tiny window-and-full sampler whose multi-token
    module drafts: a ring and a full K/V layer over shared K/V heads, verify
    steps of two positions."""
    from dalle_pytorch_tpu.models import lm

    mdl = lm.CausalLM(
        num_tokens=40, dim=32, depth=2, seq_len=24, heads=4, dim_head=8, draft_layers=1,
        trunk=dict(norm="rms", use_bias=False, layerscale=False, kv_heads=2, qk_norm=True,
                   window=4, attn_types=("window", "full"), draft_positions=1,
                   rotary_specs={"window": {"type": "default", "dim": 8, "theta": 1e4}},
                   ff_kinds=("swiglu", "swiglu_experts"), ff_dim=48, experts_total=4,
                   experts_per_token=2, experts_held=(0, 2), expert_dim=16, moe_buffer_rows=64,
                   moe_score="sigmoid", moe_score_bias=True, shared_dim=16))
    variables = jax.jit(mdl.init)(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    sampler = jax.jit(lm._verify_sampler_builder(mdl, (4, 0.9, 1.0, 1, None)),
                      donate_argnums=(2,))
    return sampler.lower(
        variables, jax.random.PRNGKey(1), mdl.init_cache(2), jnp.zeros((2, 2), jnp.int32),
        jnp.full((2,), 8, jnp.int32)).compile().as_text()


def _lm_sample_text(**indexed) -> str:
    """Compiled text of a tiny language-model sampler: latent attention over
    its cache, a dense layer and a routed one with a shared expert; with
    `indexed`, a lightning indexer beside the attention that selects 4 of the
    16 cached positions."""
    from dalle_pytorch_tpu.models import lm

    rope = {"type": "default", "dim": 8, "theta": 1e4}
    mdl = lm.CausalLM(
        num_tokens=40, dim=32, depth=2, seq_len=16, heads=2, dim_head=16,
        trunk=dict(norm="rms", use_bias=False, layerscale=False, sandwich_norm=True,
                   attn_types=("latent",), rotary_specs={"latent": rope}, q_lora_rank=12,
                   kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8, v_dim=8,
                   ff_kinds=("swiglu", "swiglu_experts"), ff_dim=48, experts_total=4,
                   experts_per_token=2, experts_held=(0, 2), expert_dim=16, moe_buffer_rows=64,
                   moe_score="sigmoid", routed_scale=2.5, shared_dim=16, **indexed))
    variables = jax.jit(mdl.init)(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    sampler = jax.jit(lm._sampler_builder(mdl, (4, 0.9, 1.0, 1)), donate_argnums=(2,))
    return sampler.lower(
        variables, jax.random.PRNGKey(1), mdl.init_cache(2), jnp.zeros((2, 2), jnp.int32),
        jnp.asarray(8, jnp.int32)).compile().as_text()


def test_every_rule_is_hit_by_the_programs_own_text(program_texts):
    """A rule that no program's paths reach is a rule for a name that has
    moved: names and rules move in one commit."""
    names = set().union(*(_op_names(t) for t in program_texts.values()))
    for i, (comp, pat) in enumerate(scopes.RULES):
        assert any(pat.search(n) for n in names), (i, comp, pat.pattern)
    assert any(scopes.REMAT_MARK in n for n in names)
    assert any(scopes.BWD_MARK in n for n in names)


@pytest.mark.parametrize("which,floor", [("train", 0.55), ("sample", 0.45)])
def test_most_instructions_get_a_component_and_the_rest_is_said(program_texts, which, floor):
    """The table joins with itself whole, and `unscoped` is reported, not
    hidden: on the CPU backend most fusions' and every copy's metadata is
    gone (on the chip it is 0.8% and 3.2% of the TIME, PERF.md)."""
    table = scopes.classify(scopes.parse(program_texts[which]))
    work = {n: row for n, row in table.items()
            if row[0] not in ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")
            and row[0] not in scopes.CONTAINERS}
    ops = {f"%{n} = {row[1]} {row[0]}(...)": {"seconds": 1.0} for n, row in work.items()}
    joined = scopes.join(ops, table)
    unscoped = scopes.share(joined, ["unscoped"])
    print(f"{which}: instructions {len(work)}, unscoped {unscoped:.1f}%, "
          f"unjoined {100 * (1 - joined['placed_share']):.1f}%")
    assert joined["placed_share"] == 1.0
    assert sum(scopes.share(joined, [c]) for c in scopes.COMPONENTS) == pytest.approx(100.0)
    assert unscoped < 100 * (1 - floor)
    # a table that is another program's places (next to) nothing
    other = scopes.classify(scopes.parse(
        program_texts["sample" if which == "train" else "train"]))
    assert scopes.join(ops, other)["placed_share"] < 0.5


def test_train_step_text_tells_forward_backward_and_recompute_apart(program_texts):
    table = scopes.classify(scopes.parse(program_texts["train"]))
    seen = {(row[2], row[3]) for row in table.values()}
    for comp in ("attn_proj", "ff"):
        assert {(comp, "fwd"), (comp, "bwd"), (comp, "remat")} <= seen, comp
    # the model's default keeps the flash kernels' residuals across remat:
    # no kernel runs in the recompute (`to_out` and `ff`'s GEGLU do)
    assert {("attn_kernel", "fwd"), ("attn_kernel", "bwd")} <= seen
    assert ("attn_kernel", "remat") not in seen
    assert ("optimizer", "fwd") in seen and ("loss", "bwd") in seen


def test_the_train_step_computes_the_two_live_blocks_of_the_logits_and_no_more(program_texts):
    """The loss's head (`DALLE._split_loss`): a text row's logits over the
    text ids, an image row's over the image ids. No instruction of the step,
    fused or not, has the full [B, N, V] logits' shape; both products are
    `head`'s, both cross-entropies `loss`'s, and nothing the loss traces is
    left `unscoped`."""
    text_vocab, batch = 64 + TEXT_SEQ, 2
    full = f"[{batch},{TEXT_SEQ + IMG_SEQ},{text_vocab + TRAIN_IMG_VOCAB}]"
    blocks = {"text": f"[{batch},{TEXT_SEQ},{text_vocab}]",
              "image": f"[{batch},{IMG_SEQ},{TRAIN_IMG_VOCAB}]"}
    train = program_texts["train"]
    assert full not in train
    parsed = scopes.parse(train)
    table = scopes.classify(parsed)
    path = {name: op_name or "" for name, (_, _, op_name) in parsed.items()}
    assert not any("logits_mask" in p for p in path.values())
    for block, dims in blocks.items():
        scope = f"/logits_{block}/"
        shaped = {name for name, row in table.items() if row[1].endswith(dims)}
        assert {table[n][2] for n in shaped} == {"head", "loss"}
        # the product's bias add has the block's shape (the CPU's `dot` is
        # two-dimensional), and so have the softmax's exponential and the
        # cotangent the loss hands back
        assert any(scope in path[n] and table[n][3] == "fwd" for n in shaped)
        assert any(path[n].endswith("/loss/exp") for n in shaped)
        assert any(table[n][2:] == ["loss", "bwd"] for n in shaped)
        scoped = [n for n in table if scope in path[n]]
        assert all(table[n][2] == "head" for n in scoped)
        assert {table[n][3] for n in scoped if table[n][0] == "dot"} == {"fwd", "bwd"}
    traced = [n for n in table if "DALLE._split_loss" in path[n]]
    assert traced and all(table[n][2] in ("head", "loss") for n in traced)


# ------------------------------------------------------------------ kernels


def _pallas_names(fn, *args) -> list:
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _kernel_cases():
    from dalle_pytorch_tpu.ops import pallas_attention as pa, pallas_decode as pd

    q = jnp.zeros((1, 2, 128, 16), jnp.bfloat16)
    kv = jnp.zeros((1, 2, 256, 16), jnp.bfloat16)
    one = jnp.zeros((1, 2, 1, 16), jnp.bfloat16)
    lens = jnp.full((1,), 200, jnp.int32)
    pages = jnp.zeros((9, 2, 32, 16), jnp.bfloat16)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    bitmap = jnp.ones((1, 2), jnp.int32)
    flash = lambda q, k, v: pa.flash_attention(q, k, v).astype(jnp.float32).sum()
    return {
        "fwd_flash": (lambda: _pallas_names(flash, q, q, q)),
        "dq_flash": (lambda: _pallas_names(jax.grad(flash, (0, 1, 2)), q, q, q)),
        "dkv_flash": (lambda: _pallas_names(jax.grad(flash, (0, 1, 2)), q, q, q)),
        "decode_slots": (lambda: _pallas_names(pd.flash_decode_attention, one, kv, kv, lens)),
        "decode_sparse": (lambda: _pallas_names(
            lambda *a: pd.block_sparse_flash_decode_attention(*a, block_k=128),
            one, kv, kv, lens, bitmap)),
        "decode_paged": (lambda: _pallas_names(
            pd.paged_flash_decode_attention, one, pages, pages, lens, table)),
        "decode_sparse_paged": (lambda: _pallas_names(
            pd.block_sparse_paged_flash_decode_attention,
            one, pages, pages, lens, table, jnp.ones((1, 8), jnp.int32))),
    }


@pytest.mark.parametrize("kernel", scopes.KERNELS)
def test_every_pallas_call_has_its_stable_name(kernel):
    names = _kernel_cases()[kernel]()
    assert names and all(names), names  # none is anonymous
    assert kernel in names
    assert set(names) <= set(scopes.KERNELS)


def test_no_pallas_call_in_ops_is_left_unnamed():
    src = "".join((REPO / "dalle_pytorch_tpu" / "ops" / f).read_text()
                  for f in ("pallas_attention.py", "pallas_decode.py"))
    calls = re.findall(r"pl\.pallas_call\(\n(?:.*\n){0,8}?\s+name=\"(\w+)\"", src)
    assert sorted(calls) == sorted(scopes.KERNELS)
    assert src.count("pl.pallas_call(") == len(scopes.KERNELS)


# ----------------------------------------------------------------- programs

BUILDERS = {
    "sample_cached": D._cached_sampler_builder,
    "sample_cached_batched": D._batched_sampler_builder,
    "sample_full": D._full_sampler_builder,
    "sample_text": D._text_sampler_builder,
    "slots_prefill": D._prefill_slots_builder,
    "slots_resume": D._resume_slots_builder,
    "slots_release": D._release_builder,
    "slots_chunk": D._chunk_builder,
    "slots_prefill_paged": D._prefill_slots_paged_builder,
    "slots_resume_paged": D._resume_slots_paged_builder,
    "slots_chunk_paged": D._chunk_paged_builder,
    "prefix_admit": D._admit_prefix_builder,
    "sidecar_slice": D._slice_sidecar_builder,
}


@pytest.fixture(scope="module")
def ladder():
    """Every sampler and serving program dispatched once at a toy size: the
    micro engine, the slotted and the paged continuous engines (resume on)
    and the two uncached samplers. Returns {program name: the module name
    its remembered function lowers to}."""
    from dalle_pytorch_tpu.serving.engine import (
        ContinuousEngine, GenerationEngine, PagedContinuousEngine,
    )
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    scopes.forget()
    model, variables, text, _ = _tiny(depth=1)
    GenerationEngine(model=model, variables=variables, batch_shapes=(2,),
                     registry=MetricsRegistry()).warmup()
    ContinuousEngine(model=model, variables=variables, max_batch=4, chunk_tokens=2,
                     prefill_batch=2, resume_enabled=True,
                     registry=MetricsRegistry()).warmup()
    PagedContinuousEngine(model=model, variables=variables, max_batch=4, chunk_tokens=2,
                          prefill_batch=2, resume_enabled=True, page_size=4,
                          registry=MetricsRegistry()).warmup()
    D.generate_images_cached(model, variables, jax.random.PRNGKey(0), text)
    D.generate_images(model, variables, jax.random.PRNGKey(0), text)
    D.generate_texts(model, variables, jax.random.PRNGKey(0), text, 2)
    counted = (scopes.remembered, scopes.lowered)
    modules = {}
    for p in list(scopes._programs.values()):
        lowered = p["fn"].lower(*p["specs"]).as_text()
        modules.setdefault(p["name"], set()).add(
            re.search(r"module @(\w+)", lowered).group(1))
    yield modules, counted
    scopes.forget()


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_each_builder_lowers_to_a_module_of_its_own_name(ladder, name):
    modules, _ = ladder
    assert BUILDERS[name].__name__.endswith("_builder")  # still a builder
    assert modules.get(name) == {f"jit_{name}"}, (name, sorted(modules))
    others = set().union(*(v for k, v in modules.items() if k != name))
    assert f"jit_{name}" not in others
    assert "jit_fn" not in others


def test_remembering_costs_one_tree_map_per_program_and_lowers_nothing(ladder):
    _, (remembered, lowered) = ladder
    assert lowered == 0  # nobody asked for a table
    # every program was dispatched several times and remembered once
    assert remembered == len(scopes._programs) or remembered == scopes.KEPT
    assert remembered < 40


def test_remember_is_once_and_table_is_on_demand():
    scopes.forget()
    try:
        f = scopes.remembering(jax.jit(lambda x: jnp.tanh(x) * 2.0))
        f.name = "tiny_program"
        for _ in range(3):
            f(jnp.ones((4,)))
        assert (scopes.remembered, scopes.lowered) == (1, 0)
        assert scopes.table("no_such_program") is None and scopes.lowered == 0
        table = scopes.table("tiny_program")
        assert scopes.lowered == 1 and table
        assert scopes.table("tiny_program") is table and scopes.lowered == 1
        # a plain function remembers itself while it is traced, tracers and all
        def step(x):
            scopes.remember("self_traced", step, (x,), donate_argnums=0)
            return x + 1

        jax.jit(step, donate_argnums=0)(jnp.ones((3,)))
        jax.jit(step, donate_argnums=0)(jnp.ones((3,)))
        assert scopes.remembered == 2 and scopes.names() == ["tiny_program", "self_traced"]
        assert scopes.table("self_traced") and scopes.lowered == 2
    finally:
        scopes.forget()


def test_the_kept_programs_are_bounded():
    scopes.forget()
    try:
        for i in range(scopes.KEPT + 5):
            scopes.remember(f"p{i}", (lambda i=i: i), ())
        assert len(scopes._programs) == scopes.KEPT
        assert scopes.names()[0] == "p5"
    finally:
        scopes.forget()


# ---------------------------------------------------------- the benchmark's


def _run_cell(cell: str, trace: int):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark import run\n"
        "run.main(['--workload', %r, '--seed', '3', '--seconds', '1', '--trace', %r])\n"
        "from dalle_pytorch_tpu.obs import scopes\n"
        "import json; print('SCOPES ' + json.dumps([scopes.remembered, scopes.lowered, scopes.names()]))\n"
    ) % (str(REPO), cell, str(trace))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, cwd=str(REPO), timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines() if ln.startswith("SCOPES "))
    return json.loads(line[len("SCOPES "):])


@pytest.mark.parametrize("cell,programs", [
    ("_tiny.train", ["step"]),
    ("_tiny.generate", ["sample_cached", "sample_cached"]),
])
def test_an_untraced_run_remembers_once_per_program_and_lowers_nothing(cell, programs):
    remembered, lowered, names = _run_cell(cell, 0)
    assert lowered == 0
    assert names == programs and remembered == len(programs)


# ---------------------------------------------------------------- host spans


def _host_events(trace_dir) -> list:
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert files
    data = ProfileData.from_file(files[0])
    return [e.name for plane in data.planes if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events if e.name.startswith(HOST_SPAN_PREFIX)]


def test_host_spans_land_in_a_profiler_capture_of_the_train_loop(tmp_path):
    """The `_tiny.train` loop's pieces (Prefetcher, jitted step) under a CPU
    profiler capture: the input spans are in the profiler's own file."""
    sys.path.insert(0, str(REPO))
    from benchmark import harness
    from benchmark.loops import train

    cfg = harness.load("configs", "_tiny")
    job = harness.load("workloads", "_tiny.train")["job"]
    prog = train.Program(cfg, job)
    state, feed, rng = prog.begin(3)
    try:
        state, rng, loss = prog.dispatch(state, feed, rng)  # compile outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                state, rng, loss = prog.dispatch(state, feed, rng)
            with host_span("train.log_sync", step=3):
                float(loss)
        finally:
            jax.profiler.stop_trace()
    finally:
        feed.close()
    names = _host_events(tmp_path)
    assert names.count("dalle:input.wait") >= 3
    assert "dalle:input.assemble" in names and "dalle:train.log_sync" in names


def test_sampler_dispatch_and_engine_chunk_spans(tmp_path):
    from dalle_pytorch_tpu.serving.engine import ContinuousEngine, SampleSpec
    from dalle_pytorch_tpu.training.metrics import MetricsRegistry

    model, variables, text, _ = _tiny(depth=1)
    registry = MetricsRegistry()
    eng = ContinuousEngine(model=model, variables=variables, max_batch=4, chunk_tokens=2,
                           prefill_batch=2, registry=registry)
    eng.warmup()
    ids = np.zeros(TEXT_SEQ, np.int32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.prefill_slots([(0, SampleSpec(ids, seed=1)), (2, SampleSpec(ids, seed=2))])
        eng.step_chunk()
        eng.step_chunk()
        eng.harvest([0])
    finally:
        jax.profiler.stop_trace()
    names = _host_events(tmp_path)
    for want in ("dalle:serve.prefill", "dalle:serve.chunk", "dalle:serve.harvest",
                 "dalle:sample.dispatch"):
        assert want in names, (want, sorted(set(names)))
    # the occupancy histogram reads the rows the chunk span carries: two
    # chunks of two live rows
    text_out = registry.render()
    assert "dalle_serving_batch_occupancy_rows_sum 4" in text_out
    assert "dalle_serving_batch_occupancy_rows_count 2" in text_out
