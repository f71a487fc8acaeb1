"""A causal language model over the same trunk as DALLE.

Token embedding, `Transformer` (models/transformer.py, whose block options
the configuration sets), a final norm and a head, its own matrix or (`tied_head`)
the embedding; the loss is the
mean next-token cross-entropy over positions 0..N-2, computed by the
vocab-chunked loss of `ops/losses.py` so that the [B, N, V] logits are
never whole.

Generation, for a trunk of latent attention layers (with or without a
lightning indexer that selects the cached positions a query attends), of
linear (gated delta rule) and full ones, of window and full ones over
grouped K/V heads, or of layers that are ONE sublayer each (a Mamba-2 mixer,
an attention over grouped K/V heads or ungated routed experts: `_ssm_trunk`),
or of attention in a compressed latent whose q and k pass two causal
convolutions, beside top-1 experts behind a router that carries a state down
the depth (`_cca_trunk`): `prefill` writes a batch of prompts into a decode cache
(models/decode_cache.py, per-layer layout: the latent kind of layer, or
recurrent and K/V layers in one tree, or window rings beside full K/V),
`decode_step` takes one token a row against it, and `generate_tokens_cached`
runs a whole token loop in one dispatch, the cache held in place in the
loop's carry. A turn starts where the session's document ended: the K/V
layers' index set back, the recurrent layers' state and the window layers'
rings restored from the snapshot `prefill_cached` took. A latent trunk also
takes NEW tokens against what its cache holds (`extend`): `prefill_cached(
chunk=)` puts a long prompt in that way, a chunk a dispatch.

A trunk with a layer of grouped K/V heads (the window-and-full one, and the
one of single sublayers, whose Mamba-2 state and ring then stand in the same
per-row cache: rows whose documents differ in length share a step; and the
convolved one, whose layers keep their last position's tail there) keeps every
row at its OWN position; the window-and-full trunk may carry a
multi-token module (`draft_layers`; `CausalLM.draft_step`): one more block
after the trunk that, from the trunk's last hidden state at position i and
the token at i + 1, drafts the token at i + 2. Its token loop
(`_verify_sampler_builder`) feeds each row its committed token and its draft,
two positions a step, keeps the draft iff it IS the token the first
position's logits give, and drops a rejected position from every layer by the
row's index alone. `serving/paging.py` and the slot cache keep the DALL-E
cache geometry (ROADMAP.md, Queue 2 B).

Which layers a family builds (each layer's mixer and cached path, its cache
kind, its rotary table, its feed-forward) is `CausalLM.plan()`, the trunk's
`Transformer.plan`: what this module needs to know of a layer it reads there.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.core import freeze
from jax import lax

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.transformer import Transformer, routed_layers
from dalle_pytorch_tpu.obs.tracing import host_span
from dalle_pytorch_tpu.ops.losses import chunked_masked_ce
from dalle_pytorch_tpu.ops.sampling import gumbel_sample, gumbel_sample_per_row, top_k_filter

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# a published config's `layer_types` in `Transformer.attn_types`' words
LAYER_KINDS = {"sliding_attention": "window", "full_attention": "full",
               "linear_attention": "linear"}
MOE_COUNTS = ("moe_load", "moe_rows", "moe_dropped")
# an indexed latent layer's counters of a token step, beside them: the
# positions its indexer scored and the positions it then attended, over the rows
DSA_COUNTS = ("dsa_scored", "dsa_selected")
# what the trainers say of the family `_ssm_trunk` builds (training/steps.py, train_lm.py)
FORWARD_ONLY = (
    "the nemotron_h family (hybrid_override_pattern: layers that are a Mamba-2 mixer, an "
    "attention or ungated experts alone) is forward only: there is no backward for the chunked "
    "state-space scan nor for the ungated experts' grouped products; generate with it "
    "(generate_lm.py --config ...)")
# and of the family `_cca_trunk` builds
FORWARD_ONLY_CCA = (
    "the zaya family (cca_time0: attention in a compressed latent behind two causal "
    "convolutions, a router that carries its state from layer to layer, a scaled residual) is "
    "forward only: no gradient of it has been held to the reference; generate with it "
    "(generate_lm.py --config ...)")


def forward_only(plan) -> Optional[str]:
    """Why a model of this plan (`CausalLM.plan`) does not train, or None."""
    if any(layer.kind == "ssm" or layer.ff_kind == "relu2_experts" for layer in plan):
        return FORWARD_ONLY
    return FORWARD_ONLY_CCA if any(layer.kind == "cca" for layer in plan) else None


def rotary_spec(spec: dict, dim: int) -> dict:
    """A published `rope_parameters` entry in `ops/rotary.py`'s own words."""
    out = {"type": spec["rope_type"], "dim": dim, "theta": spec["rope_theta"]}
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"):
        if key in spec:
            out[key] = spec[key]
    return out


def _routed_ff(cfg: dict, dense: tuple, held: int, experts: str, shared: str,
               score: str) -> dict:
    """What both routed families read for their feed-forward: `dense` says
    which layers are a dense SwiGLU of `intermediate_size` and not routed;
    `experts` and `shared` name the keys that count the routed experts and
    the shared ones (of the routed width each); `scoring_func` (`score`
    where the config has no such key), `routed_scaling_factor`, and
    `norm_topk_prob`, which the routed layer always does."""
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("the routed layer renormalises the chosen scores (norm_topk_prob)")
    return dict(
        ff_kinds=tuple("swiglu" if d else "swiglu_experts" for d in dense),
        ff_dim=cfg["intermediate_size"] if any(dense) else 0,
        experts_total=cfg.get("published", {}).get(experts, held),
        moe_score=cfg.get("scoring_func", score),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_dim=int(cfg.get(shared, 0)) * cfg["moe_intermediate_size"],
    )


def _router_choice(cfg: dict) -> dict:
    """What a config with the keys `n_group` and `topk_group` says of the
    router's CHOICE: a learned score-correction bias (`topk_method:
    noaux_tc`, or no such key: the family of `_window_trunk`), and the groups
    the choice is limited to. Without the keys: nothing."""
    if "n_group" not in cfg:
        return {}
    method = cfg.get("topk_method", "noaux_tc")
    if method not in ("noaux_tc", "greedy", "group_limited_greedy"):
        raise ValueError(f"unknown topk_method {method!r}")
    groups = (int(cfg["n_group"]), int(cfg.get("topk_group", 1)))
    return dict(moe_score_bias=method == "noaux_tc",
                **({} if groups == (1, 1) else {"moe_groups": groups}))


def _latent_trunk(cfg: dict, depth: int, held: int) -> dict:
    """The trunk options of the family whose config has `kv_lora_rank`:
    latent attention in every layer, `first_k_dense_replace` dense SwiGLU
    layers before the routed ones, a shared expert of `n_shared_experts`
    times the routed width, sigmoid scores times `routed_scaling_factor`.
    With `rope_scaling` of type `yarn` the rotary table is YaRN's and the
    softmax scale is multiplied by m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    (the table's own factor is m(mscale) / m(mscale_all_dim)); with
    `index_topk` every layer has a lightning indexer of `index_n_heads` heads
    of `index_head_dim` and attends that many positions a query; with
    `n_group` the router chooses as `_router_choice` says."""
    dense, rope_dim = int(cfg["first_k_dense_replace"]), cfg["qk_rope_head_dim"]
    rotary = {"type": "default", "dim": rope_dim, "theta": cfg["rope_theta"]}
    extra = {}
    scaling = cfg.get("rope_scaling")
    if scaling:
        if scaling.get("type") != "yarn":
            raise ValueError(f"unknown rope_scaling type {scaling.get('type')!r}")
        m = lambda scale: 0.1 * float(scale) * math.log(float(scaling["factor"])) + 1.0
        all_dim = m(scaling.get("mscale_all_dim", 0.0))
        rotary = rotary_spec(
            {**scaling, "rope_type": "yarn", "rope_theta": cfg["rope_theta"],
             "attention_factor": m(scaling.get("mscale", 1.0)) / all_dim}, rope_dim)
        extra["softmax_mult"] = all_dim * all_dim
    if cfg.get("index_topk"):
        extra.update(index_heads=int(cfg["index_n_heads"]), index_dim=int(cfg["index_head_dim"]),
                     index_topk=int(cfg["index_topk"]))
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("this family's router scores by sigmoid (scoring_func)")
    return dict(
        attn_types=("latent",),
        rotary_specs={"latent": rotary},
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"], qk_rope_dim=rope_dim,
        v_dim=cfg["v_head_dim"], sandwich_norm=bool(cfg.get("sandwich_norm", False)),
        **extra, **_router_choice(cfg),
        **_routed_ff(cfg, tuple(i < dense for i in range(depth)), held,
                     "n_routed_experts", "n_shared_experts", "sigmoid"),
    )


def _window_trunk(cfg: dict, depth: int, held: int) -> dict:
    """The trunk options of the family whose config has `layer_types` of
    `sliding_attention` and `full_attention`: `num_key_value_heads` K/V heads
    shared by groups of query heads, a per-head q/k norm, a window of
    `sliding_window`; `mlp_layer_types` says which layers are `dense` and
    which `sparse` (routed: `num_experts`, `num_shared_experts`,
    `scoring_func`, `routed_scaling_factor`). `rope_parameters` holds an
    entry a kind of layer, or ONE entry for the model: the rotate-half
    rotary then turns the window layers alone and the full ones take none
    (the family's convention for a hybrid of local and global layers). A
    config with the keys `n_group` and `topk_group` has the router they
    belong to: the experts are chosen by score plus a learned
    score-correction bias, among the best `topk_group` of `n_group` groups
    (`_router_choice`)."""
    rope, dim_head = cfg["rope_parameters"], cfg["head_dim"]
    return dict(
        kv_heads=cfg["num_key_value_heads"], qk_norm=True, window=int(cfg["sliding_window"]),
        attn_types=tuple(LAYER_KINDS[k] for k in cfg["layer_types"][:depth]),
        rotary_specs=({"window": rotary_spec(rope, dim_head)} if "rope_type" in rope else
                      {LAYER_KINDS[k]: rotary_spec(spec, dim_head) for k, spec in rope.items()}),
        **{"moe_score_bias": False, **_router_choice(cfg)},
        **_routed_ff(cfg, tuple(t == "dense" for t in cfg["mlp_layer_types"][:depth]), held,
                     "num_experts", "num_shared_experts", "softmax"),
    )


def _hybrid_trunk(cfg: dict, depth: int) -> dict:
    """The trunk options of the family whose config has `linear_key_head_dim`:
    gated delta-rule layers (`linear_*` keys) among full ones by `layer_types`,
    full attention with one RMS gain over all of q's and of k's columns and no
    rotary embedding (a `rope_theta` of null), a norm on each sublayer's
    output alone, a dense SwiGLU in every layer."""
    heads, hidden = cfg["num_attention_heads"], cfg["hidden_size"]
    if cfg.get("rope_parameters", {}).get("rope_theta") is not None:
        raise ValueError("this family's full layers take no rotary embedding "
                         "(rope_parameters.rope_theta is null as published)")
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("a linear layer's key heads shared by value heads are not built")
    if cfg.get("num_key_value_heads", heads) != heads or hidden % heads:
        raise ValueError("a full layer keeps one K/V head of hidden_size / heads a query head")
    if not cfg.get("linear_allow_neg_eigval", False):
        raise ValueError("beta is 2 sigmoid(.) (linear_allow_neg_eigval)")
    return dict(
        attn_types=tuple(LAYER_KINDS[k] for k in cfg["layer_types"][:depth]),
        qk_norm="whole", prenorm=False, sandwich_norm=True,
        ff_kind="swiglu", ff_dim=cfg["intermediate_size"],
        linear_heads=cfg["linear_num_key_heads"], linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"], linear_conv=cfg["linear_conv_kernel_dim"],
    )


def _ssm_trunk(cfg: dict, depth: int, held: int) -> dict:
    """The trunk options of the family whose config has
    `hybrid_override_pattern` (`nemotron_h`): every layer ONE sublayer under
    one pre-norm and one residual, by the pattern's character: `M` a Mamba-2
    mixer (`mamba_num_heads` heads of `mamba_head_dim`, `n_groups` pairs of B
    and C of `ssm_state_size`, `conv_kernel` taps with a bias, prefill in
    chunks of `chunk_size`), `*` attention over `num_key_value_heads` shared
    K/V heads with no position embedding (the state-space layers carry
    position; `rope_theta` is read by nothing), `E` routed experts WITHOUT a
    gate, relu(x W_up)^2 W_down (`mlp_hidden_act: relu2`), chosen by sigmoid
    score plus a correction bias (`_router_choice`), beside a shared expert of
    `moe_shared_expert_intermediate_size`. A dense `-` layer is not built."""
    pattern = cfg["hybrid_override_pattern"][:depth]
    if len(pattern) != depth or set(pattern) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: {depth} layers of M, * and E")
    off = [k for k in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias") if cfg.get(k)]
    if (off or cfg["mlp_hidden_act"] != "relu2" or cfg.get("mamba_hidden_act", "silu") != "silu"
            or not cfg.get("use_conv_bias", True) or int(cfg.get("n_shared_experts", 1)) != 1):
        raise ValueError("this family builds relu2 experts beside one shared one, a SiLU "
                         f"state-space mixer with a convolution bias, and no other bias {off}")
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("the routed layer renormalises the chosen scores (norm_topk_prob)")
    return dict(
        attn_types=tuple({"M": "ssm", "*": "full", "E": "none"}[c] for c in pattern),
        ff_kinds=tuple("relu2_experts" if c == "E" else "none" for c in pattern),
        kv_heads=cfg["num_key_value_heads"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        ssm_conv=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        experts_total=cfg.get("published", {}).get("n_routed_experts", held),
        moe_score="sigmoid", routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_dim=int(cfg["moe_shared_expert_intermediate_size"]),
        **_router_choice(cfg),
    )


def _cca_trunk(cfg: dict, depth: int, held: int) -> dict:
    """The trunk options of the family whose config has `cca_time0`
    (`model_type: zaya`): every layer (`layer_types` all `hybrid`) an attention
    sublayer in a compressed latent, `num_attention_heads` query heads over
    `num_key_value_heads` K/V heads of `head_dim` whose q and k pass two causal
    convolutions of `cca_time0` and `cca_time1` = 2 taps, a rotate-half rotary
    over `partial_rotary_factor` of each head (`rope_parameters.hybrid`); then
    `num_experts` SwiGLU experts, ONE a token, no shared one, chosen by softmax
    score plus a balancing bias behind a router that is an MLP of
    `router_hidden_size` carrying its state down the depth, the chosen score the
    gate as it is (with one choice a renormalised one would be 1); every
    sublayer's result meets the stream scaled and shifted."""
    kinds = set(cfg["layer_types"][:depth])
    if (kinds != {"hybrid"} or (cfg["cca_time0"], cfg["cca_time1"]) != (2, 2)
            or cfg.get("sliding_window") or int(cfg["num_experts_per_tok"]) != 1):
        raise ValueError("this family builds `hybrid` layers alone (no sliding window), two "
                         f"convolutions of 2 taps each and ONE expert a token ({sorted(kinds)})")
    rot_dim = int(cfg["head_dim"] * float(cfg["partial_rotary_factor"]))
    return dict(
        attn_types=("cca",), kv_heads=cfg["num_key_value_heads"],
        rotary_specs={"cca": rotary_spec(cfg["rope_parameters"]["hybrid"], rot_dim)},
        ff_kind="swiglu_experts", moe_score="softmax", moe_score_bias=True,
        moe_renormalise=False, router_dim=int(cfg["router_hidden_size"]), residual="affine",
        experts_total=cfg.get("published", {}).get("num_experts", held),
    )


class CausalLM(nn.Module):
    num_tokens: int  # rows of the embedding and of the head held here
    dim: int
    depth: int
    seq_len: int
    heads: int = 8
    dim_head: int = 64
    # every further `Transformer` option (block variants, attn_types,
    # experts, attn_impl ...), as the configuration gives them
    trunk: Any = None
    # blocks of the multi-token module after the trunk (0: none; 1: one full-
    # attention routed block that drafts one token a row a step)
    draft_layers: int = 0
    tied_head: bool = False  # the head is the embedding (no matrix of its own)
    reversible: bool = False  # per-layer remat, as `DALLE.reversible`
    reversible_impl: str = "remat"
    remat_policy: Optional[str] = None
    ce_chunk: int = 2048
    dtype: Any = jnp.float32
    # what the matrices (embedding, head, the trunk's) are STORED in; norm
    # gains and routers stay float32
    param_dtype: Any = jnp.float32

    @classmethod
    def from_config(cls, cfg: dict, seq_len: int, **overrides) -> "CausalLM":
        """The model that a published `config.json` describes, as this
        process's share of it. `cfg` holds the published keys at its top
        level: the first `num_hidden_layers` layers, ids below `vocab_size`,
        and the experts held. Five families of keys are read. With `cca_time0`
        (`_cca_trunk`): `cca_time1`, `partial_rotary_factor`, `router_hidden_size`,
        `num_experts` ... (attention in a compressed latent behind two causal
        convolutions, top-1 experts behind an MLP router with a carried state,
        a scaled residual). With `hybrid_override_pattern` (`_ssm_trunk`): `mamba_*`, `ssm_state_size`,
        `n_groups`, `conv_kernel`, `chunk_size`, `mlp_hidden_act`,
        `layer_norm_epsilon`, `moe_shared_expert_intermediate_size` ... (layers
        that are a Mamba-2 mixer, an attention or ungated routed experts
        alone). With `linear_key_head_dim`: `layer_types` of `linear_attention` and
        `full_attention`, the `linear_*` keys, `intermediate_size`, a null
        `rope_theta` (gated delta-rule layers among full ones, no experts).
        Else with `layer_types`: `hidden_size`, `head_dim`, `rope_parameters`,
        `mlp_layer_types`, `num_experts`, `num_shared_experts`,
        `scoring_func`, `num_nextn_predict_layers` ... (grouped K/V heads,
        window and full layers, dense or routed by layer, a shared expert, a
        multi-token module: `_window_trunk`). With `kv_lora_rank`: `q_lora_rank`, `qk_nope_head_dim`,
        `qk_rope_head_dim`, `v_head_dim`, `first_k_dense_replace`,
        `n_routed_experts`, `n_shared_experts`, `routed_scaling_factor`,
        `sandwich_norm` ... (latent attention, leading dense layers, a shared
        expert beside sigmoid-routed ones). Three groups beside them are
        this repo's own and optional: `published`, what the router chooses
        among where fewer experts are held; `deployment.experts_first`, the
        first one held; `program`, how it is run: `dtype`, `weights_dtype`
        (what the matrices are stored in; `float32` where left out),
        `attn_impl`, `executor`, `moe_buffer_rows` (the static bound on the
        assignments a layer makes to the experts held), `reversible`,
        `reversible_impl`. `overrides` replace keys of `program`. Which layers
        the options below build is not decided here: `plan()` of the model
        made says it (`Transformer.plan`)."""
        prog = dict(cfg.get("program", {}), **overrides)
        depth = int(cfg["num_hidden_layers"])
        ssm = "hybrid_override_pattern" in cfg  # its activations are `_ssm_trunk`'s to check
        cca = "cca_time0" in cfg  # the one family whose head is the embedding
        if ((not ssm and cfg["hidden_act"] != "silu") or cfg.get("attention_bias", False)
                or cfg.get("lm_head_bias", False) or (cfg["tie_word_embeddings"] and not cca)):
            raise ValueError("the trunk builds SiLU gates, no biases and an untied head "
                             "(a tied one with `cca_time0` alone)")
        latent, hybrid = "kv_lora_rank" in cfg, "linear_key_head_dim" in cfg
        param_dtype = DTYPES[prog.get("weights_dtype", "float32")]
        trunk = dict(
            norm="rms", norm_eps=float(cfg["layer_norm_epsilon" if ssm else "rms_norm_eps"]),
            use_bias=False, layerscale=False,
            attn_impl=prog.get("attn_impl", "auto"), executor=prog.get("executor", "unrolled"),
        )
        if not hybrid:
            held = int(cfg["n_routed_experts" if latent or ssm else "num_experts"])
            trunk.update(
                experts_per_token=cfg["num_experts_per_tok"],
                experts_held=(cfg.get("deployment", {}).get("experts_first", 0), held),
                expert_dim=cfg["moe_intermediate_size"],
                moe_buffer_rows=int(prog["moe_buffer_rows"]),
            )
        if ssm:
            trunk.update(_ssm_trunk(cfg, depth, held), param_dtype=param_dtype)
            dim_head = cfg["head_dim"]
        elif cca:
            trunk.update(_cca_trunk(cfg, depth, held), param_dtype=param_dtype)
            dim_head = cfg["head_dim"]
        elif hybrid:
            trunk.update(_hybrid_trunk(cfg, depth), param_dtype=param_dtype)
            dim_head = cfg["hidden_size"] // cfg["num_attention_heads"]
        elif latent:
            trunk.update(_latent_trunk(cfg, depth, held), param_dtype=param_dtype)
            dim_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        else:
            drafts = int(cfg.get("num_nextn_predict_layers", 0))
            if drafts > 1 or (drafts and cfg.get("mtp_layer_types") != ["full_attention"]):
                raise ValueError("the multi-token module is one full-attention block")
            trunk.update(_window_trunk(cfg, depth, held), param_dtype=param_dtype,
                         draft_positions=drafts)
            dim_head = cfg["head_dim"]
        if (latent or hybrid or ssm or cca) and cfg.get("num_nextn_predict_layers", 0):
            raise ValueError("a multi-token module is built after the window-and-full trunk")
        return cls(
            num_tokens=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
            seq_len=seq_len, heads=cfg["num_attention_heads"], dim_head=dim_head,
            # frozen: the model is hashable, which keys its compiled samplers
            trunk=freeze(trunk), draft_layers=trunk.get("draft_positions", 0),
            tied_head=bool(cfg["tie_word_embeddings"]),
            reversible=bool(prog.get("reversible", False)),
            reversible_impl=prog.get("reversible_impl", "remat"),
            dtype=DTYPES[prog.get("dtype", "bfloat16")], param_dtype=param_dtype,
        )

    def setup(self):
        trunk = dict(self.trunk or {})
        self.token_emb = nn.Embed(
            self.num_tokens, self.dim, param_dtype=self.param_dtype,
            embedding_init=nn.initializers.normal(self.dim**-0.5),
        )
        self.transformer = Transformer(
            reversible=self.reversible, reversible_impl=self.reversible_impl,
            remat_policy=self.remat_policy, **self._trunk_options())
        norm = trunk.get("norm", "layer")
        self.logits_norm = (
            nn.RMSNorm(epsilon=trunk.get("norm_eps", 1e-6), dtype=self.dtype)
            if norm == "rms" else nn.LayerNorm(dtype=self.dtype)
        )
        if not self.tied_head:
            self.logits_dense = nn.Dense(self.num_tokens, use_bias=False, dtype=self.dtype,
                                         param_dtype=self.param_dtype)
        if not self.draft_layers:
            return
        assert self.draft_layers == 1 and norm == "rms", "one block, under RMS norms"
        rms = lambda: nn.RMSNorm(epsilon=trunk.get("norm_eps", 1e-6), dtype=self.dtype)
        self.mtp_norm_e, self.mtp_norm_h, self.mtp_norm_f = rms(), rms(), rms()
        self.mtp_proj = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                                 param_dtype=self.param_dtype)
        # the trunk's block, with full attention and a routed feed-forward
        self.mtp_block = Transformer(**{
            **self._trunk_options(), "depth": 1, "attn_types": ("full",),
            "ff_kinds": ("swiglu_experts",)})

    def hidden(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """[B, N, dim]: the trunk's output under the final norm."""
        x = self.token_emb(tokens).astype(self.dtype)
        return self.logits_norm(self.transformer(x))

    def _head(self, h: jnp.ndarray) -> jnp.ndarray:
        """Float32 logits of normed states [..., dim]."""
        with jax.named_scope("logits_head"):  # the `head` component (obs/scopes.py)
            if self.tied_head:  # over the embedding's rows, as it lies: no transpose
                return lax.dot_general(
                    h, self.token_emb.embedding.astype(h.dtype),
                    (((h.ndim - 1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            kernel = self.logits_dense.variables["params"]["kernel"]
            return jnp.dot(h, kernel.astype(h.dtype), preferred_element_type=jnp.float32)

    def draft_step(self, next_tokens: jnp.ndarray, hidden: jnp.ndarray,
                   layer_cache: Optional[dict] = None, start: bool = False):
        """The multi-token module over n positions a row: `(float32 logits
        [B, n, V], layer_cache)`. Position i takes the trunk's output there
        BEFORE the final norm (`hidden` [B, n, dim]) and the token at i + 1
        (`next_tokens` [B, n]):

            u_i = W_eh [rms_e(Emb(t_{i+1})); rms_h(h_i)]
            g_i = Block(u_0 .. u_i)       full attention, a routed feed-forward
            logits for position i + 2 = Head(rms_f(g_i))

        with the trunk's own embedding and head. `layer_cache`: the module's
        K/V layer of a decode cache, written at its own index, or with
        `start` from position 0 on (the chunk starts the rows' sequences: a
        prefill); left out, the n positions are a whole sequence. Everything
        runs under the scope `mtp`."""
        with jax.named_scope("mtp"):
            e = self.mtp_norm_e(self.token_emb(next_tokens).astype(self.dtype))
            u = self.mtp_proj(jnp.concatenate([e, self.mtp_norm_h(hidden)], axis=-1))
            if layer_cache is None:
                g = self.mtp_block(u)
            else:
                g, new = self.mtp_block(u, start=start, cache={decode_cache.layer_key(0): {
                    decode_cache.ATTN: layer_cache[decode_cache.ATTN]}})
                layer_cache = {**layer_cache, **new[decode_cache.layer_key(0)]}
            return self._head(self.mtp_norm_f(g)), layer_cache

    def draft_logits(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """[B, N - 1, V]: the module's logits over a whole sequence, uncached:
        entry i drafts position i + 2."""
        x = self.transformer(self.token_emb(tokens).astype(self.dtype))
        return self.draft_step(tokens[:, 1:], x[:, :-1])[0]

    def __call__(self, tokens: jnp.ndarray, return_loss: bool = False):
        """Logits [B, N, V] (float32), or with `return_loss` the mean
        cross-entropy of positions 0..N-2 against the next token."""
        h = self.hidden(tokens)
        if not return_loss or self.is_initializing():
            logits = (self._head(h) if self.tied_head
                      else self.logits_dense(h).astype(jnp.float32))
            if self.draft_layers and self.is_initializing():
                self.draft_logits(tokens)  # the module's parameters
            if not return_loss:
                return logits
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
        kernel = (self.token_emb.embedding.T if self.tied_head
                  else self.logits_dense.variables["params"]["kernel"])
        with jax.named_scope("loss"):
            per_pos = chunked_masked_ce(
                h[:, :-1], kernel, None, tokens[:, 1:],
                row_is_text=jnp.ones((tokens.shape[1] - 1,), bool),
                num_text_vocab=self.num_tokens, chunk=self.ce_chunk,
            )
            return jnp.mean(per_pos)

    def route_choices(self, tokens: jnp.ndarray, layer: int = 0) -> jnp.ndarray:
        """[B, N, k]: what layer `layer`'s router chooses for these tokens."""
        x = self.token_emb(tokens).astype(self.dtype)
        return self.transformer.route_choices(x, layer)

    # ------------------------------------------------------------ generation

    def init_cache(self, batch: int, max_len: Optional[int] = None) -> dict:
        """A zeroed decode cache of `batch` rows and `max_len` positions
        (`seq_len` where left out), in the model's dtype. Usable unbound. A
        multi-token module keeps its own full K/V layer after the trunk's,
        (`decode_cache.layer_key(depth)`), with the leaf `hidden` beside it."""
        max_len = max_len or self.seq_len
        trunk = self._trunk()
        cache = Transformer.init_cache(trunk, batch, max_len, self.dtype)
        if self.draft_layers:
            cache[decode_cache.layer_key(self.depth)] = decode_cache.make(
                decode_cache.PER_LAYER, 1, batch=batch, max_len=max_len, per_row=True,
                hidden=True, heads=trunk.kv_heads or self.heads, dim_head=self.dim_head,
                dim=self.dim, dtype=self.dtype)[decode_cache.layer_key(0)]
        return cache

    def plan(self) -> tuple:
        """The trunk's plan, a `LayerPlan` a layer (`Transformer.plan`: which
        layers a family builds, their cache kinds and cached paths)."""
        return self._trunk().plan()

    @property
    def per_row(self) -> bool:
        """Whether the cache keeps every row at its own position (the trunk
        of grouped K/V heads, window layers or a rotate-half rotary)."""
        return self.plan()[0].per_row

    def _trunk_options(self) -> dict:
        return dict(dim=self.dim, depth=self.depth, seq_len=self.seq_len, heads=self.heads,
                    dim_head=self.dim_head, rotary_emb=False, dtype=self.dtype,
                    **dict(self.trunk or {}))

    def _trunk(self) -> Transformer:
        """The trunk's configuration, unbound (for its plan and `init_cache`'s arithmetic)."""
        return Transformer(parent=None, **self._trunk_options())

    def extend(self, tokens: jnp.ndarray, cache: dict) -> dict:
        """The cache after further `tokens` [B, n] at the cache's index, each
        attending what the cache holds and the chunk up to itself: a prefill
        in chunks. A trunk of latent layers (`LatentAttention`: any index,
        the rows in lockstep); the other kinds raise where they meet it. No
        logits, as `prefill`."""
        x = self.token_emb(tokens).astype(self.dtype)
        return self.transformer(x, cache=cache)[1]

    def prefill(self, tokens: jnp.ndarray, cache: dict) -> dict:
        """The cache after `tokens` [B, n], which START each row's sequence
        (positions 0..n-1; further tokens against what a cache holds go
        through `extend`). No logits: the prompt's last token goes through
        `decode_step`, which gives them."""
        x = self.token_emb(tokens).astype(self.dtype)
        x, new = self.transformer(x, cache=cache, start=True)
        if not self.draft_layers:
            return new
        # the module over positions 0..n-2 (position i needs the token at
        # i + 1); the last position's state waits for the turn's first token
        at = decode_cache.layer_key(self.depth)
        layer = cache[at]  # of a one-token prompt the module holds no position yet
        if tokens.shape[1] > 1:
            _, layer = self.draft_step(tokens[:, 1:], x[:, :-1], layer, start=True)
        return {**new, at: {**layer, decode_cache.HIDDEN: x[:, -1]}}

    def verify_step(self, tokens: jnp.ndarray, cache: dict):
        """`(float32 logits [B, n, V], the trunk's output before the final
        norm [B, n, dim], cache)` after n tokens a row (a committed token and
        n - 1 drafts) at each row's own index, which comes back advanced by
        n: the caller sets it to what stayed. The module's layer is passed
        through untouched."""
        x = self.token_emb(tokens).astype(self.dtype)
        x, new = self.transformer(x, cache=cache)
        logits = self._head(self.logits_norm(x))
        return lax.optimization_barrier(logits), x, {**cache, **new}

    def decode_step(self, token: jnp.ndarray, cache: dict):
        """(float32 logits [B, V], cache) after one `token` [B] a row, at
        the cache's index."""
        x = self.token_emb(token[:, None]).astype(self.dtype)
        x, cache = self.transformer(x, cache=cache)
        logits = self._head(self.logits_norm(x[:, 0]))
        # whole once: the compiler otherwise computes the product again for its
        # second reader (a step samples from the logits AND keeps rows of
        # them), which at 100,352 ids is another read of the head (PERF.md, PR 33)
        return lax.optimization_barrier(logits), cache

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "CausalLM has no uncached sampler: `generate_tokens_cached` decodes a trunk "
            "of latent layers, of linear and full ones, of window and full ones over "
            "grouped K/V heads (with its multi-token module drafting), of single "
            "sublayers (state-space, attention, ungated experts), or of convolved latent "
            "attention beside top-1 experts through its cache; "
            "serving it (slots, pages, the engine) is not built (ROADMAP.md, Queue 2 B)"
        )


# ------------------------------------------------------------------ sampling


def _jitted(builder, model, static_key):
    """One compiled program per (builder, model, sampling parameters), as the
    DALL-E samplers keep theirs (models/dalle.py: jitted once, donating what
    the builder says, remembered by obs/scopes.py at its first dispatch). XLA
    calls the module after the function the builder returns: `jit_lm_sample`,
    `jit_lm_prefill`. Imported late: that module imports half the package."""
    from dalle_pytorch_tpu.models.dalle import _jitted_sampler

    return _jitted_sampler(builder, model, static_key)


def prefill_cached(model: CausalLM, variables, tokens: jnp.ndarray, cache: dict, row: int = 0,
                   chunk: Optional[int] = None):
    """`(cache, counts)`: `cache` (DONATED) with rows `row ..` (or, `row` a
    sequence of R rows, those) holding
    `tokens` [R, n] from position 0, and the routed layers' counts over the
    prompts (as `generate_tokens_cached` gives them). The prompts go through
    `CausalLM.prefill` into a fresh cache of their own length, whose rows
    are then written into the sessions' (one dispatch), a recurrent layer's
    state and a window layer's ring both as the running one and as the
    snapshot a later turn restores (`decode_cache.snapshot`). Every layer's index is left where it was: the
    caller sets it (`decode_cache.set_index`).

    With `chunk` the prompts go in CHUNKS of that many tokens
    (`prefill_chunks`, then `place_rows`): a trunk of latent layers."""
    if chunk is not None:
        fresh, counts = prefill_chunks(model, variables, tokens, chunk)
        return place_rows(model, cache, fresh, row), counts
    jitted = _jitted(_prefill_builder, model, ())
    with host_span("lm.prefill", program=jitted.name, rows=int(tokens.shape[0])):
        return jitted(variables, tokens, cache, jnp.asarray(row, jnp.int32))


def prefill_chunks(model: CausalLM, variables, tokens: jnp.ndarray, chunk: int):
    """`(fresh, counts)`: a cache of the prompts' own length holding `tokens`
    [R, n] from position 0, filled `chunk` tokens a dispatch through
    `CausalLM.extend` (host span `lm.prefill` each), every chunk attending
    what the cache holds by then and itself; the routed layers' counts summed
    over the chunks. A trunk of latent layers, or of convolved ones
    (`ConvLatentAttention`: each chunk goes on from the tail the last one
    left): any other raises here."""
    if {layer.cache_kind for layer in model.plan()} not in ({"latent"}, {"cca"}):
        raise NotImplementedError("a prefill in chunks takes a trunk of latent layers, or of "
                                  "convolved ones (whose tail hands a chunk's end to the next)")
    extend = _jitted(_extend_builder, model, ())
    fresh, counts = model.init_cache(*tokens.shape), None
    for at in range(0, tokens.shape[1], chunk):
        with host_span("lm.prefill", program=extend.name, rows=int(tokens.shape[0]), at=at):
            fresh, new = extend(variables, tokens[:, at:at + chunk], fresh)
        counts = new if counts is None else jax.tree.map(jnp.add, counts, new)
    return fresh, counts


def place_rows(model: CausalLM, cache: dict, fresh: dict, row: int = 0) -> dict:
    """`cache` (DONATED) with row r of `fresh` (a cache of prompts:
    `prefill_chunks`) written into its row `row + r`; sessions that hold one
    document each keep their own copy of it, a call a copy. The indices are
    left where they were."""
    first = next(iter(fresh.values()))[decode_cache.ATTN]
    rows = (first[decode_cache.K] if decode_cache.K in first
            else decode_cache.latent_leaf(fresh)).shape[0]
    at = row + jnp.arange(rows, dtype=jnp.int32)
    return _jitted(_place_builder, model, ())(cache, fresh, at)


def _rings(model, cache: dict) -> list:
    """The K leaves of a cache's window layers (rings)."""
    return [cache[decode_cache.layer_key(i)][decode_cache.ATTN][decode_cache.K]
            for i, layer in enumerate(model.plan()) if layer.cache_kind == "window"]


def _prefill_builder(model, key):
    def lm_prefill(variables, tokens, cache, row):
        fresh, aux = model.apply(
            variables, tokens, model.init_cache(*tokens.shape), method=CausalLM.prefill,
            mutable=["stats"])
        rows = row if row.ndim else row + jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return (decode_cache.scatter_rows(cache, decode_cache.snapshot(fresh), rows),
                _moe_counts(aux.get("stats", {})))

    return lm_prefill


_prefill_builder._donate_argnums = (2,)


def _extend_builder(model, key):
    def lm_extend(variables, tokens, fresh):
        fresh, aux = model.apply(variables, tokens, fresh, method=CausalLM.extend,
                                 mutable=["stats"])
        return fresh, _moe_counts(aux.get("stats", {}))

    return lm_extend


_extend_builder._donate_argnums = (2,)


def _place_builder(model, key):
    def lm_place(cache, fresh, rows):  # a convolved layer's tail also as its snapshot
        return decode_cache.scatter_rows(cache, decode_cache.snapshot(fresh), rows)

    return lm_place


_place_builder._donate_argnums = (0,)


def generate_tokens_cached(model: CausalLM, variables, key: jax.Array, cache: dict,
                           forced: jnp.ndarray, steps: int, filter_thres: float = 0.5,
                           temperature: float = 1.0, logit_rows: int = 0,
                           start: Optional[int] = None):
    """`steps` token steps of every row of `cache` (DONATED) in ONE dispatch.

    Step i feeds `forced[:, i]` while `i < forced.shape[1]` (at least one:
    a prompt's last token) and the row's own previous sample after; every
    step samples from its logits (`ops/sampling.py`: the top `1 -
    filter_thres` of the vocabulary, Gumbel noise at `temperature`; a
    `filter_thres` of 1.0 keeps one logit, which is greedy). The turn starts
    at `start` (the cache's own index where left out): the K/V layers' index
    is set there, which copies nothing, and a recurrent layer's state and
    ring, like a window layer's ring of K/V, are restored from their
    snapshot, one device copy a turn (`decode_cache.restore`). The cache rides the loop's carry and is written
    in place, one position a step from its index on.

    Returns `(tokens [B, steps] int32, logits [steps, logit_rows, V] float32
    of the first `logit_rows` rows, counts, cache)`; `counts` are the routed
    layers' `moe_load` [L, held], `moe_rows`, `moe_dropped` and `moe_touched`
    [L] (held experts with at least one row), each summed over the steps;
    of a cache with recurrent layers also, as host numbers, `state_bytes`
    (running and kept), `kv_bytes` and `state_restored_bytes`, the turn's copy.
    Of a trunk that selects the positions it attends (`index_topk`) over a
    cache longer than that: `dsa_scored` and `dsa_selected` [depth] (the
    positions a layer's indexer scored and the positions it attended, summed
    over rows and steps) and, with `logit_rows`, `picks`, NOT summed: of those
    rows `selected` [steps, depth, rows, index_topk] and `selected_count`
    [steps, depth, rows] (what each layer attended at each step) and
    `experts` [steps, rows, k] (the first routed layer's choice).

    A model whose cache keeps every row at its own position (`model.per_row`:
    the window-and-full trunk) runs `steps` VERIFY steps instead
    (`_verify_sampler_builder`): a step emits one token a row, or two where
    the multi-token module's draft was kept, and `forced` is what a row must
    EMIT first (`forced[:, 0]` is the token at `start`, fed by the first
    step; a draft is kept iff it equals the forced token). It returns
    `tokens` [B, cap] (`cap` = steps x the positions a step takes; a row's
    first `counts["emitted"]` entries are the tokens after `forced[:, 0]`),
    and for `logits` a dict over the first `logit_rows` rows: `logits`
    [steps, rows, positions, V], `draft` [steps, rows, V] (the module's
    logits that gave the NEXT step's draft, for position `at + 2 +
    accepted`), `at` [steps, rows] (the position of the step's committed
    token), `drafted` [steps, rows] (the draft the step fed beside it, at
    `at + 1`) and `accepted` [steps, rows]. `counts` gains `emitted`,
    `accepted` [B] and, as host numbers, `verify_steps`, `kv_bytes`,
    `ring_slots` and `ring_bytes` (the window layers' geometry: K and V, the
    running rings and their snapshots) and, of a cache with recurrent layers,
    `state_bytes` and `state_restored_bytes` as above. `start` may be [B]:
    every row's own position.
    """
    assert forced.ndim == 2 and 1 <= forced.shape[1] <= steps, forced.shape
    static_key = (int(steps), float(filter_thres), float(temperature), int(logit_rows))
    if start is None:  # a copy: the cache's own leaf is donated with it
        start = next(iter(cache.values()))[decode_cache.ATTN][decode_cache.INDEX] + 0
    if model.per_row:
        jitted = _jitted(_verify_sampler_builder, model, static_key + (None,))
        rings = _rings(model, cache)
        geometry = {"verify_steps": int(steps), "kv_bytes": decode_cache.kv_bytes(cache),
                    "ring_slots": rings[0].shape[2] if rings else 0,
                    # k and v, each running and kept
                    "ring_bytes": 4 * sum(r.size * r.dtype.itemsize for r in rings)}
        held = decode_cache.state_bytes(cache)
        if held:  # recurrent layers among them: running and kept, and the turn's copy
            geometry.update(state_bytes=held, state_restored_bytes=held // 2)
        with host_span("lm.sample.dispatch", program=jitted.name):
            tokens, logits, counts, cache = jitted(
                variables, key, cache, forced,
                jnp.broadcast_to(jnp.asarray(start, jnp.int32), forced.shape[:1]))
        return tokens, logits, {**counts, **geometry}, cache
    jitted = _jitted(_sampler_builder, model, static_key)
    held = decode_cache.state_bytes(cache)
    with host_span("lm.sample.dispatch", program=jitted.name):
        tokens, logits, counts, cache = jitted(
            variables, key, cache, forced, jnp.asarray(start, jnp.int32))
    if held:
        counts = {**counts, "state_bytes": held, "kv_bytes": decode_cache.kv_bytes(cache),
                  "state_restored_bytes": held // 2}
    return tokens, logits, counts, cache


def _moe_counts(stats: dict) -> dict:
    """The routed layers' counters of one step, stacked over those layers
    (in layer order, a multi-token module's block last), with `moe_touched`
    beside them."""
    routed = []
    for part in ("transformer", "mtp_block"):  # the trunk's, then the module's
        layers = stats.get(part, {})
        routed += [layers[n] for n in sorted((n for n in layers if "moe_load" in layers[n]),
                                             key=lambda n: int(n.rsplit("_", 1)[1]))]
    if not routed:
        return {}
    out = {k: jnp.stack([layer[k] for layer in routed]).astype(jnp.int32) for k in MOE_COUNTS}
    out["moe_touched"] = jnp.sum(out["moe_load"] > 0, axis=-1, dtype=jnp.int32)
    return out


def _zero_moe_counts(model, drafting: bool = False) -> dict:
    """A token loop's zeroed `_moe_counts`: of the layers the plan routes and,
    `drafting`, the multi-token module's block; nothing where none routes."""
    routed = routed_layers(model.plan()) + drafting
    zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
    return {} if not routed else {
        "moe_load": zeros(routed, model.trunk["experts_held"][1]),
        **{k: zeros(routed) for k in ("moe_rows", "moe_dropped", "moe_touched")}}


def _sown(layers: dict, key: str) -> list:
    """What the trunk's layers sowed under `key`, in layer order."""
    return [layers[n][key] for n in sorted((n for n in layers if key in layers[n]),
                                           key=lambda n: int(n.rsplit("_", 1)[1]))]


def _dsa_counts(stats: dict) -> dict:
    """The indexed latent layers' counters of one step (`DSA_COUNTS`),
    stacked over those layers in layer order; nothing where no layer selects."""
    layers = stats.get("transformer", {})
    return {k: jnp.stack(_sown(layers, k)).astype(jnp.int32)
            for k in DSA_COUNTS if _sown(layers, k)}


def _picks(picked: dict, rows: int) -> dict:
    """What one step sowed into `picks`, of the first `rows` rows: the
    positions each indexed layer selected (`selected` [layers, rows, k],
    `selected_count` [layers, rows]) and the FIRST routed layer's choice of
    experts (`experts` [rows, k])."""
    layers = picked.get("transformer", {})
    out = {k: jnp.stack([leaf[:rows] for leaf in _sown(layers, k)])
           for k in ("selected", "selected_count") if _sown(layers, k)}
    if _sown(layers, "experts"):
        out["experts"] = _sown(layers, "experts")[0][:rows]
    return out


def _sampler_builder(model, key):
    steps, filter_thres, temperature, logit_rows = key
    # a trunk that selects the positions it attends says, for the rows whose
    # logits are kept, what it selected and what its first router chose
    index_topk = max(layer.selects for layer in model.plan())
    picking = ["picks"] if index_topk and logit_rows else []

    def lm_sample(variables, rng, cache, forced, start):
        batch, n_forced = forced.shape
        cache, kept = decode_cache.restore(cache)
        cache = decode_cache.set_index(cache, start)

        def step(carry, i):
            cache, prev, rng, counts = carry
            with jax.named_scope("sample"):
                fed = lax.dynamic_index_in_dim(forced, jnp.minimum(i, n_forced - 1), 1, False)
                token = jnp.where(i < n_forced, fed, prev)
            (logits, cache), aux = model.apply(
                variables, token, cache, method=CausalLM.decode_step,
                mutable=["stats"] + picking)
            with jax.named_scope("rng_split"):
                rng, sample_rng = jax.random.split(rng)
            with jax.named_scope("sample"):
                filtered = top_k_filter(logits, thres=filter_thres)
                new = gumbel_sample(sample_rng, filtered, temperature=temperature)
                new = new.astype(jnp.int32)
            stats = aux.get("stats", {})
            counts = jax.tree.map(jnp.add, counts, {**_moe_counts(stats), **_dsa_counts(stats)})
            ys = (new, logits[:logit_rows])
            return (cache, new, rng, counts), ys + ((_picks(aux["picks"], logit_rows),)
                                                     if picking else ())

        counts = _zero_moe_counts(model)
        if index_topk and index_topk < decode_cache.max_len(cache):
            counts.update({k: jnp.zeros((model.depth,), jnp.int32) for k in DSA_COUNTS})
        carry = (cache, jnp.zeros((batch,), jnp.int32), rng, counts)
        (cache, _, _, counts), (tokens, logits, *picked) = lax.scan(
            step, carry, jnp.arange(steps))
        if picked:
            counts = {**counts, "picks": picked[0]}
        return tokens.T, logits, counts, decode_cache.snapshot(cache, kept)

    return lm_sample


_sampler_builder._donate_argnums = (2,)


def _verify_sampler_builder(model, key):
    """The token loop of a model whose cache keeps every row at its own
    position, `steps` verify steps in one `lax.scan`.

    A row stands at `pos` with the committed token `c` (the sequence's token
    at `pos`, not yet fed) and, where the model has a multi-token module, the
    draft `d` of the token at `pos + 1`. A step feeds `[c, d]` at `pos, pos +
    1` through the trunk; draws t1, the token at `pos + 1`, from the first
    position's logits; keeps the draft iff `d == t1`, and then also draws t2
    for `pos + 2` from the second's. Every layer's index is set to `pos + 1 +
    kept`: that alone drops a rejected position (models/decode_cache.py); the
    turn itself began from the rings' snapshot (`decode_cache.restore`). The
    module then runs over the two positions (`h_pos` with t1, `h_{pos+1}` with
    t2; its index, which stands at `pos`, moves by `1 + kept` too) and the
    logits of the last one that stayed give the next draft, their argmax.
    Before the first step it takes
    the one position it lags the trunk by: the prompt's last state, kept by
    the prefill (`hidden`), with the turn's first token.

    A token is drawn with a key folded from the ROW and the POSITION it
    takes, never from the step: the sequence emitted is the same whatever the
    drafts were, and equal to the loop's without a module (one position a
    step). The j-th token a row emits after `forced[:, 0]` is `forced[:, j +
    1]` while there is one. `key`: `(steps, filter_thres, temperature,
    logit_rows, drafter)`; `drafter(draft [B], position [B]) -> [B]` replaces
    the module's draft (tests: an oracle, a coin)."""
    steps, filter_thres, temperature, logit_rows, drafter = key
    drafting = bool(model.draft_layers)
    n = 2 if drafting else 1
    at_module = decode_cache.layer_key(model.depth)

    def lm_sample(variables, rng, cache, forced, start):
        batch, n_forced = forced.shape
        rows = jnp.arange(batch)
        cap = n * steps
        module = cache.get(at_module) if drafting else None
        cache = {name: layer for name, layer in cache.items() if layer is not module}
        run = lambda method, *args: model.apply(variables, *args, method=method,
                                                mutable=["stats"])

        def draw(logits, at, emitted):
            """[B]: the token at position `at`, the row's `emitted`-th."""
            with jax.named_scope("sample"):
                keys = jax.vmap(lambda r, p: jax.random.fold_in(jax.random.fold_in(rng, r), p))(
                    rows, at)
                new = gumbel_sample_per_row(
                    keys, top_k_filter(logits, thres=filter_thres),
                    jnp.full((batch,), temperature, jnp.float32)).astype(jnp.int32)
                must = forced[rows, jnp.minimum(emitted + 1, n_forced - 1)]
                return jnp.where(emitted + 1 < n_forced, must, new)

        def propose(logits, at):
            with jax.named_scope("mtp"), jax.named_scope("sample"):
                d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return d if drafter is None else drafter(d, at)

        def step(carry, _):
            cache, module, pos, c, d, count, out, counts = carry
            fed = jnp.stack([c, d], axis=1) if drafting else c[:, None]
            (logits, hidden, cache), aux = run(CausalLM.verify_step, fed, cache)
            # each part's counts from the call that ran it
            stats = {"transformer": aux.get("stats", {}).get("transformer", {})}
            t1 = draw(logits[:, 0], pos + 1, count)
            if drafting:
                with jax.named_scope("sample"), jax.named_scope("verify"):
                    kept = d == t1
                t2 = draw(logits[:, 1], pos + 2, count + 1)
            else:
                kept, t2 = jnp.zeros((batch,), bool), t1
            with jax.named_scope("sample"), jax.named_scope("verify"):
                out = out.at[rows, count].set(t1)
                out = out.at[rows, jnp.where(kept, count + 1, cap)].set(t2, mode="drop")
                moved = 1 + kept.astype(jnp.int32)
                cache = decode_cache.set_index(cache, pos + moved)
            draft_logits = jnp.zeros((batch, 0), jnp.float32)
            ys = (logits[:logit_rows], pos[:logit_rows], d[:logit_rows], kept[:logit_rows])
            if drafting:
                (both, module), aux = run(CausalLM.draft_step, jnp.stack([t1, t2], axis=1),
                                          hidden, module)
                stats["mtp_block"] = aux.get("stats", {}).get("mtp_block", {})
                with jax.named_scope("mtp"), jax.named_scope("verify"):
                    module = decode_cache.set_index(
                        {at_module: module}, pos + moved)[at_module]
                    draft_logits = both[rows, kept.astype(jnp.int32)]
                d = propose(draft_logits, pos + moved + 1)
            counts = jax.tree.map(
                jnp.add, counts,
                {**_moe_counts(stats), "accepted": kept.astype(jnp.int32)})
            carry = (cache, module, pos + moved, jnp.where(kept, t2, t1), d, count + moved,
                     out, counts)
            return carry, ys + (draft_logits[:logit_rows],)

        cache, rings = decode_cache.restore(cache)
        cache = decode_cache.set_index(cache, start)
        c, d = forced[:, 0], jnp.zeros((batch,), jnp.int32)
        if drafting:
            # the position the module lags the trunk by: the prompt's last state
            module = decode_cache.set_index({at_module: module}, start - 1)[at_module]
            (first, module), _ = run(CausalLM.draft_step, c[:, None],
                                     module[decode_cache.HIDDEN][:, None], module)
            d = propose(first[:, 0], start + 1)
        zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
        counts = {"accepted": zeros(batch), **_zero_moe_counts(model, drafting)}
        carry = (cache, module, start, c, d, zeros(batch), zeros(batch, cap), counts)
        (cache, module, _, _, _, count, out, counts), (logits, at, fed, kept, drafts) = lax.scan(
            step, carry, None, length=steps)
        cache = decode_cache.snapshot(cache, rings)
        if drafting:
            cache = {**cache, at_module: module}
        return (out, {"logits": logits, "draft": drafts, "at": at, "drafted": fed,
                      "accepted": kept}, {**counts, "emitted": count}, cache)

    return lm_sample


_verify_sampler_builder._donate_argnums = (2,)
