"""A causal language model over the same trunk as DALLE.

Token embedding, `Transformer` (models/transformer.py, whose block options
the configuration sets), a final norm and an untied head; the loss is the
mean next-token cross-entropy over positions 0..N-2, computed by the
vocab-chunked loss of `ops/losses.py` so that the [B, N, V] logits are
never whole.

Generation, for a trunk of latent attention layers or of linear (gated
delta rule) and full ones: `prefill` writes a batch of prompts into a decode
cache (models/decode_cache.py, per-layer layout: the latent kind of layer, or
recurrent and K/V layers in one tree), `decode_step` takes one token a row
against it, and `generate_tokens_cached` runs a whole token loop in one
dispatch, the cache held in place in the loop's carry. A turn starts where
the session's document ended: the K/V layers' index set back, the recurrent
layers' state restored from the snapshot `prefill_cached` took. A trunk with
fewer K/V heads than query heads, a window or a rotate-half rotary outside
the latent layer trains only; `serving/paging.py` and the slot cache keep one
cache geometry (ROADMAP.md, Queue 2 B).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.core import freeze
from jax import lax

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.transformer import Transformer
from dalle_pytorch_tpu.obs.tracing import host_span
from dalle_pytorch_tpu.ops.losses import chunked_masked_ce
from dalle_pytorch_tpu.ops.sampling import gumbel_sample, top_k_filter

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# a published config's `layer_types` in `Transformer.attn_types`' words
LAYER_KINDS = {"sliding_attention": "window", "full_attention": "full",
               "linear_attention": "linear"}
MOE_COUNTS = ("moe_load", "moe_rows", "moe_dropped")


def rotary_spec(spec: dict, dim: int) -> dict:
    """A published `rope_parameters` entry in `ops/rotary.py`'s own words."""
    out = {"type": spec["rope_type"], "dim": dim, "theta": spec["rope_theta"]}
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"):
        if key in spec:
            out[key] = spec[key]
    return out


def _latent_trunk(cfg: dict, depth: int, held: int) -> dict:
    """The trunk options of the family whose config has `kv_lora_rank`:
    latent attention in every layer, `first_k_dense_replace` dense SwiGLU
    layers before the routed ones, a shared expert of `n_shared_experts`
    times the routed width, sigmoid scores times `routed_scaling_factor`."""
    dense = int(cfg["first_k_dense_replace"])
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("the routed layer renormalises the chosen scores (norm_topk_prob)")
    return dict(
        attn_types=("latent",),
        rotary_specs={"latent": {"type": "default", "dim": cfg["qk_rope_head_dim"],
                                 "theta": cfg["rope_theta"]}},
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"], qk_rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], sandwich_norm=bool(cfg.get("sandwich_norm", False)),
        ff_kinds=("swiglu",) * min(dense, depth) + ("swiglu_experts",) * max(depth - dense, 0),
        ff_dim=cfg["intermediate_size"],
        experts_total=cfg.get("published", {}).get("n_routed_experts", held),
        moe_score="sigmoid", routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        shared_dim=int(cfg.get("n_shared_experts", 0)) * cfg["moe_intermediate_size"],
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
        and the experts held. Three families of keys are read. With
        `linear_key_head_dim`: `layer_types` of `linear_attention` and
        `full_attention`, the `linear_*` keys, `intermediate_size`, a null
        `rope_theta` (gated delta-rule layers among full ones, no experts).
        Else with `layer_types`: `hidden_size`, `head_dim`, `rope_parameters`,
        `num_experts` ... (grouped K/V heads, window and full layers, every
        layer routed). With `kv_lora_rank`: `q_lora_rank`, `qk_nope_head_dim`,
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
        `reversible_impl`. `overrides` replace keys of `program`."""
        prog = dict(cfg.get("program", {}), **overrides)
        depth = int(cfg["num_hidden_layers"])
        if cfg["hidden_act"] != "silu" or cfg["attention_bias"] or cfg["tie_word_embeddings"]:
            raise ValueError("the trunk builds SiLU gates, no biases and an untied head")
        latent, hybrid = "kv_lora_rank" in cfg, "linear_key_head_dim" in cfg
        param_dtype = DTYPES[prog.get("weights_dtype", "float32")]
        trunk = dict(
            norm="rms", norm_eps=float(cfg["rms_norm_eps"]), use_bias=False, layerscale=False,
            attn_impl=prog.get("attn_impl", "auto"), executor=prog.get("executor", "unrolled"),
        )
        if not hybrid:
            held = int(cfg["n_routed_experts" if latent else "num_experts"])
            trunk.update(
                experts_per_token=cfg["num_experts_per_tok"],
                experts_held=(cfg.get("deployment", {}).get("experts_first", 0), held),
                expert_dim=cfg["moe_intermediate_size"],
                moe_buffer_rows=int(prog["moe_buffer_rows"]),
            )
        if hybrid:
            trunk.update(_hybrid_trunk(cfg, depth), param_dtype=param_dtype)
            dim_head = cfg["hidden_size"] // cfg["num_attention_heads"]
        elif latent:
            trunk.update(_latent_trunk(cfg, depth, held), param_dtype=param_dtype)
            dim_head = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        else:
            if any(t != "sparse" for t in cfg["mlp_layer_types"][:depth]):
                raise ValueError("every layer's feed-forward has to be routed (`sparse`)")
            if param_dtype != jnp.float32:
                raise ValueError("weights_dtype is not built for the routed window-and-full trunk")
            trunk.update(
                ff_kind="swiglu_experts", kv_heads=cfg["num_key_value_heads"], qk_norm=True,
                window=int(cfg["sliding_window"]),
                attn_types=tuple(LAYER_KINDS[k] for k in cfg["layer_types"][:depth]),
                rotary_specs={LAYER_KINDS[k]: rotary_spec(spec, cfg["head_dim"])
                              for k, spec in cfg["rope_parameters"].items()},
                experts_total=cfg.get("published", {}).get("num_experts", held),
            )
            dim_head = cfg["head_dim"]
        return cls(
            num_tokens=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
            seq_len=seq_len, heads=cfg["num_attention_heads"], dim_head=dim_head,
            # frozen: the model is hashable, which keys its compiled samplers
            trunk=freeze(trunk), reversible=bool(prog.get("reversible", False)),
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
            dim=self.dim, depth=self.depth, seq_len=self.seq_len, heads=self.heads,
            dim_head=self.dim_head, causal=True, reversible=self.reversible,
            reversible_impl=self.reversible_impl, remat_policy=self.remat_policy,
            rotary_emb=False, dtype=self.dtype, **trunk,
        )
        norm = trunk.get("norm", "layer")
        self.logits_norm = (
            nn.RMSNorm(epsilon=trunk.get("norm_eps", 1e-6), dtype=self.dtype)
            if norm == "rms" else nn.LayerNorm(dtype=self.dtype)
        )
        self.logits_dense = nn.Dense(self.num_tokens, use_bias=False, dtype=self.dtype,
                                     param_dtype=self.param_dtype)

    def hidden(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """[B, N, dim]: the trunk's output under the final norm."""
        x = self.token_emb(tokens).astype(self.dtype)
        return self.logits_norm(self.transformer(x))

    def __call__(self, tokens: jnp.ndarray, return_loss: bool = False):
        """Logits [B, N, V] (float32), or with `return_loss` the mean
        cross-entropy of positions 0..N-2 against the next token."""
        h = self.hidden(tokens)
        if not return_loss or self.is_initializing():
            logits = self.logits_dense(h).astype(jnp.float32)
            if not return_loss:
                return logits
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
        kernel = self.logits_dense.variables["params"]["kernel"]
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
        (`seq_len` where left out), in the model's dtype. Usable unbound."""
        return Transformer.init_cache(self._trunk(), batch, max_len or self.seq_len, self.dtype)

    def _trunk(self) -> Transformer:
        """The trunk's configuration, unbound (for `init_cache`'s arithmetic)."""
        return Transformer(dim=self.dim, depth=self.depth, seq_len=self.seq_len,
                           heads=self.heads, dim_head=self.dim_head, rotary_emb=False,
                           dtype=self.dtype, parent=None, **dict(self.trunk or {}))

    def prefill(self, tokens: jnp.ndarray, cache: dict) -> dict:
        """The cache after `tokens` [B, n], which START each row's sequence
        (positions 0..n-1; a prefill of further tokens against what a cache
        holds is not built). No logits: the prompt's last token goes through
        `decode_step`, which gives them."""
        x = self.token_emb(tokens).astype(self.dtype)
        return self.transformer(x, cache=cache)[1]

    def decode_step(self, token: jnp.ndarray, cache: dict):
        """(float32 logits [B, V], cache) after one `token` [B] a row, at
        the cache's index."""
        x = self.token_emb(token[:, None]).astype(self.dtype)
        x, cache = self.transformer(x, cache=cache)
        h = self.logits_norm(x[:, 0])
        kernel = self.logits_dense.variables["params"]["kernel"]
        with jax.named_scope("logits_head"):  # the `head` component (obs/scopes.py)
            logits = jnp.dot(h, kernel.astype(h.dtype), preferred_element_type=jnp.float32)
        # whole once: the compiler otherwise computes the product again for its
        # second reader (a step samples from the logits AND keeps rows of
        # them), which at 100,352 ids is another read of the head (PERF.md, PR 33)
        return lax.optimization_barrier(logits), cache

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "CausalLM has no uncached sampler: `generate_tokens_cached` decodes a trunk "
            "of latent layers, or of linear and full ones, through its cache; cached "
            "decode of K/V heads shared by query heads, of a window or of a rotate-half "
            "rotary, and serving, are not built (ROADMAP.md, Queue 2 B)"
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


def prefill_cached(model: CausalLM, variables, tokens: jnp.ndarray, cache: dict, row: int = 0):
    """`(cache, counts)`: `cache` (DONATED) with rows `row ..` holding
    `tokens` [R, n] from position 0, and the routed layers' counts over the
    prompts (as `generate_tokens_cached` gives them). The prompts go through
    `CausalLM.prefill` into a fresh cache of their own length, whose rows
    are then written into the sessions' (one dispatch), a recurrent layer's
    state both as the running one and as the snapshot a later turn restores
    (`decode_cache.snapshot`). Every layer's index is left where it was: the
    caller sets it (`decode_cache.set_index`)."""
    jitted = _jitted(_prefill_builder, model, ())
    with host_span("lm.prefill", program=jitted.name, rows=int(tokens.shape[0])):
        return jitted(variables, tokens, cache, jnp.asarray(row, jnp.int32))


def _prefill_builder(model, key):
    def lm_prefill(variables, tokens, cache, row):
        fresh, aux = model.apply(
            variables, tokens, model.init_cache(*tokens.shape), method=CausalLM.prefill,
            mutable=["stats"])
        rows = row + jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return (decode_cache.scatter_rows(cache, decode_cache.snapshot(fresh), rows),
                _moe_counts(aux.get("stats", {})))

    return lm_prefill


_prefill_builder._donate_argnums = (2,)


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
    ring are restored from its snapshot, one device copy a turn
    (`decode_cache.restore`). The cache rides the loop's carry and is written
    in place, one position a step from its index on.

    Returns `(tokens [B, steps] int32, logits [steps, logit_rows, V] float32
    of the first `logit_rows` rows, counts, cache)`; `counts` are the routed
    layers' `moe_load` [L, held], `moe_rows`, `moe_dropped` and `moe_touched`
    [L] (held experts with at least one row), each summed over the steps;
    of a cache with recurrent layers also, as host numbers, `state_bytes`
    (running and kept), `kv_bytes` and `state_restored_bytes`, the turn's copy.
    """
    assert forced.ndim == 2 and 1 <= forced.shape[1] <= steps, forced.shape
    static_key = (int(steps), float(filter_thres), float(temperature), int(logit_rows))
    jitted = _jitted(_sampler_builder, model, static_key)
    if start is None:  # a copy: the cache's own leaf is donated with it
        start = next(iter(cache.values()))[decode_cache.ATTN][decode_cache.INDEX] + 0
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
    (in layer order), with `moe_touched` beside them."""
    layers = stats.get("transformer", {})
    routed = sorted((n for n in layers if "moe_load" in layers[n]),
                    key=lambda n: int(n.rsplit("_", 1)[1]))
    if not routed:
        return {}
    out = {k: jnp.stack([layers[n][k] for n in routed]).astype(jnp.int32) for k in MOE_COUNTS}
    out["moe_touched"] = jnp.sum(out["moe_load"] > 0, axis=-1, dtype=jnp.int32)
    return out


def _sampler_builder(model, key):
    steps, filter_thres, temperature, logit_rows = key

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
                variables, token, cache, method=CausalLM.decode_step, mutable=["stats"])
            with jax.named_scope("rng_split"):
                rng, sample_rng = jax.random.split(rng)
            with jax.named_scope("sample"):
                filtered = top_k_filter(logits, thres=filter_thres)
                new = gumbel_sample(sample_rng, filtered, temperature=temperature)
                new = new.astype(jnp.int32)
            counts = jax.tree.map(jnp.add, counts, _moe_counts(aux.get("stats", {})))
            return (cache, new, rng, counts), (new, logits[:logit_rows])

        trunk = dict(model.trunk or {})
        routed = sum(k == "swiglu_experts" for k in
                     trunk.get("ff_kinds") or (trunk.get("ff_kind"),) * model.depth)
        zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
        counts = {} if not routed else {
            "moe_load": zeros(routed, trunk["experts_held"][1]),
            **{k: zeros(routed) for k in ("moe_rows", "moe_dropped", "moe_touched")}}
        carry = (cache, jnp.zeros((batch,), jnp.int32), rng, counts)
        (cache, _, _, counts), (tokens, logits) = lax.scan(step, carry, jnp.arange(steps))
        return tokens.T, logits, counts, decode_cache.snapshot(cache, kept)

    return lm_sample


_sampler_builder._donate_argnums = (2,)
