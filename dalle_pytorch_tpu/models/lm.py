"""A causal language model over the same trunk as DALLE.

Token embedding, `Transformer` (models/transformer.py, whose block options
the configuration sets), a final norm and an untied head; the loss is the
mean next-token cross-entropy over positions 0..N-2, computed by the
vocab-chunked loss of `ops/losses.py` so that the [B, N, V] logits are
never whole. Training only: the decode kernels, `serving/paging.py` and the
slot cache keep one K/V head per query head and one cache geometry
(ROADMAP.md, Queue 2 B), and `generate` says so.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from dalle_pytorch_tpu.models.transformer import Transformer
from dalle_pytorch_tpu.ops.losses import chunked_masked_ce

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# a published config's `layer_types` in `Transformer.attn_types`' words
LAYER_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def rotary_spec(spec: dict, dim: int) -> dict:
    """A published `rope_parameters` entry in `ops/rotary.py`'s own words."""
    out = {"type": spec["rope_type"], "dim": dim, "theta": spec["rope_theta"]}
    for key in ("factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor"):
        if key in spec:
            out[key] = spec[key]
    return out


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

    @classmethod
    def from_config(cls, cfg: dict, seq_len: int, **overrides) -> "CausalLM":
        """The model that a published `config.json` describes, as this
        process's share of it. `cfg` holds the published keys at its top
        level (`hidden_size`, `head_dim`, `layer_types`, `rope_parameters`,
        `num_experts` ...: the first `num_hidden_layers` layers, ids below
        `vocab_size`, `num_experts` experts held). Three groups beside them
        are this repo's own and optional: `published.num_experts`, what the
        router chooses among where fewer are held; `deployment.experts_first`,
        the first one held; `program`, how it is run: `dtype`, `attn_impl`,
        `executor`, `moe_buffer_rows` (the static bound on the assignments a
        layer makes to the experts held), `reversible`, `reversible_impl`.
        `overrides` replace keys of `program`."""
        prog = dict(cfg.get("program", {}), **overrides)
        depth = int(cfg["num_hidden_layers"])
        if cfg["hidden_act"] != "silu" or cfg["attention_bias"] or cfg["tie_word_embeddings"]:
            raise ValueError("the trunk builds SiLU gates, no biases and an untied head")
        if any(t != "sparse" for t in cfg["mlp_layer_types"][:depth]):
            raise ValueError("every layer's feed-forward has to be routed (`sparse`)")
        kinds = tuple(LAYER_KINDS[k] for k in cfg["layer_types"][:depth])
        held = int(cfg["num_experts"])
        trunk = dict(
            norm="rms", norm_eps=float(cfg["rms_norm_eps"]), ff_kind="swiglu_experts",
            use_bias=False, layerscale=False, kv_heads=cfg["num_key_value_heads"],
            qk_norm=True, window=int(cfg["sliding_window"]), attn_types=kinds,
            rotary_specs={LAYER_KINDS[k]: rotary_spec(spec, cfg["head_dim"])
                          for k, spec in cfg["rope_parameters"].items()},
            experts_total=cfg.get("published", {}).get("num_experts", held),
            experts_per_token=cfg["num_experts_per_tok"],
            experts_held=(cfg.get("deployment", {}).get("experts_first", 0), held),
            expert_dim=cfg["moe_intermediate_size"],
            moe_buffer_rows=int(prog["moe_buffer_rows"]),
            attn_impl=prog.get("attn_impl", "auto"), executor=prog.get("executor", "unrolled"),
        )
        return cls(
            num_tokens=cfg["vocab_size"], dim=cfg["hidden_size"], depth=depth,
            seq_len=seq_len, heads=cfg["num_attention_heads"], dim_head=cfg["head_dim"],
            trunk=trunk, reversible=bool(prog.get("reversible", False)),
            reversible_impl=prog.get("reversible_impl", "remat"),
            dtype=DTYPES[prog.get("dtype", "bfloat16")],
        )

    def setup(self):
        trunk = dict(self.trunk or {})
        self.token_emb = nn.Embed(
            self.num_tokens, self.dim,
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
        self.logits_dense = nn.Dense(self.num_tokens, use_bias=False, dtype=self.dtype)

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

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "CausalLM trains only: cached decode and serving keep one K/V head "
            "per query head and one cache geometry (ROADMAP.md, Queue 2 B)"
        )
