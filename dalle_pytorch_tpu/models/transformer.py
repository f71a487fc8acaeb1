"""Transformer block assembly for DALLE/CLIP.

TPU-native re-design of the reference transformer
(`/root/reference/dalle_pytorch/transformer.py:206-353`). Feature parity:

  * per-layer attention-type cycling over
    {full, sparse, axial_row, axial_col, conv_like} (`transformer.py:238-266`)
    — every variant realized as dense attention + static mask (ops/masks.py);
  * cross-layer weight sharing via shared_attn_ids / shared_ff_ids
    (`transformer.py:242-279`) — flax module reuse shares parameters;
  * LayerScale with depth-dependent init (`transformer.py:76-90`);
  * PreNorm with optional sandwich output norm (`transformer.py:94-104`);
  * GEGLU feed-forward (`transformer.py:108-124`);
  * token-shift before attention and FF (`transformer.py:128-202`), as a
    pure function on the fixed-shape sequence;
  * dual rotary embeddings (1-D text + 2-D axial pixel with sentinel
    positions, `transformer.py:306-330`), precomputed host-side;
  * `reverse_model=True` runs layers in reversed order — the fork's
    inverse-mapping trick (`reversible.py:141-144`);
  * reversible mode, two executors selected by `reversible_impl`:
      - "remat": `jax.remat` per layer (recompute in backward — the memory
        behavior `reversible.py:57-127` buys, cost O(depth) residuals);
      - "revnet": a TRUE RevNet executor via `nn.custom_vjp` matching the
        reference's `ReversibleBlock`/`_ReversibleFunction` math
        (`reversible.py:57-127`): channels duplicated into (x1, x2) streams
        (`reversible.py:158,165`), y1 = x1 + attn(x2), y2 = x2 + ff(y1),
        output = mean of streams; the backward RECONSTRUCTS each block's
        inputs from its outputs (x2 = y2 − g(y1), x1 = y1 − f(x2)) so
        activation memory is O(1) in depth. The reference's CUDA RNG
        state capture (`reversible.py:32-53`) is unnecessary here: the
        revnet path requires deterministic execution (dropout rate 0),
        which JAX guarantees under explicit PRNG keys.

Layer executors (orthogonal to the reversible memory modes):
  * "unrolled" (default): layers unrolled in Python (static depth) — one
    big fusable graph, supports every feature (type cycling, sharing,
    cached decode, revnet);
  * "scan": homogeneous stacks run as `nn.scan` over depth-stacked
    parameters — the HLO contains ONE layer body instead of `depth`
    copies, so programs compile ~depth× faster at identical runtime
    math. Attn-type cycling runs
    as dense attention with per-layer pattern masks scanned over depth;
    no cross-layer sharing. KV-cached decode is native: the depth-stacked
    cache rides the layer scan's CARRY beside x, each layer writes its
    chunk's positions into the stack at its own index and attends over
    `stack[layer]`, so a token step moves one token's K/V and the loop
    hands back the buffer it took. Pattern masks included — each layer's
    traced mask row-slices at the decode position like the unrolled
    executor's static masks.
"""

from __future__ import annotations

import functools
import math
from itertools import cycle, islice
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.attention import (
    CCA, LATENT, LINEAR, ROWS, SSM,
    Attention,
    ConvLatentAttention,
    GatedDeltaAttention,
    LatentAttention,
    Mamba2Mixer,
    attention_path,
    cca_tail_dim,
)
from dalle_pytorch_tpu.ops.masks import (
    axial_static_mask,
    conv_like_mask,
    block_sparse_layout,
    block_layout_to_token_mask,
)
from dalle_pytorch_tpu.models.moe import RoutedExperts
from dalle_pytorch_tpu.ops.pallas_attention import RESIDUAL_NAMES
from dalle_pytorch_tpu.ops.rotary import build_dalle_rotary, rotary_cos_sin
from dalle_pytorch_tpu.ops.shift import (
    shift_tokens_dalle,
    shift_ring_from_prefill,
    shift_ring_from_prefill_at,
    shift_token_step,
)


# the two policy names that are not `jax.checkpoint_policies`' own, rungs of
# one ladder of what a remat layer keeps beside its input: `flash_residuals`
# what the flash kernels' forward rule hands their backward and nothing
# else, `layer_residuals` those and the feed-forward's two products.
# ONE policy object a name for every layer: jax caches a jitted emitter's
# partial evaluation by the policy's identity, and a policy made anew per
# layer would lower each kernel body once per call site.
FLASH_RESIDUALS = "flash_residuals"
LAYER_RESIDUALS = "layer_residuals"
FF_RESIDUAL_NAMES = ("ff_hidden", "ff_out")
_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
_KEEP_LAYER_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *RESIDUAL_NAMES, *FF_RESIDUAL_NAMES)


def resolve_remat_policy(name: "Optional[str]"):
    """`jax.checkpoint_policies` member by name, `FLASH_RESIDUALS`,
    `LAYER_RESIDUALS`, or None (save nothing). Single resolution point for
    all three executors (scan, unrolled remat, pipeline) so their
    activation-memory behavior cannot drift.

    What a remat layer holds from its forward to its backward, in bytes a
    layer at two bytes an element (B x N tokens, `dim` = heads x dim_head):

    - None / `nothing_saveable`: the layer's input alone, B x N x dim x 2.
      The backward runs the whole layer a second time.
    - `flash_residuals`: + the flash kernels' q, k, v, attention result and
      log-sum-exp (`pallas_attention.RESIDUAL_NAMES`), B x N x (4 x dim x 2
      + heads x 4). The backward runs neither the forward kernel nor the
      projection and rotary before it a second time.
    - `layer_residuals`: + the feed-forward's first product as the GEGLU
      reads it and its result (`FF_RESIDUAL_NAMES`), B x N x (2 x ff_mult +
      1) x dim x 2. The backward reads the first where the GEGLU's gradient
      needs it and the second where the LayerScale vector's does (its
      gradient is the result times the cotangent), so neither product runs
      twice; what it still builds again is the feed-forward's INPUT
      (`to_out`, the token shift, a norm) and the GEGLU's elementwise pass.

    The flagship step (16 x 1,280 x 1,024, 12 layers, `ff_mult` 4) plans
    5.39 | 8.15 | 12.66 GB on one v5e, which loads 16.9 (14.65 without remat;
    `tests/test_tpu_compile.py`). A layer whose attention is not the flash
    kernel, or whose feed-forward is not `FeedForward`, has no such names
    and keeps nothing for them. A deep trunk (64 layers keep 64 x 607 MB at
    that batch) or a stack at its memory's edge asks for a leaner rung by
    name."""
    if name == FLASH_RESIDUALS:
        return _KEEP_FLASH_RESIDUALS
    if name == LAYER_RESIDUALS:
        return _KEEP_LAYER_RESIDUALS
    return getattr(jax.checkpoint_policies, name) if name else None


def layerscale_init(layer_index: int) -> float:
    """LayerScale init epsilon by 1-based layer index (`transformer.py:79-84`)."""
    if layer_index <= 18:
        return 0.1
    if layer_index <= 24:
        return 1e-5
    return 1e-6


class DivideMax(nn.Module):
    """Divide by the (detached) max along an axis (`transformer.py:31-38`)."""

    axis: int = -1

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        maxes = jax.lax.stop_gradient(jnp.max(x, axis=self.axis, keepdims=True))
        return x / maxes


class FeedForward(nn.Module):
    """GEGLU feed-forward (`transformer.py:108-124`)."""

    dim: int
    mult: float = 4.0
    dropout: float = 0.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        hidden = int(self.dim * self.mult)
        # both products named, the first as the GEGLU reads it: a remat layer
        # whose policy knows the names keeps them and multiplies once;
        # anywhere else a name is the identity
        name_hidden, name_out = FF_RESIDUAL_NAMES
        x = checkpoint_name(nn.Dense(hidden * 2, dtype=self.dtype)(x), name_hidden)
        x, gates = jnp.split(x, 2, axis=-1)
        x = x * nn.gelu(gates)
        x = nn.Dropout(self.dropout)(x, deterministic=deterministic)
        return checkpoint_name(nn.Dense(self.dim, dtype=self.dtype)(x), name_out)


class SwiGLU(nn.Module):
    """Dense gated feed-forward, (silu(x W_gate) * (x W_up)) W_out, no biases."""

    dim: int
    hidden: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        dense = lambda width, name: nn.Dense(
            width, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype, name=name)
        return dense(self.dim, "w_out")(
            nn.silu(dense(self.hidden, "w_gate")(x)) * dense(self.hidden, "w_up")(x))


class AffineResidual(nn.Module):
    """A sublayer's result f meeting the stream x scaled and shifted: (a x + b)
    + (c f + d), `vectors` [4, dim] float32 the rows a, b, c, d (the plain sum
    is 1, 0, 1, 0), computed in float32, the result in the stream's dtype."""

    dim: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, f: jnp.ndarray) -> jnp.ndarray:
        plain = lambda key, shape: jnp.tile(jnp.array([[1.0], [0.0]], jnp.float32), (2, shape[1]))
        a, b, c, d = self.param("vectors", plain, (4, self.dim))
        return ((a * x + b) + (c * f + d)).astype(x.dtype)


def _build_static_mask(
    attn_type: str,
    seq_len: int,
    image_fmap_size: Optional[int],
    layer_ind: int,
    sparse_block: int = 16,
    sparse_text_len: Optional[int] = None,
) -> Optional[np.ndarray]:
    if attn_type == "full":
        return None
    assert image_fmap_size is not None, f"attn_type {attn_type} needs image_fmap_size"
    if attn_type == "axial_row":
        return axial_static_mask(seq_len, image_fmap_size, axis=0)
    if attn_type == "axial_col":
        return axial_static_mask(seq_len, image_fmap_size, axis=1)
    if attn_type == "conv_like":
        return conv_like_mask(seq_len, image_fmap_size)
    if attn_type == "sparse":
        # VariableSparsityConfig semantics (`attention.py:349-365`): block 16,
        # seq//block//4 random blocks, text blocks global. Padded to a block
        # multiple; layer index seeds the random blocks so layers differ.
        padded = sparse_block * math.ceil((seq_len + 1) / sparse_block)
        text_len = sparse_text_len if sparse_text_len is not None else (
            seq_len + 1 - image_fmap_size**2
        )
        layout = block_sparse_layout(
            padded,
            block=sparse_block,
            num_random_blocks=max(padded // sparse_block // 4, 1),
            global_block_indices=tuple(range(math.ceil(text_len / sparse_block))),
            causal=True,
            seed=layer_ind,
        )
        return block_layout_to_token_mask(layout, sparse_block, causal=True)
    raise ValueError(f'attention type "{attn_type}" is not valid')


def shift_with_ring(h, ring, pos, text_len, fmap, ring_end=None):
    """Token-shift dispatch shared by both executors' cached paths.

    ring None: pure batch shift (uncached). Prefill (n > 1, necessarily
    from position 0): batch shift + build the ring from trailing tokens
    — or, when `ring_end` ([B] per-row positions) is set, from each
    row's OWN trailing window below ring_end (the decode-resume path:
    one teacher-forced forward restores per-row mid-decode ring state).
    Single-token decode: streaming shift at traced position `pos`.
    Returns (shifted h, new ring or None).
    """
    with jax.named_scope("token_shift"):
        if ring is None:
            return shift_tokens_dalle(h, text_len, fmap), None
        if h.shape[1] > 1:
            shifted = shift_tokens_dalle(h, text_len, fmap)
            if ring_end is not None:
                return shifted, shift_ring_from_prefill_at(h, fmap, ring_end)
            return shifted, shift_ring_from_prefill(h, fmap)
        return shift_token_step(h, ring, pos, text_len, fmap)


class LayerPlan(NamedTuple):
    """What ONE layer of the stack is: decided once, from the trunk's options
    (`Transformer.plan`), and read wherever a layer's kind matters."""

    # of mixer: full, axial_row, axial_col, conv_like, sparse, window, latent,
    # linear, ssm, cca, or `none`: no mixer, the layer is its feed-forward alone
    kind: str
    attn_id: int  # layers of one id share their mixer's weights (`attn_{id}`)
    ff_id: int  # and their feed-forward's (`ff_{id}`)
    # the mixer it is built as and the path that mixer's cached call takes:
    # DALLE, LANES, ROWS, LATENT, LINEAR, SSM or CCA of models/attention.py (NO_MIXER: none)
    path: str
    # what `decode_cache.layer_spec` is asked for: heads, window, latent,
    # recurrent, cca, or `none` (a layer without a mixer holds nothing)
    cache_kind: str
    # positions a latent layer's lightning indexer selects for a query (0: no
    # indexer; with one the layer's cache keeps the indexer's keys too)
    selects: int
    # the cache's index is per row whoever asks (a stack with a layer on
    # ROWS); else scalar or, over the DALL-E lanes, the caller's choice
    per_row: bool
    rotary: Optional[str]  # which rotate-half table it is handed (a key of `rotary_specs`)
    takes_start: bool  # whether its call is told that a chunk starts the rows' sequences
    # geglu, swiglu, swiglu_experts or relu2_experts (routed: `routed_layers`),
    # or `none`: no feed-forward, the layer is its mixer alone
    ff_kind: str
    # what its router takes from the routed layer before it and hands to the
    # next, beside x and the cache: `router_state` (an MLP router's), or None
    carries: Optional[str] = None


NO_MIXER = "none"  # a layer's `kind` and `path` where it has no mixer
# routed feed-forwards, by the expert each holds (models/moe.py:RoutedExperts.act)
ROUTED_KINDS = {"swiglu_experts": "swiglu", "relu2_experts": "relu2"}


def routed_layers(plan) -> int:
    """How many layers of a plan route their tokens to experts."""
    return sum(layer.ff_kind in ROUTED_KINDS for layer in plan)


CACHE_KINDS = {"latent": "latent", "linear": "recurrent", "ssm": "recurrent",
               "window": "window", CCA: "cca", NO_MIXER: "none"}


@functools.lru_cache(maxsize=None)
def _stack_plan(depth, attn_types, attn_ids, ff_ids, ff_kinds, heads, kv_heads, qk_norm,
                window, rotated, index_topk, router_dim=0) -> Tuple[LayerPlan, ...]:
    """`Transformer.plan` over the options it reads, made hashable: once a
    set of options, not once a call."""
    assert len(ff_kinds) == depth, f"{len(ff_kinds)} ff_kinds for {depth} layers"
    kinds = tuple(islice(cycle(attn_types), depth))
    attn_ids = tuple(islice(cycle(attn_ids or range(depth)), depth))
    ff_ids = tuple(islice(cycle(ff_ids or range(depth)), depth))
    paths, kind_of_id = [], {}
    for ind, (kind, attn_id) in enumerate(zip(kinds, attn_ids)):
        if kind_of_id.setdefault(attn_id, kind) != kind:
            raise ValueError(
                "attn_types do not match shared_attn_ids "
                f"(ind = {ind}, attn_type = {kind!r}, "
                f"reused_attn_type = {kind_of_id[attn_id]!r})"
            )
        if kind == "window":
            assert window, 'attn_types has "window" and the model no window length'
        paths.append(kind if kind in (LATENT, LINEAR, SSM, CCA, NO_MIXER) else attention_path(
            heads, kv_heads, qk_norm, window if kind == "window" else None, kind in rotated))
    per_row = ROWS in paths or CCA in paths
    return tuple(
        LayerPlan(kind=kind, attn_id=attn_id, ff_id=ff_id, path=path,
                  cache_kind=CACHE_KINDS.get(kind, "heads"), per_row=per_row,
                  selects=index_topk if kind == "latent" else 0,
                  rotary=kind if kind in rotated else None,
                  takes_start=kind not in (LINEAR, SSM, NO_MIXER), ff_kind=ff_kind,
                  carries="router_state" if router_dim and ff_kind in ROUTED_KINDS else None)
        for kind, attn_id, ff_id, path, ff_kind in zip(kinds, attn_ids, ff_ids, paths, ff_kinds))


class _ScanBlock(nn.Module):
    """One (attn, ff) residual pair in scannable form.

    Math-identical to `Transformer._layer` for the uncached, uniform
    full-attention case; LayerScale vectors arrive as scanned-over inputs
    (they are per-layer constants at init, so they live as one stacked
    parameter on the owning Transformer instead of inside the body).

    Uncached, the carry is x and `layer` None. Cached, the carry is
    (x, the whole depth-stacked cache) and `layer` the scanned layer index.
    """

    dim: int
    seq_len: int
    causal: bool
    heads: int
    dim_head: int
    ff_mult: float
    attn_dropout: float
    ff_dropout: float
    stable: bool
    sandwich_norm: bool
    shift_tokens: bool
    text_len: int
    image_fmap_size: Optional[int]
    attn_impl: str
    sp_mesh: Any
    decode_mesh: Any
    train_mesh: Any
    decode_heads_axis: str
    decode_sparse_block: Optional[int]
    deterministic: bool
    dtype: Any

    @nn.compact
    def __call__(self, carry, attn_scale, ff_scale, pattern_idx, pattern_table,
                 layer, key_mask, rotary):
        # pattern_idx is the scanned per-layer index into the broadcast
        # table of unique [S, S] pattern masks; None = uniform full attention
        with jax.named_scope("pattern_mask"):
            pattern_mask = (
                None if pattern_table is None else pattern_table[pattern_idx]
            )
        cached = layer is not None
        x, stack = carry if cached else (carry, None)
        cache = decode_cache.layer_view(stack, layer) if cached else None
        pos = cache["attn"]["index"] if cached else None
        # per-row resume window (decode_resume injects it; absent on the
        # ordinary prefill/decode paths)
        ring_end = cache.get("ring_end") if cached else None

        def shift(h, ring):
            if not self.shift_tokens:
                return h, None
            return shift_with_ring(
                h, ring, pos, self.text_len, self.image_fmap_size,
                ring_end=ring_end,
            )

        h = nn.LayerNorm(dtype=self.dtype, name="norm_attn")(x)
        h, ring_attn = shift(h, cache.get("shift_attn") if cached else None)
        h, attn_cache = Attention(
            dim=self.dim,
            seq_len=self.seq_len,
            heads=self.heads,
            dim_head=self.dim_head,
            causal=self.causal,
            dropout=self.attn_dropout,
            stable=self.stable,
            static_mask=None,
            attn_impl=self.attn_impl,
            sp_mesh=self.sp_mesh,
            decode_mesh=self.decode_mesh,
            train_mesh=self.train_mesh,
            decode_heads_axis=self.decode_heads_axis,
            decode_sparse_block=self.decode_sparse_block,
            dtype=self.dtype,
            name="attn",
        )(h, key_mask=key_mask, rotary=rotary,
          cache=cache["attn"] if cached else None,
          deterministic=self.deterministic, mask_array=pattern_mask)
        if self.sandwich_norm:
            h = nn.LayerNorm(dtype=self.dtype, name="norm_attn_out")(h)
        x = x + h * attn_scale.astype(h.dtype)

        h = nn.LayerNorm(dtype=self.dtype, name="norm_ff")(x)
        h, ring_ff = shift(h, cache.get("shift_ff") if cached else None)
        h = FeedForward(
            dim=self.dim, mult=self.ff_mult, dropout=self.ff_dropout,
            dtype=self.dtype, name="ff",
        )(h, deterministic=self.deterministic)
        if self.sandwich_norm:
            h = nn.LayerNorm(dtype=self.dtype, name="norm_ff_out")(h)
        x = x + h * ff_scale.astype(h.dtype)

        if not cached:
            return x, None
        rings = (
            {"shift_attn": ring_attn, "shift_ff": ring_ff}
            if self.shift_tokens else {}
        )
        return (x, decode_cache.layer_store(stack, layer, attn_cache, rings)), None


class _ScanStack(nn.Module):
    """Depth-stacked `_ScanBlock` driven by `nn.scan`.

    `reverse` (the reference fork's `reverse_model`) flips the iteration —
    both directions share the same "layers" parameter collection, so a
    checkpoint is direction-agnostic exactly like the unrolled executor.
    """

    depth: int
    block_kwargs: Any  # dict of _ScanBlock constructor args (static)
    remat: bool
    remat_policy: Optional[str]

    @nn.compact
    def __call__(self, x, attn_scales, ff_scales, pattern_idx, pattern_table,
                 key_mask, rotary, cache=None, reverse: bool = False,
                 deterministic: bool = True):
        body = _ScanBlock
        if self.remat and cache is None:
            # prevent_cse=False is safe (and recommended) under scan
            body = nn.remat(
                body,
                policy=resolve_remat_policy(self.remat_policy),
                prevent_cse=False,
            )
        # attn-type cycling: each layer picks its pattern mask from the
        # broadcast table of UNIQUE masks via a scanned [depth] index;
        # None (uniform full attention) broadcasts through. The decode
        # cache (depth-stacked leaves) is CARRIED beside x, and the body is
        # told which layer it is by a scanned index (`reverse` flips it
        # with the parameters).
        idx_axis = nn.broadcast if pattern_idx is None else 0
        layer_axis = nn.broadcast if cache is None else 0
        scanned = nn.scan(
            body,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(0, 0, idx_axis, nn.broadcast, layer_axis, nn.broadcast,
                     nn.broadcast),
            length=self.depth,
            reverse=reverse,
        )
        stack = scanned(
            deterministic=deterministic, name="layers", **self.block_kwargs
        )
        if cache is None:
            x, _ = stack(
                x, attn_scales, ff_scales, pattern_idx, pattern_table, None,
                key_mask, rotary,
            )
            return x
        # the cached scan is named as a whole: what the loop itself slices
        # (parameters, the layer index) lands under it with no scope of its
        # own, and obs/scopes.py reads that as `unscoped`
        with jax.named_scope("cached_scan"):
            (x, cache), _ = stack(
                (x, cache), attn_scales, ff_scales, pattern_idx, pattern_table,
                jnp.arange(self.depth, dtype=jnp.int32), key_mask, rotary,
            )
        # decode_resume's per-row window is read by every layer and is not
        # part of the cache that comes back
        return x, {k: v for k, v in cache.items() if k != "ring_end"}


class Transformer(nn.Module):
    """Causal (or bidirectional) transformer stack with DALL-E features."""

    dim: int
    depth: int
    seq_len: int
    causal: bool = True
    heads: int = 8
    dim_head: int = 64
    ff_mult: float = 4.0
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Optional[Sequence[str]] = None
    image_fmap_size: Optional[int] = None
    sparse_attn: bool = False  # accepted for reference-parity; unused there too
    stable: bool = False
    sandwich_norm: bool = False
    shift_tokens: bool = False
    rotary_emb: bool = True
    shared_attn_ids: Optional[Sequence[int]] = None
    shared_ff_ids: Optional[Sequence[int]] = None
    reversible: bool = False
    reversible_impl: str = "remat"  # "remat" | "revnet" | "revnet_naive" (test)
    # policy name for the remat executor (`resolve_remat_policy`):
    # "flash_residuals" keeps what the flash kernels' backward reads, so a
    # layer's forward kernel runs once; "layer_residuals" the feed-forward's
    # two products beside that; "dots_with_no_batch_dims_saveable"
    # keeps matmul outputs and recomputes the elementwise work alone.
    # None or "nothing_saveable" = save nothing (full recompute); that is
    # the default HERE, and each model class owns its own (`DALLE`,
    # `CausalLM`).
    remat_policy: Optional[str] = None
    attn_impl: str = "auto"  # "dense" | "flash" | "ring" | "auto"
    sp_mesh: Any = None  # Mesh with "sp" axis for attn_impl="ring"
    decode_mesh: Any = None  # serving mesh for sharded flash decode
    train_mesh: Any = None  # trainer mesh for the sharded flash kernel
    decode_heads_axis: str = "tp"  # mesh axis the kernel splits heads over
    # decode-time policy-sparse KV tile width (None = DECODE_SPARSE_BLOCK
    # in models/attention.py); static config the serving engine clones in
    # with --decode_sparsity=policy — the bitmap itself stays traced data
    decode_sparse_block: Optional[int] = None
    # "unrolled" | "scan" — see module docstring. "scan" compiles one layer
    # body instead of `depth` copies; masked attn types run as dense with
    # depth-stacked scanned pattern masks; cached decode is native,
    # pattern masks included. No shared ids, no revnet.
    executor: str = "unrolled"
    # ---- the block's variants; the defaults are the DALL-E block
    norm: str = "layer"  # "layer" | "rms" (norm_eps; LayerNorm keeps flax's 1e-6)
    norm_eps: float = 1e-6
    ff_kind: str = "geglu"  # "geglu" | "swiglu" (width ff_dim) | "swiglu_experts" (models/moe.py)
    # the feed-forward kind of EACH layer, where they differ (leading dense
    # layers before routed ones; "relu2_experts": routed experts without a
    # gate; "none": the layer is its mixer alone); None: `ff_kind` in every layer
    ff_kinds: Optional[Sequence[str]] = None
    ff_dim: int = 0  # the dense SwiGLU's width
    use_bias: bool = True  # to_out's and the feed-forward's
    layerscale: bool = True
    kv_heads: Optional[int] = None  # K/V heads shared by groups of query heads
    qk_norm: Any = False  # RMS norm of q and k: True per head, "whole" over all columns
    # False: no norm on a sublayer's INPUT (with `sandwich_norm`, the norm on
    # its output is then the only one: h = x + norm(mixer(x)))
    prenorm: bool = True
    # "window" among attn_types: query t sees key p iff 0 <= t - p < window
    window: Optional[int] = None
    # positions a cached step may take beyond its first (a verify step of a
    # committed token and one draft: 1); a window layer's ring is that longer
    draft_positions: int = 0
    # attn type -> `ops/rotary.py:rotary_cos_sin` spec: one rotate-half table
    # per KIND of layer, on q and k, in place of the DALL-E table
    rotary_specs: Optional[Any] = None
    # swiglu_experts: router width, choices per token, the (first, count)
    # held here, their width, and the static bound on assignments made here
    experts_total: int = 0
    experts_per_token: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    expert_dim: int = 0
    moe_buffer_rows: int = 0
    moe_score: str = "softmax"  # or "sigmoid": how router outputs become scores
    routed_scale: float = 1.0  # on the renormalised weights of the chosen
    shared_dim: int = 0  # width of the shared expert beside the routed ones (0: none)
    moe_score_bias: bool = False  # a score-correction bias in the router's choice
    # (groups the router's outputs stand in, groups a token's choice is
    # limited to): the best groups by their two best scores, then the experts
    moe_groups: Tuple[int, int] = (1, 1)
    moe_renormalise: bool = True  # the chosen experts' scores, before `routed_scale`
    # > 0: the router is an MLP that wide whose state runs down the depth, from
    # each routed layer to the next (models/moe.py:RoutedExperts.mlp_router_probs)
    router_dim: int = 0
    # how a sublayer's result f meets the stream x: "sum", x + f; "affine",
    # (a x + b) + (c f + d) with four learned float32 vectors a sublayer
    residual: str = "sum"
    # "latent" among attn_types (models/attention.py:LatentAttention)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    softmax_mult: float = 1.0  # on the latent attention's 1/sqrt(nope + rope): YaRN's m^2
    # a lightning indexer beside the latent attention (`index_topk` > 0):
    # its heads, their size, and the positions a query attends
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    # "linear" among attn_types (models/attention.py:GatedDeltaAttention)
    linear_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv: int = 4
    # "ssm" among attn_types (models/attention.py:Mamba2Mixer): heads, their
    # width, the groups that share B and C, the state size, the
    # convolution's taps and the prefill's chunk
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # "cca" among attn_types (models/attention.py:ConvLatentAttention) takes
    # `heads`, `kv_heads` and `dim_head`, and no option of its own
    dtype: Any = jnp.float32
    # what the MATRICES are stored in (the new block options' only: norm
    # gains and the router stay float32, the DALL-E block keeps float32)
    param_dtype: Any = jnp.float32

    def _block_variant(self) -> Optional[str]:
        """The first block option that is not the DALL-E block's, or None."""
        defaults = dict(norm="layer", ff_kind="geglu", ff_kinds=None, use_bias=True,
                        layerscale=True, kv_heads=None, qk_norm=False, window=None,
                        rotary_specs=None, prenorm=True, residual="sum")
        return next((k for k, v in defaults.items() if getattr(self, k) != v), None)

    def _norm(self):
        if self.norm == "rms":
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype)
        assert self.norm == "layer", f"unknown norm {self.norm!r}"
        return nn.LayerNorm(dtype=self.dtype)

    def _scan_supported(self) -> Optional[str]:
        """None if the scan executor can run this config, else the reason."""
        if any(layer.carries for layer in self.plan()):
            return ("a router that carries its state from layer to layer (the scanned body "
                    "passes x and the cache, and nothing else)")
        if self._block_variant() is not None:
            return f"the block option {self._block_variant()} (one scanned body, one kind of layer)"
        if self.attn_types and any(t != "full" for t in self.attn_types):
            # masked attn types run as dense + per-layer pattern masks
            # scanned over depth; flash needs host-side masks for block
            # skipping, so it cannot take the scanned (traced) ones
            if self.attn_impl == "flash":
                return (
                    'attn_impl="flash" with masked attn_types '
                    "(scanned pattern masks are traced; use dense/auto)"
                )
        if self.shared_attn_ids or self.shared_ff_ids:
            return "cross-layer weight sharing"
        if self.reversible and self.reversible_impl != "remat":
            return "revnet reversible executor"
        if self.attn_impl == "ring" or self.sp_mesh is not None:
            # shard_map inside nn.scan is unvalidated; keep the guard with
            # the executor rather than only in training/pipeline.py.
            return "ring attention / sp mesh"
        return None

    def setup(self):
        if self.shift_tokens and self.image_fmap_size is None:
            # executor-independent invariant (shift_tokens_dalle needs the
            # image geometry); checked here so both executors fail at bind
            # time with the same clear message instead of a mid-trace
            # assert/TypeError deep in the layer body
            raise ValueError("shift_tokens=True requires image_fmap_size")
        if self.executor == "scan":
            why = self._scan_supported()
            if why is not None:
                raise ValueError(
                    f'executor="scan" does not support {why}; use the '
                    'default unrolled executor'
                )
            self._setup_scan()
            return
        assert self.executor == "unrolled", f"unknown executor {self.executor!r}"
        depth = self.depth
        plan = self.plan()
        if self.reversible and self.reversible_impl != "remat" and self.residual != "sum":
            raise ValueError("the revnet executor couples its streams by plain sums "
                             f'(residual="{self.residual}")')
        if self.reversible and any(layer.carries for layer in plan):
            raise ValueError(
                "a router that carries its state from layer to layer runs on the plain unrolled "
                "executor: the reversible ones (remat, revnet) pass x from layer to layer, and "
                "nothing else")
        shared_attn, shared_ff = {}, {}
        for ind, layer in enumerate(plan):
            if layer.attn_id not in shared_attn:
                shared_attn[layer.attn_id] = self._mixer(ind, layer)
            if layer.ff_id not in shared_ff:
                shared_ff[layer.ff_id] = self._feed_forward(layer)
        assert all(layer.kind != NO_MIXER or layer.ff_kind != "none" for layer in plan), (
            "a layer with neither a mixer nor a feed-forward")
        # a layer of ONE sublayer (a mixer or a feed-forward alone) has None in
        # the other's place, and its norm there, never called, has no gain
        self.attn_layers = [shared_attn[layer.attn_id] for layer in plan]
        self.ff_layers = [shared_ff[layer.ff_id] for layer in plan]
        assert self.prenorm or self.sandwich_norm, "a sublayer with no norm at all"
        if self.prenorm:
            self.attn_norms = [self._norm() for _ in range(depth)]
            self.ff_norms = [self._norm() for _ in range(depth)]
        if self.sandwich_norm:
            self.attn_norms_out = [self._norm() for _ in range(depth)]
            self.ff_norms_out = [self._norm() for _ in range(depth)]
        self.rotary_table = self._build_rotary_table()
        self.rotary_cs = {
            kind: rotary_cos_sin(np.arange(self.seq_len), dict(spec))
            for kind, spec in dict(self.rotary_specs or {}).items()
        }
        self.text_len = self._derived_text_len()
        assert self.residual in ("sum", "affine"), f"unknown residual {self.residual!r}"
        if self.residual == "affine":
            self.attn_res = [AffineResidual(self.dim) for _ in range(depth)]
            self.ff_res = [AffineResidual(self.dim) for _ in range(depth)]
        if not self.layerscale:
            return
        self.attn_scales = [
            self.param(
                f"attn_scale_{i}",
                lambda key, shape, i=i: jnp.full(shape, layerscale_init(i + 1)),
                (1, 1, self.dim),
            )
            for i in range(depth)
        ]
        self.ff_scales = [
            self.param(
                f"ff_scale_{i}",
                lambda key, shape, i=i: jnp.full(shape, layerscale_init(i + 1)),
                (1, 1, self.dim),
            )
            for i in range(depth)
        ]

    def plan(self) -> Tuple[LayerPlan, ...]:
        """The plan of the stack, a `LayerPlan` a layer: which mixer, which
        cache kind and which cached path each layer takes, its rotary table,
        its feed-forward. THE one derivation: `setup`, `init_cache`, the
        pattern table, models/lm.py and serving/sparsity.py read it. Pure
        config math: usable unbound, as `init_cache` is."""
        return _stack_plan(
            self.depth, tuple(self.attn_types or ("full",)), tuple(self.shared_attn_ids or ()),
            tuple(self.shared_ff_ids or ()), tuple(self.ff_kinds or (self.ff_kind,) * self.depth),
            self.heads, self.kv_heads, self.qk_norm, self.window,
            frozenset(dict(self.rotary_specs or {})), self.index_topk, self.router_dim)

    def _mixer(self, ind: int, layer: LayerPlan):
        """The mixer the plan says layer `ind` is built as (None: it has none)."""
        name = f"attn_{layer.attn_id}"
        if layer.path == NO_MIXER:
            return None
        if layer.path == SSM:
            return Mamba2Mixer(
                dim=self.dim, seq_len=self.seq_len, heads=self.ssm_heads,
                head_dim=self.ssm_head_dim, groups=self.ssm_groups, state_dim=self.ssm_state,
                conv_width=self.ssm_conv, chunk=self.ssm_chunk, norm_eps=self.norm_eps,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )
        if layer.path == CCA:
            return ConvLatentAttention(
                dim=self.dim, seq_len=self.seq_len, heads=self.heads, kv_heads=self.kv_heads,
                dim_head=self.dim_head, norm_eps=self.norm_eps, attn_impl=self.attn_impl,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )
        if layer.path == LATENT:
            return LatentAttention(
                dim=self.dim, seq_len=self.seq_len, heads=self.heads,
                q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
                qk_nope_dim=self.qk_nope_dim, qk_rope_dim=self.qk_rope_dim,
                v_dim=self.v_dim, norm_eps=self.norm_eps, attn_impl=self.attn_impl,
                softmax_mult=self.softmax_mult, index_heads=self.index_heads,
                index_dim=self.index_dim, index_topk=self.index_topk,
                dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )
        if layer.path == LINEAR:
            return GatedDeltaAttention(
                dim=self.dim, seq_len=self.seq_len, heads=self.linear_heads,
                key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
                conv_width=self.linear_conv, norm_eps=self.norm_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name,
            )
        # beyond the DALL-E block's arguments: nothing, for that block
        variant = {} if self._block_variant() is None else dict(
            kv_heads=self.kv_heads, qk_norm=self.qk_norm, norm_eps=self.norm_eps,
            use_bias=self.use_bias, param_dtype=self.param_dtype,
            window=self.window if layer.kind == "window" else None,
            step_positions=self.draft_positions + 1,
        )
        return Attention(
            dim=self.dim, seq_len=self.seq_len, heads=self.heads, dim_head=self.dim_head,
            causal=self.causal, dropout=self.attn_dropout, stable=self.stable,
            static_mask=None if layer.kind == "window" else _build_static_mask(
                layer.kind, self.seq_len, self.image_fmap_size, ind),
            attn_impl=self.attn_impl, sp_mesh=self.sp_mesh, decode_mesh=self.decode_mesh,
            train_mesh=self.train_mesh, decode_heads_axis=self.decode_heads_axis,
            decode_sparse_block=self.decode_sparse_block, dtype=self.dtype,
            path=layer.path, name=name, **variant,
        )

    def _feed_forward(self, layer: LayerPlan):
        name = f"ff_{layer.ff_id}"
        if layer.ff_kind == "none":
            return None
        if layer.ff_kind in ROUTED_KINDS:
            return RoutedExperts(
                act=ROUTED_KINDS[layer.ff_kind],
                dim=self.dim, expert_dim=self.expert_dim, experts_total=self.experts_total,
                experts_per_token=self.experts_per_token, experts_held=tuple(self.experts_held),
                buffer_rows=self.moe_buffer_rows, score=self.moe_score,
                routed_scale=self.routed_scale, shared_dim=self.shared_dim,
                score_bias=self.moe_score_bias, groups=tuple(self.moe_groups),
                renormalise=self.moe_renormalise, router_dim=self.router_dim,
                norm_eps=self.norm_eps, dtype=self.dtype, param_dtype=self.param_dtype, name=name,
            )
        if layer.ff_kind == "swiglu":
            return SwiGLU(dim=self.dim, hidden=self.ff_dim, dtype=self.dtype,
                          param_dtype=self.param_dtype, name=name)
        assert layer.ff_kind == "geglu", f"unknown ff_kind {layer.ff_kind!r}"
        assert self.use_bias, "the GEGLU feed-forward keeps its biases"
        return FeedForward(dim=self.dim, mult=self.ff_mult, dropout=self.ff_dropout,
                           dtype=self.dtype, name=name)

    def _derived_text_len(self) -> int:
        return (
            self.seq_len - self.image_fmap_size**2 + 1
            if self.image_fmap_size is not None
            else self.seq_len
        )

    def _build_rotary_table(self):
        if not self.rotary_emb or self.rotary_specs:
            return None
        assert self.image_fmap_size is not None
        return build_dalle_rotary(
            self.seq_len - self.image_fmap_size**2 + 1,
            self.image_fmap_size,
            self.dim_head,
        )

    def _setup_scan(self):
        """Scan-executor setup: one stacked parameter collection."""
        depth, dim = self.depth, self.dim
        self.rotary_table = self._build_rotary_table()
        self.text_len = self._derived_text_len()

        # attn-type cycling: per-layer pattern masks served from a table of
        # UNIQUE masks plus a scanned per-layer index — cycling repeats the
        # same few [S, S] patterns (sparse is per-layer-seeded, so it stays
        # per-layer), and a depth-stacked copy of each would cost
        # depth/n_types more device memory for no information. Builders may
        # return [S+1, S+1] or block-padded sizes; crop uniformly to [S, S].
        self.scan_pattern_table, self.scan_pattern_idx = (
            self._build_pattern_table()
        )

        def stacked_scale_init(key, shape):
            del key  # deterministic depth-dependent init (layerscale_init)
            return jnp.stack(
                [jnp.full(shape[1:], layerscale_init(i + 1)) for i in range(shape[0])]
            )

        self.attn_scales_stacked = self.param(
            "attn_scale_stack", stacked_scale_init, (depth, 1, 1, dim)
        )
        self.ff_scales_stacked = self.param(
            "ff_scale_stack", stacked_scale_init, (depth, 1, 1, dim)
        )
        self.scan_stack = _ScanStack(
            depth=depth,
            remat=self.reversible,
            remat_policy=self.remat_policy,
            block_kwargs=self._scan_block_kwargs(),
        )

    def _build_pattern_table(self):
        """(unique-mask table [K, S, S], per-layer index [depth]) for the
        attn-type cycle, or (None, None) for uniform full attention.
        Pure config math (usable unbound — the pipeline executor rebuilds
        it outside this module's scope)."""
        type_per_layer = [layer.kind for layer in self.plan()]
        if not any(t != "full" for t in type_per_layer):
            return None, None
        S = self.seq_len
        table, index_of, idx = [], {}, []
        for ind, t in enumerate(type_per_layer):
            m = _build_static_mask(t, S, self.image_fmap_size, ind)
            if m is None:
                m = np.ones((S, S), dtype=bool)
            else:
                m = np.asarray(m)[:S, :S]
            key = m.tobytes()
            if key not in index_of:
                index_of[key] = len(table)
                table.append(m)
            idx.append(index_of[key])
        return (
            jnp.asarray(np.stack(table)),
            jnp.asarray(np.array(idx, np.int32)),
        )

    def _scan_block_kwargs(self) -> dict:
        """_ScanBlock constructor args for this config — pure config math,
        shared by the scan executor and `pipeline_trunk_apply` so the two
        can never drift."""
        return dict(
            dim=self.dim,
            seq_len=self.seq_len,
            causal=self.causal,
            heads=self.heads,
            dim_head=self.dim_head,
            ff_mult=self.ff_mult,
            attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            stable=self.stable,
            sandwich_norm=self.sandwich_norm,
            shift_tokens=self.shift_tokens,
            text_len=self._derived_text_len(),
            image_fmap_size=self.image_fmap_size,
            attn_impl=self.attn_impl,
            sp_mesh=self.sp_mesh,
            decode_mesh=self.decode_mesh,
            train_mesh=self.train_mesh,
            decode_heads_axis=self.decode_heads_axis,
            decode_sparse_block=self.decode_sparse_block,
            dtype=self.dtype,
        )

    def _shift(self, h: jnp.ndarray, ring, pos, ring_end=None):
        """Token-shift h; in cached mode also maintain the ring buffer
        (see `shift_with_ring` — shared with the scan executor)."""
        assert self.image_fmap_size is not None
        return shift_with_ring(
            h, ring, pos, self.text_len, self.image_fmap_size,
            ring_end=ring_end,
        )

    def _half_attn(self, i, x, key_mask, layer_cache, deterministic=True, start=False):
        """Attention half-block f (norm → shift → attn → [sandwich] → scale),
        the composition the reference wraps as `f` in `ReversibleBlock`
        (`reversible.py:57-63`, built at `transformer.py:291-294`).
        Returns (residual_branch, new_attn_cache, new_shift_ring)."""
        cached = layer_cache is not None
        pos = layer_cache["attn"]["index"] if cached else None
        h = self.attn_norms[i](x) if self.prenorm else x
        ring = None
        if self.shift_tokens:
            h, ring = self._shift(
                h, layer_cache.get("shift_attn") if cached else None, pos,
                ring_end=layer_cache.get("ring_end") if cached else None,
            )
        layer = self.plan()[i]
        variant = {"rotary_cs": self.rotary_cs[layer.rotary]} if layer.rotary else {}
        if start and layer.takes_start:
            variant["start"] = True  # a linear layer takes any longer chunk for a start
        h, attn_cache = self.attn_layers[i](
            h,
            key_mask=key_mask,
            rotary=self.rotary_table,
            cache=layer_cache["attn"] if cached else None,
            deterministic=deterministic,
            **variant,
        )
        if self.sandwich_norm:
            h = self.attn_norms_out[i](h)
        if not self.layerscale:
            return h, attn_cache, ring
        return h * self.attn_scales[i].astype(h.dtype), attn_cache, ring

    def _half_ff(self, i, x, layer_cache, pos, deterministic=True, carried=None):
        """Feed-forward half-block g (norm → shift → ff → [sandwich] → scale).
        `pos` is the pre-update decode position (for the streaming shift);
        `carried` what the routed layer before handed on, where the plan says
        this layer's router carries anything. Returns (residual_branch,
        new_shift_ring, what this layer hands on: `carried` where it carries
        nothing)."""
        cached = layer_cache is not None
        h = self.ff_norms[i](x) if self.prenorm else x
        ring = None
        if self.shift_tokens:
            h, ring = self._shift(
                h, layer_cache.get("shift_ff") if cached else None, pos,
                ring_end=layer_cache.get("ring_end") if cached else None,
            )
        if self.plan()[i].carries:
            h, carried = self.ff_layers[i](h, deterministic=deterministic, carried=carried)
        else:
            h = self.ff_layers[i](h, deterministic=deterministic)
        if self.sandwich_norm:
            h = self.ff_norms_out[i](h)
        if not self.layerscale:
            return h, ring, carried
        return h * self.ff_scales[i].astype(h.dtype), ring, carried

    def route_choices(self, x: jnp.ndarray, layer: int = 0) -> jnp.ndarray:
        """[B, N, k]: the experts layer `layer`'s router chooses for each
        token of x [B, N, dim] (the trunk's input), through the layers
        before it and its own attention half: what the routed layer itself
        would choose, read outside any train step."""
        assert self.plan()[layer].ff_kind in ROUTED_KINDS, "only a routed layer chooses"
        carried = None
        for i in range(layer):
            x, _, carried = self._layer(i, x, None, None, True, carried=carried)
        if self.plan()[layer].kind != NO_MIXER:
            x = self._residual("attn", layer, x, self._half_attn(layer, x, None, None, True)[0])
        return self.ff_layers[layer].choices(self.ff_norms[layer](x), carried)

    def _rev_f(self, x: jnp.ndarray, i: int, deterministic: bool = True):
        return self._half_attn(i, x, None, None, deterministic)[0]

    def _rev_g(self, x: jnp.ndarray, i: int, deterministic: bool = True):
        return self._half_ff(i, x, None, None, deterministic)[0]

    def _revnet(self, x: jnp.ndarray, order: Tuple[int, ...]):
        """True reversible executor (`reversible.py:57-127` semantics).

        Forward runs the (f, g) couplings; the custom backward reconstructs
        activations block-by-block from the outputs, so nothing between
        layer boundaries is kept live — the JAX analogue of
        `_ReversibleFunction.backward` (`reversible.py:121-127`).
        """

        def fn(mdl, x1, x2):
            for i in order:
                x1 = x1 + mdl._rev_f(x2, i)
                x2 = x2 + mdl._rev_g(x1, i)
            return x1, x2

        def fwd(mdl, x1, x2):
            y1, y2 = fn(mdl, x1, x2)
            variables = {"params": mdl.variables["params"]}
            return (y1, y2), (y1, y2, variables)

        mdl_def = self.clone(parent=None)

        def bwd(residuals, tangents):
            y1, y2, variables = residuals
            dy1, dy2 = tangents

            def f_pure(v, h, i):
                return mdl_def.apply(v, h, i, method=Transformer._rev_f)

            def g_pure(v, h, i):
                return mdl_def.apply(v, h, i, method=Transformer._rev_g)

            params_t = jax.tree_util.tree_map(jnp.zeros_like, variables)
            for i in reversed(order):
                g_out, g_vjp = jax.vjp(lambda v, h: g_pure(v, h, i), variables, y1)
                x2 = y2 - g_out
                dv_g, dy1_add = g_vjp(dy2)
                dy1 = dy1 + dy1_add
                f_out, f_vjp = jax.vjp(lambda v, h: f_pure(v, h, i), variables, x2)
                x1 = y1 - f_out
                dv_f, dx2_add = f_vjp(dy1)
                dy2 = dy2 + dx2_add
                params_t = jax.tree_util.tree_map(
                    lambda a, b, c: a + b + c, params_t, dv_g, dv_f
                )
                y1, y2 = x1, x2
            return (params_t, dy1, dy2)

        if self.reversible_impl == "revnet_naive":
            # autodiff-through-forward variant: same function, plain VJP.
            # Exists so tests can check the custom backward against autodiff.
            y1, y2 = fn(self, x, x)
        else:
            rev = nn.custom_vjp(fn, forward_fn=fwd, backward_fn=bwd)
            y1, y2 = rev(self, x, x)
        # channel-duplication mean-out (`reversible.py:158,165`)
        return (y1 + y2) / 2

    def _residual(self, half: str, i: int, x, h):
        """The stream after the result h of layer i's `half` (`attn` or `ff`):
        x + h, or with `residual="affine"` (a x + b) + (c h + d) by that
        sublayer's four vectors (`AffineResidual`)."""
        if self.residual == "sum":
            return x + h
        return (self.attn_res if half == "attn" else self.ff_res)[i](x, h)

    def _layer(
        self,
        i: int,
        x: jnp.ndarray,
        key_mask,
        layer_cache,
        deterministic: bool,
        start: bool = False,
        carried=None,
    ):
        """One (attn, ff) residual pair, or the one sublayer of a layer that is
        a mixer or a feed-forward alone (one norm, one residual); returns (x,
        updated layer cache: None of a layer that holds nothing, what the
        layer's router hands to the next routed layer's: `carried`, what the
        one before handed to it, where the plan says it carries nothing)."""
        cached = layer_cache is not None
        pos = layer_cache["attn"]["index"] if cached else None
        layer = self.plan()[i]

        attn_cache = ring_attn = ring_ff = None
        if layer.kind != NO_MIXER:
            h, attn_cache, ring_attn = self._half_attn(
                i, x, key_mask, layer_cache, deterministic, start
            )
            x = self._residual("attn", i, x, h)
        if layer.ff_kind != "none":
            h, ring_ff, carried = self._half_ff(i, x, layer_cache, pos, deterministic, carried)
            x = self._residual("ff", i, x, h)

        if not cached:
            return x, None, carried
        new_cache = {"attn": attn_cache}
        if self.shift_tokens:
            new_cache["shift_attn"] = ring_attn
            new_cache["shift_ff"] = ring_ff
        return x, new_cache, carried

    def __call__(
        self,
        x: jnp.ndarray,
        key_mask: Optional[jnp.ndarray] = None,
        reverse_model: bool = False,
        cache: Optional[dict] = None,
        deterministic: bool = True,
        start: bool = False,
    ):
        """`start`: the cached chunk starts every row's sequence (a prefill
        into rows that hold nothing yet; `Attention.__call__`), on the plain
        unrolled executor."""
        assert not start or (cache is not None and self.executor != "scan" and not (
            self.reversible and self.reversible_impl != "remat")), "start: a cached unrolled pass"
        if self.executor == "scan":
            return self.scan_stack(
                x,
                self.attn_scales_stacked,
                self.ff_scales_stacked,
                self.scan_pattern_idx,
                self.scan_pattern_table,
                key_mask,
                self.rotary_table,
                cache=cache,
                reverse=reverse_model,
                deterministic=deterministic,
            )
        order = range(self.depth - 1, -1, -1) if reverse_model else range(self.depth)
        if self.reversible and self.reversible_impl != "remat":
            if cache is not None:
                # cached decode of the SAME two-stream function the revnet
                # trains: (x1, x2) streams advance through cached halves.
                x1 = x2 = x
                new_cache = {}
                for i in order:
                    lc = cache[decode_cache.layer_key(i)]
                    pos = lc["attn"]["index"]
                    h, attn_cache, ring_a = self._half_attn(
                        i, x2, key_mask, lc, deterministic
                    )
                    x1 = x1 + h
                    h, ring_f, _ = self._half_ff(i, x1, lc, pos, deterministic)
                    x2 = x2 + h
                    layer_new = {"attn": attn_cache}
                    if self.shift_tokens:
                        layer_new["shift_attn"] = ring_a
                        layer_new["shift_ff"] = ring_f
                    new_cache[decode_cache.layer_key(i)] = layer_new
                return (x1 + x2) / 2, new_cache
            assert key_mask is None, "revnet executor has no key-mask path"
            assert deterministic or (self.attn_dropout == 0 and self.ff_dropout == 0), (
                "revnet executor requires deterministic execution (no dropout); "
                "use reversible_impl='remat' for dropout training"
            )
            return self._revnet(x, tuple(order))
        new_cache = {} if cache is not None else None
        carried = None  # what a routed layer's router hands to the next one's
        for i in order:
            if self.reversible and cache is None:
                # activation rematerialization: recompute the layer in the
                # backward pass instead of saving activations — the memory
                # behavior the reference's ReversibleSequence buys
                # (`reversible.py:57-127`), via flax's lifted remat.
                def layer_fn(mdl, y, i=i):
                    return mdl._layer(i, y, key_mask, None, deterministic)[0]

                x = nn.remat(
                    layer_fn, policy=resolve_remat_policy(self.remat_policy)
                )(self, x)
            else:
                x, layer_cache, carried = self._layer(
                    i, x, key_mask,
                    cache.get(decode_cache.layer_key(i)) if cache else None,
                    deterministic, start, carried,
                )
                if layer_cache:
                    new_cache[decode_cache.layer_key(i)] = layer_cache
        if cache is not None:
            return x, new_cache
        return x

    @property
    def cache_layout(self) -> str:
        """The decode-cache layout this executor takes: the one place that
        says so (`models/decode_cache.py` owns what each layout IS)."""
        return (
            decode_cache.STACKED if self.executor == "scan" else decode_cache.PER_LAYER
        )

    def init_cache(
        self, batch: int, max_len: int, dtype=jnp.float32, *,
        per_row: bool = False, pages: Optional[tuple] = None, kv_dtype=None,
    ) -> dict:
        """Zeroed decode cache for this trunk's geometry (K/V + token-shift
        rings), in the layout its executor takes. Pure config math: usable
        unbound. `per_row`, `pages = (n_pages, page_size)` and `kv_dtype`
        as `decode_cache.layer_spec` reads them. Each layer takes the kind
        the plan gives it: latent, recurrent (linear attention, or a state-space
        mixer: its leaves sized from that mixer), a window's ring (of `window +
        draft_positions` slots), K/V heads, or nothing (a layer without a
        mixer); recurrent, window and K/V layers may share a cache, and a stack
        with a layer on the ROWS path keeps every row at its own index."""
        plan = self.plan()
        kinds = [layer.cache_kind for layer in plan]
        recurrent = {layer.kind for layer in plan if layer.cache_kind == "recurrent"}
        assert len(recurrent) <= 1, "linear and state-space layers in one stack are not built"
        state = dict(linear_heads=self.linear_heads, key_dim=self.linear_key_dim,
                     value_dim=self.linear_value_dim, conv_taps=self.linear_conv)
        if recurrent == {SSM}:  # a head's state [state size, head width], a ring of x | B | C
            state = dict(linear_heads=self.ssm_heads, key_dim=self.ssm_state,
                         value_dim=self.ssm_head_dim, conv_taps=self.ssm_conv,
                         conv_dim=self.ssm_heads * self.ssm_head_dim
                         + 2 * self.ssm_groups * self.ssm_state)
        if "latent" in kinds and set(kinds) != {"latent"}:
            raise NotImplementedError(
                "latent layers beside K/V or recurrent ones in one cache are not built "
                "(recurrent and K/V layers are)")
        if any(layer.path == ROWS and layer.cache_kind == "heads" and layer.kind != "full"
               for layer in plan):
            raise NotImplementedError(
                "a patterned layer over shared K/V heads or under a rotate-half rotary "
                "has no cached path (full and window layers do)")
        # K/V lanes alone, at the caller's index: what the DALL-E ladder
        # pages, quantises and keeps its token-shift rings beside
        plain = not plan[0].per_row and set(kinds) == {"heads"}
        assert plain or (pages is None and kv_dtype is None)
        return decode_cache.make(
            self.cache_layout, self.depth, kinds=kinds, batch=batch, max_len=max_len,
            dtype=dtype, per_row=per_row or plan[0].per_row, pages=pages, kv_dtype=kv_dtype,
            heads=self.kv_heads or self.heads, dim_head=self.dim_head, dim=self.dim,
            image_fmap_size=self.image_fmap_size, shift_tokens=self.shift_tokens and plain,
            ring=(self.window or 0) + self.draft_positions,
            latent_dim=self.kv_lora_rank, rope_dim=self.qk_rope_dim,
            index_dim=self.index_dim if any(layer.selects for layer in plan) else None,
            tail_dim=cca_tail_dim(self.heads, self.kv_heads or self.heads, self.dim_head),
            **state,
        )


def scan_params_to_unrolled(tparams: dict, depth: int) -> dict:
    """Convert a scan-executor Transformer param subtree to the unrolled
    layout (e.g. to run the cached decode path on a scan-trained model).

    `tparams` is the subtree under ".../transformer" of a scan-executor
    model; returns the equivalent unrolled-executor subtree.
    """
    layers = tparams["scan_stack"]["layers"]

    def slice_i(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    out = {}
    for i in range(depth):
        out[f"attn_{i}"] = slice_i(layers["attn"], i)
        out[f"ff_{i}"] = slice_i(layers["ff"], i)
        out[f"attn_norms_{i}"] = slice_i(layers["norm_attn"], i)
        out[f"ff_norms_{i}"] = slice_i(layers["norm_ff"], i)
        if "norm_attn_out" in layers:
            out[f"attn_norms_out_{i}"] = slice_i(layers["norm_attn_out"], i)
            out[f"ff_norms_out_{i}"] = slice_i(layers["norm_ff_out"], i)
        out[f"attn_scale_{i}"] = tparams["attn_scale_stack"][i]
        out[f"ff_scale_{i}"] = tparams["ff_scale_stack"][i]
    return out


def unrolled_params_to_scan(tparams: dict, depth: int) -> dict:
    """Inverse of `scan_params_to_unrolled` (uniform-stack configs only)."""

    def stack(fmt):
        trees = [tparams[fmt.format(i)] for i in range(depth)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)

    layers = {
        "attn": stack("attn_{}"),
        "ff": stack("ff_{}"),
        "norm_attn": stack("attn_norms_{}"),
        "norm_ff": stack("ff_norms_{}"),
    }
    if "attn_norms_out_0" in tparams:
        layers["norm_attn_out"] = stack("attn_norms_out_{}")
        layers["norm_ff_out"] = stack("ff_norms_out_{}")
    return {
        "scan_stack": {"layers": layers},
        "attn_scale_stack": stack("attn_scale_{}"),
        "ff_scale_stack": stack("ff_scale_{}"),
    }


def make_pipeline_trunk(transformer: "Transformer", mesh, n_micro: int):
    """Build `fn(tparams, x, key_mask=None)` running this Transformer
    config's trunk pipeline-parallel over a 'pp' mesh
    (parallel/gpipe.py GPipe schedule).

    `tparams` is the Transformer's own parameter tree in the scan layout
    ([depth, ...] leaves — the trained/checkpointed layout; convert
    unrolled checkpoints with `unrolled_params_to_scan`). Numerically
    equal to `transformer.apply` for the uncached deterministic case —
    including the attn-type cycle (per-layer pattern-mask indices ride
    with each stage's layer slice). Restrictions mirror the scan
    executor's (`_scan_supported`) plus: no reverse pass, no dropout
    (deterministic inference/eval or an externally rematerialized
    training forward).

    The block module is constructed HERE, at make time — flax intercepts
    module construction inside a parent module's scope, so building the
    returned closure outside any `apply` lets it serve as a
    `DALLE(..., trunk_fn=...)` override inside the model's own apply.

    The reference has no pipeline parallelism to cite; this is the
    TPU-native depth-scaling axis on top of its reversibility story
    (`/root/reference/dalle_pytorch/reversible.py`).
    """
    from dalle_pytorch_tpu.parallel.gpipe import gpipe_apply

    assert transformer.executor == "scan", "pipeline runs the scan layout"
    reason = transformer._scan_supported()
    assert reason is None, f"unsupported config for pipelining: {reason}"

    block = _ScanBlock(
        deterministic=True, **transformer._scan_block_kwargs()
    )
    rotary = transformer._build_rotary_table()
    # attn-type cycling: the per-layer index into the unique-mask table is
    # depth-leading, so it rides WITH each stage's layer slice; the small
    # table itself is closed over (replicated), same as the scan executor
    pattern_table, pattern_idx = transformer._build_pattern_table()

    def run(tparams: dict, x: jnp.ndarray,
            key_mask: Optional[jnp.ndarray] = None):
        pp_params = {
            "block": tparams["scan_stack"]["layers"],
            "s_attn": tparams["attn_scale_stack"],
            "s_ff": tparams["ff_scale_stack"],
        }
        if pattern_idx is not None:
            pp_params["pidx"] = pattern_idx

        def call_block(lp, h, km):
            pidx = lp["pidx"] if pattern_idx is not None else None
            y, _ = block.apply(
                {"params": lp["block"]}, h, lp["s_attn"], lp["s_ff"],
                pidx, pattern_table, None, km, rotary,
            )
            return y

        if transformer.reversible:
            # honor the config's activation-memory setting: per-layer
            # rematerialization (same policy the scan executor wraps via
            # nn.remat) — values unchanged, backward recomputes
            call_block = jax.checkpoint(
                call_block,
                policy=resolve_remat_policy(transformer.remat_policy),
                prevent_cse=False,
            )

        if key_mask is None:
            return gpipe_apply(
                mesh, pp_params, lambda lp, h: call_block(lp, h, None),
                x, n_micro,
            )

        # key_mask is per-example, so it must ride the microbatch
        # schedule (each stage masks the microbatch it is processing)
        return gpipe_apply(
            mesh, pp_params, call_block, x, n_micro, aux=key_mask
        )

    return run


def pipeline_trunk_apply(
    transformer: "Transformer",
    tparams: dict,
    mesh,
    x: jnp.ndarray,
    n_micro: int,
    key_mask: Optional[jnp.ndarray] = None,
):
    """One-shot convenience over `make_pipeline_trunk` (standalone use,
    outside any flax module scope)."""
    return make_pipeline_trunk(transformer, mesh, n_micro)(
        tparams, x, key_mask
    )
