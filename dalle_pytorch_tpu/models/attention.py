"""Attention module: one dense MXU-friendly kernel, many static mask patterns.

The reference has four attention classes
(`/root/reference/dalle_pytorch/attention.py:39,103,225,339`): full causal,
conv-like sparse (unfold), axial row/col sparse, and a DeepSpeed CUDA
block-sparse wrapper. On TPU, every one of these is expressed as *dense
attention with a static boolean mask* (see ops/masks.py) — a single fused
einsum chain that XLA tiles onto the MXU; masking is a free epilogue. This
is both simpler and faster than gather-based sparsity at DALL-E sequence
lengths (<= a few thousand tokens); the Pallas flash kernel
(ops/pallas_attention.py) takes over for long sequences — O(N) memory,
static-mask block skipping — selected via `attn_impl` ("auto" switches at
AUTO_FLASH_MIN_SEQ).

Semantics preserved from the reference:
  * rotary embeddings are applied to q, k AND v (`attention.py:67`);
  * optional stable softmax (`attention.py:27-30`);
  * key-padding mask [B, N] (True = valid key);
  * causal mask composed with the per-layer static pattern mask.

The decode-time KV cache is a fixed-shape pytree {k, v, index} with k/v of
shape [B, heads, max_len, dim_head]; causality during cached decode is
enforced by masking positions > index (the reference instead relies on only
having written the prefix, `attention.py:71-76,86`). The cached path has its
own kernel dispatch (`_use_flash_decode`): the Pallas flash-decode kernel
(ops/pallas_decode.py) reads only each row's live KV blocks — per-row
`index` included, the continuous-batching slot cache — with dense attention
over the whole cache as the fallback for pattern masks and small caches.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
import jax.lax as lax
import flax.linen as nn

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.ops.attention_core import dense_attention
from dalle_pytorch_tpu.ops.delta_step import delta_step
from dalle_pytorch_tpu.ops.grouped_decode import grouped_decode_attention
from dalle_pytorch_tpu.ops.index_score import index_scores
from dalle_pytorch_tpu.ops.index_select import selected_indices, selected_mask
from dalle_pytorch_tpu.ops.latent_decode import latent_decode_attention
from dalle_pytorch_tpu.ops.ssm_step import ssm_step, ssm_step_operands
from dalle_pytorch_tpu.ops.sparse_latent_decode import (
    sparse_latent_decode_attention,
    split_rows,
)
from dalle_pytorch_tpu.ops.pallas_attention import (
    TOKEN_MAJOR,
    flash_attention,
    heads_per_block,
)
from dalle_pytorch_tpu.ops.pallas_decode import (
    block_sparse_flash_decode_attention,
    flash_decode_attention,
    paged_decode_attention,
    paged_gather,
    sharded_flash_decode_attention,
    sharded_paged_decode_attention,
)
from dalle_pytorch_tpu.ops.pallas_rotary import rotary_split
from dalle_pytorch_tpu.ops.rotary import apply_rotary, apply_rotary_half

# Of the three thresholds below one has a chip reading behind it, at one
# length; the other two were set from a roofline model of CPU-compiled
# programs (scripts since deleted) and are NOT measured: no cell decodes
# through the flash-decode kernel yet (PERF.md section 7, cells 0, 2, 5).

# Sequence length at or above which `attn_impl="auto"` switches from the
# dense einsum to the Pallas flash kernel (O(N) memory vs dense's O(N^2)
# score tensors). Measured at 1280 only (one v5e, `flagship.train`;
# ledger, PR 26, and PERF.md section 6): flash 33,854 tokens/s, dense
# 30,719. Lengths between 256 and 1280 are not measured; 1024 is the
# largest power of two that still selects flash for the flagship.
# Overridable per model (attn_impl=) or by rebinding this constant.
AUTO_FLASH_MIN_SEQ = 1024

# Cache length at or above which `attn_impl="auto"` runs the CACHED decode
# path through the Pallas flash-decode kernel (ops/pallas_decode.py) instead
# of dense attention over the whole [B, H, max_len, D] cache. Not measured:
# the argument is that the kernel reads only each row's live K/V blocks
# (half the cache on average, a third for a freshly admitted slot still at
# its text prefix) against a per-call charge that wins below some length.
AUTO_FLASH_DECODE_MIN_LEN = 512

# KV tile width for POLICY-sparse flash decode (the per-row block bitmap in
# ops/pallas_decode.py:block_sparse_flash_decode_attention). Not measured.
# The trade: the skip fraction a policy can express falls with tile width
# (every tile a single live position touches is read whole: an axial-row
# policy at the flagship cache keeps 48% of 64-wide tiles live, 60% of
# 128-wide, 79% of 256-wide; arithmetic on the masks), while the per-tile
# grid charge grows as tiles shrink. 128 matches
# `flash_decode_attention`'s default block_k, so the all-ones bitmap keeps
# BIT-IDENTITY with the dense-causal flash path (same tile boundaries, same
# accumulation order), the serving stack's parity pin. Overridable per model
# (decode_sparse_block=); must divide into whole pages on the paged "kernel"
# impl (page_size | block).
DECODE_SPARSE_BLOCK = 128


# The mixer a layer is built as and the path its cached call takes, which
# `Transformer.plan` decides from the trunk's options and a module is TOLD
# (`Attention.path`): by what it is built with, never by what it is called with.
#   DALLE   the fused 3 x inner projection, the DALL-E rotary on q, k AND v; cached
#           over K/V lanes or pages, a scalar or per-row index, int8, a block bitmap
#   LANES   the grouped projection (a q/k norm, a K/V head a query head) over those
#           same lanes (`_cached_lanes`)
#   ROWS    the grouped projection over per-row K/V or a window's ring
#           (`_cached_grouped`): K/V heads shared, a window, or a rotate-half rotary
#   LATENT, LINEAR, SSM   `LatentAttention`, `GatedDeltaAttention`, `Mamba2Mixer`
#   CCA     `ConvLatentAttention`: grouped K/V per row as on ROWS, a tail beside them
DALLE, LANES, ROWS, LATENT, LINEAR = "dalle", "grouped_lanes", "grouped_rows", "latent", "linear"
SSM, CCA = "ssm", "cca"
ATTN_IMPLS = ("auto", "dense", "flash", "ring")


def attention_path(heads, kv_heads, qk_norm, window, rotated: bool = False) -> str:
    """Which of DALLE, LANES and ROWS an `Attention` of these options is on
    (`rotated`: its layers are handed a rotate-half table)."""
    if (kv_heads or heads) != heads or window is not None or rotated:
        return ROWS
    return LANES if kv_heads is not None or qk_norm else DALLE


def _known_impl(attn_impl: str):
    if attn_impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attn_impl {attn_impl!r}: one of {ATTN_IMPLS}")


def _kv_quantize(x: jnp.ndarray):
    """Symmetric int8 quantization over the head dim: x [B,H,n,D] ->
    (q int8 [B,H,n,D], scale fp32 [B,H,n]).

    fp32 math end to end (quantization error must not depend on the
    cache dtype), eps-clipped so an all-zero row round-trips to zeros
    instead of NaN. Dequant is `q.astype(f32) * scale[..., None]` —
    done INSIDE the decode kernels (ops/pallas_decode.py) so the HBM
    read stays 1 byte/element."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale[..., None]


class Attention(nn.Module):
    """Multi-head (optionally causal) attention with a static pattern mask."""

    dim: int
    seq_len: int
    heads: int = 8
    dim_head: int = 64
    causal: bool = True
    dropout: float = 0.0
    stable: bool = False
    static_mask: Optional[np.ndarray] = None  # [S, S] bool, True = attend
    attn_impl: str = "auto"  # one of ATTN_IMPLS; "flash" is the in-repo Pallas kernel
    sp_mesh: Any = None  # Mesh with an "sp" axis, required for attn_impl="ring"
    # serving mesh for the SHARDED flash-decode dispatch: a Pallas call is
    # a single-device program GSPMD cannot partition, so when the sharded
    # continuous engine sets this the cached flash path runs
    # ops/pallas_decode.py:sharded_flash_decode_attention (shard_map over
    # `decode_heads_axis`, heads split — bit-identical to unsharded).
    # The axis must match the one the engine's KV-cache shardings use
    # (ShardedContinuousEngine clones the model with its model_axis).
    decode_mesh: Any = None
    decode_heads_axis: str = "tp"
    # trainer mesh for the UNCACHED flash kernel, for the same reason: under
    # a multi-device pjit the bare pallas_call is refused ("Mosaic kernels
    # cannot be automatically partitioned"), so the trainer's mesh rides
    # here and the kernel runs under shard_map — batch over (dp, fsdp),
    # heads over tp, each shard the unmodified single-device kernel.
    train_mesh: Any = None
    # KV tile width the decode-time block bitmap is expressed at (None =
    # DECODE_SPARSE_BLOCK). Static model config: the serving engine clones
    # the model with it when --decode_sparsity=policy, and the policy's
    # host-side bitmap derivation must use the SAME width (the bitmap
    # itself stays traced data — only this boundary is baked into the
    # compiled program).
    decode_sparse_block: Optional[int] = None
    # K/V heads, each shared by `heads // kv_heads` query heads (None: one
    # per query head, the DALL-E layout and its fused 3 x inner projection)
    kv_heads: Optional[int] = None
    # RMS norm of q and k before the rotation: True, per head over dim_head;
    # "whole", one gain over all of a projection's columns, before the heads
    # are split
    qk_norm: Any = False
    norm_eps: float = 1e-6
    # sliding window: query t sees key p iff 0 <= t - p < window
    window: Optional[int] = None
    # the most positions a cached STEP takes, attended against the cache at
    # each row's own index (a verify step takes two). Grouped K/V heads, a
    # window and the rotate-half rotary only (`_cached_grouped`)
    step_positions: int = 1
    use_bias: bool = True  # to_out's (to_qkv never had one)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32  # what the two matrices are stored in
    # DALLE, LANES or ROWS, which `Transformer` fills from its plan; None (a
    # standalone module): `attention_path` of the options above
    path: Optional[str] = None

    def __post_init__(self):
        _known_impl(self.attn_impl)
        super().__post_init__()

    def _use_flash(self, n: int, key_mask) -> bool:
        """Flash path: static masks only (dynamic key-padding stays dense)."""
        if self.attn_impl == "flash":
            if key_mask is not None:
                raise ValueError(
                    'attn_impl="flash" does not support a dynamic key-padding '
                    "mask; encode padding statically or use attn_impl=\"dense\""
                )
            return True
        if self.attn_impl == "dense" or key_mask is not None:
            return False
        return n >= AUTO_FLASH_MIN_SEQ

    def _head_shards(self) -> int:
        """How many ways `train_mesh` splits the heads under `_flash`."""
        mesh = self.train_mesh
        tp = 1 if mesh is None or mesh.size == 1 else dict(mesh.shape).get("tp", 1)
        return tp if self.heads % tp == 0 else 1

    def _token_major(self) -> bool:
        """Whether the flash kernels take this module's heads as column
        blocks of `[B, N, H, D]` (`heads_per_block`, asked of the heads one
        shard of `train_mesh` holds): then nothing is transposed or copied
        between the projections and the kernels. Else q, k, v go to them
        head-major."""
        heads = self.heads // self._head_shards()
        return heads_per_block(self.dim_head, heads, heads) is not None

    def _mesh_axes(self, batch: int):
        """(data axes, head axis) that `train_mesh` splits `_flash`'s operands
        over, each None where it does not divide its dimension (the batch-1
        dummy of `model.init`) and stays replicated."""
        shape = dict(self.train_mesh.shape)
        data = tuple(a for a in ("dp", "fsdp") if shape.get(a, 1) > 1)
        n_data = int(np.prod([shape[a] for a in data])) if data else 1
        return (data if data and batch % n_data == 0 else None,
                "tp" if self._head_shards() > 1 else None)

    def _attend(self, q, k, v, n: int, **layout):
        mask = self._full_mask(n, n) if self.static_mask is not None else None
        if self.window is None:
            return flash_attention(q, k, v, mask=mask, causal=self.causal, **layout)
        return flash_attention(q, k, v, causal=self.causal, window=self.window, **layout)

    def _flash(self, q, k, v, n: int):
        """The in-repo flash kernel over [B, H, N, D]; under a multi-device
        `train_mesh`, shard_mapped over batch and heads (attention mixes
        neither, so the concatenation of the shards is exact)."""
        kernel = lambda q_, k_, v_: self._attend(q_, k_, v_, n)
        mesh = self.train_mesh
        if mesh is None or mesh.size == 1:
            return kernel(q, k, v)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        on_data, on_heads = self._mesh_axes(q.shape[0])
        spec = P(on_data, on_heads, None, None)
        return shard_map(
            kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def _flash_columns(self, qkv, n: int, angles=None):
        """The same kernels over the fused projection's columns, qkv
        [B, n, 3 x heads x dh] as `to_qkv` writes them, giving [B, n, heads,
        dh], the columns `to_out` contracts over: the kernels index a head
        as a column block, so nothing is transposed, and with `angles` (the
        DALL-E rotary's) the rotary's one pass also hands q, k and v over
        as three arrays, so nothing is sliced either. Under a multi-device
        `train_mesh` each shard takes its heads' columns of all three."""
        h, dh = self.heads, self.dim_head

        def kernel(qkv_):
            b, heads = qkv_.shape[0], qkv_.shape[-1] // (3 * dh)
            if angles is not None:
                q, k, v = rotary_split(angles, qkv_, heads, 3)
            else:
                q, k, v = (t.reshape(b, n, heads, dh) for t in jnp.split(qkv_, 3, axis=-1))
            return self._attend(q, k, v, n, layout=TOKEN_MAJOR)

        mesh = self.train_mesh
        if mesh is None or mesh.size == 1:
            return kernel(qkv)
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        on_data, on_heads = self._mesh_axes(qkv.shape[0])
        return shard_map(
            lambda t: kernel(t.reshape(*t.shape[:2], -1)), mesh=mesh,
            in_specs=(P(on_data, None, None, on_heads, None),),
            out_specs=P(on_data, None, on_heads, None), check_vma=False,
        )(qkv.reshape(*qkv.shape[:2], 3, h, dh))

    def _grouped_qkv(self, x, rotary_cs, positions=None):
        """q [B, H, n, dh] and k, v [B, kv_heads, n, dh] from one fused
        projection of (H + 2 kv_heads) x dh columns; q and k normed (per head,
        or over their whole width) and turned by the rotate-half tables where
        there are any (their rows 0..n-1, or with `positions` [B, n] each
        row's own), v left as it is."""
        b, n, _ = x.shape
        h, dh = self.heads, self.dim_head
        hkv = h if self.kv_heads is None else self.kv_heads
        assert h % hkv == 0, f"{h} query heads cannot share {hkv} K/V heads"
        qkv = nn.Dense((h + 2 * hkv) * dh, use_bias=False, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="to_qkv")(x)
        q, k, v = jnp.split(qkv, [h * dh, (h + hkv) * dh], axis=-1)
        norm = lambda name: nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)
        if self.qk_norm == "whole":
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        q = q.reshape(b, n, h, dh)
        k, v = (t.reshape(b, n, hkv, dh) for t in (k, v))
        if self.qk_norm is True:
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if rotary_cs is not None:
            with jax.named_scope("rotary"):
                if positions is None:
                    cos, sin = (t[:n] for t in rotary_cs)
                else:  # [B, 1, n, dh], over the heads
                    cos, sin = (jnp.take(t, positions, axis=0, mode="clip")[:, None]
                                for t in rotary_cs)
                q, k = apply_rotary_half(cos, sin, q), apply_rotary_half(cos, sin, k)
        return q, k, v

    def _cached_grouped(self, q, k, v, cache, start):
        """`(out or None, cache)` of a cached chunk whose K/V heads are shared
        by groups of query heads (query head j reads K/V head j // group),
        under a window or not; q and k come rotated at the positions they
        take. The cache's `index` is per row.

        A STEP (not `start`; n <= `step_positions`) is written from each
        row's own index on, a full layer's along its lanes, a window layer's
        into its ring, and attended against the cache, each query under its
        own mask by true position, the group's heads and the step's positions
        as one operand's rows: causal for a full layer (scope `global_attend`:
        the kernel `decode_grouped` over each row's live positions,
        ops/grouped_decode.py), `0 <= t - p < window` over the positions the
        ring's slots hold for a window layer (`window_attend`: XLA's grouped
        product over the ring). A chunk that STARTS the rows'
        sequences (`start`: a prefill) takes positions 0..n-1 whatever the
        index was, and attends itself (`out` None: the caller's uncached
        path, flash kernels included). A longer chunk onto what a cache holds
        (a later turn's prompt, a prefill in chunks) is not built, and is
        refused here rather than answered from the chunk alone."""
        b, h, n, dh = q.shape
        index = cache["index"]
        chunk = {"k": k, "v": v}
        if not start and n > self.step_positions:
            raise NotImplementedError(
                f"a cached chunk of {n} positions onto what the cache holds: a step takes "
                f"at most {self.step_positions}, and a longer chunk has to start the rows' "
                "sequences (`start=True`: a prefill); a prefill in chunks is not built")
        write = decode_cache.write_ring if self.window is not None else decode_cache.write_rows
        written = write(cache, chunk, start)
        if start:
            return None, {**cache, **written, "index": jnp.full_like(index, n)}
        new_cache = {**cache, **written, "index": index + n}
        ck, cv = written["k"], written["v"]
        hkv, length = ck.shape[1], ck.shape[2]
        group = h // hkv
        q = q.reshape(b, hkv, group * n, dh)
        if self.window is None:
            with jax.named_scope("global_attend"):
                out = grouped_decode_attention(q, ck, cv, new_cache["index"], n=n)
            return out.reshape(b, h, n, dh), new_cache
        at = index[:, None] + jnp.arange(n, dtype=index.dtype)  # [B, n]
        held = decode_cache.ring_positions(index + n - 1, length)[:, None]  # [B, 1, ring]
        gap = at[:, :, None] - held
        mask = (gap >= 0) & (gap < self.window) & (held >= 0)
        mask = jnp.broadcast_to(mask[:, None, None], (b, 1, group, n, length))
        with jax.named_scope("window_attend"):
            out = dense_attention(q, ck, cv, mask=mask.reshape(b, 1, group * n, length))
        return out.reshape(b, h, n, dh), new_cache

    def _use_flash_decode(
        self, max_len: int, has_pattern: bool, sparse: bool = False
    ) -> bool:
        """Cached-path dispatch: flash-decode reads only each row's live KV
        blocks (ops/pallas_decode.py); dense reads the whole cache. Pattern
        masks (static or traced) fall back to dense — a per-step row-sliced
        mask cannot drive the kernel's block skip — UNLESS the cache
        carries a policy block bitmap (`sparse`): then the pattern's
        block-level shadow IS the skip structure, and masked rows route
        through the block-sparse flash kernel instead of reading the whole
        cache dense. `attn_impl="flash"` forces the kernel; "auto"
        switches on cache length; "dense"/"ring" stay dense (ring is a
        training-time layout)."""
        if has_pattern and not sparse:
            return False
        if self.attn_impl == "flash":
            return True
        if self.attn_impl == "auto":
            return max_len >= AUTO_FLASH_DECODE_MIN_LEN
        return False

    def _full_mask(self, n_q: int, n_k: int) -> Optional[np.ndarray]:
        """Host-side composition of causal + static masks, cropped."""
        mask = None
        if self.causal:
            mask = np.tril(np.ones((n_k, n_k), dtype=bool))[n_k - n_q :, :]
            if self.window is not None:
                mask &= ~np.tril(np.ones((n_k, n_k), dtype=bool), -self.window)[n_k - n_q :, :]
        if self.static_mask is not None:
            sm = np.asarray(self.static_mask)[n_k - n_q : n_k, :n_k]
            mask = sm if mask is None else (mask & sm)
        return mask

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        key_mask: Optional[jnp.ndarray] = None,
        rotary: Optional[jnp.ndarray] = None,
        cache: Optional[dict] = None,
        deterministic: bool = True,
        mask_array: Optional[jnp.ndarray] = None,
        rotary_cs: Optional[tuple] = None,
        start: bool = False,
    ):
        """`rotary_cs`: (cos, sin) float32 [>= n, dim_head] tables of the
        rotate-half rotary (`ops/rotary.py:rotary_cos_sin`), applied to q and
        k only; `rotary` is the DALL-E angle table, applied to q, k and v.

        `start`: the cached chunk starts every row's sequence (a prefill into
        a cache whose rows hold nothing yet). The grouped cached path
        (`_cached_grouped`) has to be told: it takes a longer chunk in no
        other way. The DALL-E path attends whatever the cache holds and takes
        no notice.

        `mask_array`: a TRACED [S, S] bool pattern mask (True = attend),
        the per-layer scanned-input analogue of the host-side `static_mask`
        attribute — used by the scan executor, where each layer's pattern
        arrives as data rather than a compile-time constant. Dense paths
        only (a traced mask cannot drive flash's host-side block-occupancy
        skipping); the cached path row-slices it at the decode position
        exactly like `static_mask`."""
        path = self.path or attention_path(self.heads, self.kv_heads, self.qk_norm, self.window)
        if mask_array is not None:
            assert self.static_mask is None, (
                "pass either the static_mask attribute or mask_array, not both"
            )
            assert self.attn_impl not in ("flash", "ring"), (
                f'attn_impl="{self.attn_impl}" cannot apply a traced pattern '
                "mask; scan executor uses dense for masked layers"
            )
        if rotary_cs is not None and path != ROWS:
            raise ValueError(f"a rotate-half table (`rotary_cs`) handed to a module on the {path} "
                             f"path: a rotated layer is built on {ROWS} (`Transformer.plan`)")
        b, n, _ = x.shape
        rows = cache is not None and path == ROWS
        if rows and jnp.ndim(cache["index"]) != 1:
            raise ValueError(
                f"a cache whose index is a scalar (the rows in lockstep) handed to a layer on the "
                f"{ROWS} path, which decodes per row: `Transformer.init_cache` builds its cache")
        # a chunk attends itself ALONE where there is no cache and, on the ROWS
        # path, where it starts the rows' sequences (it is written as well)
        alone = cache is None or (rows and start)
        flash = (alone and self.attn_impl != "ring" and mask_array is None
                 and self._use_flash(n, key_mask))
        # the uncached flash kernels read q, k, v where the DALL-E projection
        # wrote them, [B, n, heads, dh], and write the columns `to_out`
        # contracts over; every other path takes [B, heads, n, dh] and gives
        # it back. (The grouped paths stay head-major: their per-head norm and
        # rotate-half rotary are XLA's, and compiled for a v5e with q, k, v
        # kept token-major, as [.., heads, 128] or as rows of [.., 128], a
        # layer's reshapes and copies hold 1.9 times the bytes of the
        # transposes they replace: PERF.md section 6, PR 34. The kernels
        # take a 128-wide head token-major all the same.)
        tokens = flash and path == DALLE and self._token_major()

        # 1. project
        if path == DALLE:
            h, dh = self.heads, self.dim_head
            qkv = nn.Dense(h * dh * 3, use_bias=False, dtype=self.dtype, name="to_qkv")(x)
            if not tokens:  # else the kernels take `qkv` as it is (`_flash_columns`)
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q, k, v = (t.reshape(b, n, h, dh).transpose(0, 2, 1, 3) for t in (q, k, v))
        else:
            at = None  # on ROWS a step's q and k are turned at each row's own positions
            if rows and not start:
                at = cache["index"][:, None] + jnp.arange(n, dtype=cache["index"].dtype)
            q, k, v = self._grouped_qkv(x, rotary_cs, at)

        # 2. a cached write-and-attend on the module's one path, or attend alone
        new_cache = None
        if rows:
            out, new_cache = self._cached_grouped(q, k, v, cache, start)
        elif cache is not None:
            out, new_cache = self._cached_lanes(q, k, v, cache, rotary, mask_array)
        if tokens:  # the rotary beside the kernels
            out = self._flash_columns(qkv, n, None if rotary is None else rotary[:n])
        elif alone:
            out = self._alone(q, k, v, key_mask, rotary, mask_array, flash)

        # 3. to_out
        if not tokens:
            out = out.transpose(0, 2, 1, 3)
        out = nn.Dense(self.dim, use_bias=self.use_bias, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="to_out")(out.reshape(b, n, -1))
        out = nn.Dropout(self.dropout)(out, deterministic=deterministic)
        return out, new_cache

    def _cached_lanes(self, q, k, v, cache, rotary, mask_array):
        """`(out, cache)` of a cached chunk on the DALLE and LANES paths: the
        chunk written into the cache's K/V lanes or pages at its index, then
        attended against what the cache holds."""
        b, _, n, _ = q.shape
        # n-token chunk (prefill or single-token decode) written into a
        # fixed-shape cache at sequence position `index`. A scalar index
        # means the whole batch decodes in lockstep; a [B] index means
        # per-row positions (continuous-batching slots admitted at
        # different times) — every index-dependent op below (rotary row
        # slice, cache write, causal mask, pattern-mask row slice) then
        # runs per row via vmap, at identical per-row numerics.
        #
        # A cache carrying a "page_table" key is BLOCK-PAGED: k/v are a
        # physical page pool [P, H, page_size, D] shared by all rows
        # and the [B, n_pages] table maps each row's logical blocks to
        # pages (serving/paging.py allocates; released rows point at
        # the reserved garbage page 0, so a stale write can never
        # corrupt a reallocated page). Reads either gather the row's
        # logical view and run the IDENTICAL dense/flash path as the
        # slotted cache (bit-for-bit — the paging parity contract) or
        # stream pages directly through the paged Pallas kernel
        # (ops/pallas_decode.py PAGED_DECODE_IMPL).
        #
        # A cache carrying a "layer" key is the scan executor's: k, v
        # (and their scales) are the DEPTH-STACKED leaves [L, ...] held
        # in the layer scan's carry, and `layer` is this layer's traced
        # index. The chunk's n positions are written into the stack at
        # [layer], in place; what attention reads is `stack[layer]` of
        # the updated stack, a view nothing else consumes. The other
        # leaves (index, page_table, block_bitmap) arrive as the
        # layer's own.
        index = cache["index"]
        layer = cache.get(decode_cache.LAYER)
        per_row = jnp.ndim(index) == 1
        paged = "page_table" in cache
        if rotary is not None:
            if per_row:
                rot = jax.vmap(
                    lambda i: lax.dynamic_slice_in_dim(rotary, i, n, axis=0)
                )(index)
                rot = rot[:, None]  # [B,1,n,dr]
            else:
                rot = lax.dynamic_slice_in_dim(rotary, index, n, axis=0)
                rot = jnp.expand_dims(rot, (0, 1))  # [1,1,n,dr]
            q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        # int8 KV cache: quantize AFTER rotary (the cache stores what
        # attention reads), carry per-(position, head) fp32 scales in
        # sibling leaves; q stays full precision
        quant = "k_scale" in cache
        if quant:
            (qk, k_sc), (qv, v_sc) = _kv_quantize(k), _kv_quantize(v)
            chunk = {"k": qk, "v": qv, "k_scale": k_sc, "v_scale": v_sc}
        else:
            chunk = {"k": k, "v": v}
        # the chunk written at the cache's index (lanes or pages, a
        # leaf or a stack at [layer]); max_len is the virtual
        # contiguous length, the slotted cache's (total_seq_len + 1),
        # so dense/flash see identical shapes on both stores
        written, max_len = decode_cache.write(cache, chunk, self.seq_len + 1)
        pt = cache.get("page_table")
        # this layer's K/V as the reads below take it
        ck, cv = (decode_cache.view(written[x], layer) for x in ("k", "v"))
        cks = cvs = None
        if quant:
            cks, cvs = (
                decode_cache.view(written[x], layer) for x in ("k_scale", "v_scale")
            )
        # policy block bitmap ([B, nb] int32, nb = ceil(max_len /
        # decode_sparse_block), nonzero = KV tile may be read): traced
        # DATA riding the cache pytree (models/dalle.py threads it from
        # the serving engine's host-side policy), so flipping or
        # re-deriving the policy NEVER recompiles the chunk program.
        # When present, it supersedes the pattern masks below — the
        # engine derived it FROM those patterns (conservative
        # block-level shadow, text prefix always live), and it unlocks
        # the flash path for pattern-masked rows.
        bitmap = cache.get("block_bitmap")
        sparse = bitmap is not None
        sparse_block = (
            DECODE_SPARSE_BLOCK
            if self.decode_sparse_block is None
            else self.decode_sparse_block
        )
        # mirror the kernel's block_k clamp so bitmap widths agree on
        # tiny caches (tests run seq_len << DECODE_SPARSE_BLOCK)
        sparse_block = max(min(sparse_block, max_len), 1)
        if self._use_flash_decode(
            max_len,
            has_pattern=(
                self.static_mask is not None or mask_array is not None
            ),
            sparse=sparse,
        ):
            # per-row live length = cache index + this chunk; the kernel
            # applies the same causal-over-prefix mask the dense branch
            # builds below, but reads ONLY each row's live K/V blocks
            # (scalar index = lockstep decode: every row at one length)
            lengths = jnp.broadcast_to(index + n, (b,)).astype(jnp.int32)
            scales = {"k_scale": cks, "v_scale": cvs} if quant else {}
            sparse_kw = (
                {"block_bitmap": bitmap, "sparse_block": sparse_block}
                if sparse else {}
            )
            if paged:
                if self.decode_mesh is not None:
                    out = sharded_paged_decode_attention(
                        self.decode_mesh, q, ck, cv, lengths, pt,
                        max_len, head_axis=self.decode_heads_axis,
                        **scales, **sparse_kw,
                    )
                else:
                    out = paged_decode_attention(
                        q, ck, cv, lengths, pt, max_len,
                        **scales, **sparse_kw,
                    )
            elif self.decode_mesh is not None:
                out = sharded_flash_decode_attention(
                    self.decode_mesh, q, ck, cv, lengths,
                    head_axis=self.decode_heads_axis,
                    **scales, **sparse_kw,
                )
            elif sparse:
                out = block_sparse_flash_decode_attention(
                    q, ck, cv, lengths, bitmap,
                    block_k=sparse_block, **scales,
                )
            else:
                out = flash_decode_attention(q, ck, cv, lengths, **scales)
        else:
            with jax.named_scope("cache_read"):
                if paged:
                    # one gathered view per dispatch; dead positions
                    # hold garbage-page bytes but the causal mask below
                    # replaces their scores with the same NEG constant
                    # the slotted path uses, so outputs stay
                    # bit-identical
                    gk = paged_gather(ck, pt, max_len)
                    gv = paged_gather(cv, pt, max_len)
                    if quant:
                        gk = _kv_dequantize(
                            gk,
                            paged_gather(cks[..., None], pt, max_len)[..., 0],
                        )
                        gv = _kv_dequantize(
                            gv,
                            paged_gather(cvs[..., None], pt, max_len)[..., 0],
                        )
                else:
                    gk, gv = ck, cv
                    if quant:
                        gk = _kv_dequantize(gk, cks)
                        gv = _kv_dequantize(gv, cvs)
            # query row i sits at global position index + i: causal over
            # the written prefix (the reference instead relies on only
            # having written the prefix, `attention.py:71-76,86`)
            if per_row:
                valid = (
                    jnp.arange(max_len)[None, None, :]
                    <= index[:, None, None] + jnp.arange(n)[None, :, None]
                )
                mask = valid[:, None]  # [B,1,n,max_len]
            else:
                valid = (
                    jnp.arange(max_len)[None, :]
                    <= index + jnp.arange(n)[:, None]
                )
                mask = valid[None, None]

            def mask_rows_at(pm):
                # pad to max_len with True (decode caches may be 1
                # longer than the mask), then row-slice at the decode
                # position — shared by the host-side static_mask and
                # the scan executor's traced mask_array so the two
                # paths cannot drift
                if pm.shape[0] < max_len:
                    pad = max_len - pm.shape[0]
                    pm = jnp.pad(
                        pm, ((0, pad), (0, pad)), constant_values=True
                    )
                pm = pm[:, :max_len]
                if per_row:
                    return jax.vmap(
                        lambda i: lax.dynamic_slice_in_dim(pm, i, n, axis=0)
                    )(index)[:, None]  # [B,1,n,max_len]
                return lax.dynamic_slice_in_dim(pm, index, n, axis=0)[
                    None, None
                ]

            if sparse:
                # the bitmap supersedes the pattern masks on the dense
                # fallback too (small caches / attn_impl="dense"), so
                # BOTH decode paths compute the identical block-level
                # policy — the sparse-vs-dense oracle the tests pin
                kv_live = jnp.repeat(bitmap != 0, sparse_block, axis=1)
                mask = mask & kv_live[:, :max_len][:, None, None, :]
            else:
                if self.static_mask is not None:
                    mask = mask & mask_rows_at(
                        jnp.asarray(np.asarray(self.static_mask))
                    )
                if mask_array is not None:
                    mask = mask & mask_rows_at(mask_array)
            with jax.named_scope("attend"):
                out = dense_attention(
                    q, gk, gv, mask=mask, stable=self.stable
                )
        # structural round-trip: the cache that comes back has the
        # leaves it came with, side leaves included (the callers strip
        # them from both layouts alike)
        new_cache = {"k": written["k"], "v": written["v"], "index": index + n}
        new_cache.update(written)  # + an int8 store's scale leaves
        if paged:
            new_cache["page_table"] = pt
        if sparse:
            new_cache["block_bitmap"] = bitmap
        return out, new_cache

    def _alone(self, q, k, v, key_mask, rotary, mask_array, flash):
        """The chunk attended by itself (head-major: ring, the flash kernels
        or dense), the DALL-E rotary first where there is one."""
        _, h, n, _ = q.shape
        if rotary is not None:
            rot = jnp.expand_dims(rotary[:n], (0, 1))
            q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        if self.attn_impl == "ring":
            # sequence-parallel exact attention: tokens sharded over the
            # mesh "sp" axis, KV blocks rotate via ppermute (parallel/
            # ring.py). Long-context path beyond the reference's
            # sparsity-based scaling (SURVEY.md §5.7).
            from dalle_pytorch_tpu.parallel.ring import ring_attention_sharded

            assert self.sp_mesh is not None, 'attn_impl="ring" needs sp_mesh'
            assert self.static_mask is None and key_mask is None, (
                "ring attention supports plain causal/full attention only"
            )
            # the streaming LSE accumulator is inherently max-subtracted;
            # reject the stable flag rather than silently diverge from
            # the dense stable-softmax numerics
            assert not self.stable, 'attn_impl="ring" does not take stable='
            sp = self.sp_mesh.shape["sp"]
            assert n % sp == 0, (
                f"sequence length {n} must be divisible by the sp axis ({sp}); note "
                "the uncached generate_images() re-forwards growing "
                "prefixes — use the KV-cached decode path with ring models"
            )
            return ring_attention_sharded(self.sp_mesh, q, k, v, causal=self.causal)
        if flash:
            return self._flash(q, k, v, n)
        mask = self._full_mask(n, n)
        mask = None if mask is None else jnp.asarray(mask)[None, None]
        if k.shape[1] != h:  # the dense path spells the sharing out
            k, v = (jnp.repeat(t, h // t.shape[1], axis=1) for t in (k, v))
        if mask_array is not None:
            tm = mask_array[:n, :n][None, None]
            mask = tm if mask is None else (mask & tm)
        if key_mask is not None:
            km = key_mask[:, None, None, :]
            mask = km if mask is None else (mask & km)
        return dense_attention(q, k, v, mask=mask, stable=self.stable)



class LatentAttention(nn.Module):
    """Causal multi-head LATENT attention: queries through a low-rank
    bottleneck, and keys and values of every head expanded from one
    compressed vector a position, beside one rotary key that all heads share.

        c_q = rms(x W_dq)            q = c_q W_uq -> per head q_n | q_r
        c | k_r = x W_dkv            c = rms(c);  q_r, k_r rotated (rotate-half)
        k_n | v = c W_ukv            per head
        s = (q_n . k_n + q_r . k_r) / sqrt(qk_nope_dim + qk_rope_dim) * softmax_mult

    Two forms that must agree. Without a cache, and for a chunk that STARTS
    its rows' sequences (`start`: a prefill into rows that hold nothing
    yet), the EXPANDED form: k and v of all heads made from the
    chunk's latent, attention by the dense path or the flash kernels (q and k
    `qk_nope_dim + qk_rope_dim` wide; v is padded with zeros to that width,
    because the kernels keep one width, and the padding is cut from the
    result). With a cache otherwise, the ABSORBED form over the cache's
    latent itself: `q_c = q_n W_uk^T` per head, the scores and the weighted
    sum against `c`, and `o = o_c W_uv` after; the
    cache holds `c` and the rotated `k_r` (models/decode_cache.py, kind
    `latent`) and no head ever has its keys or values written out. A
    one-token step runs it through the kernel (ops/latent_decode.py); a
    longer chunk of NEW tokens, written at the cache's index, attends what
    the cache holds and itself in blocks of cached positions under a running
    softmax (`_chunk_attend`).

    `index_topk` > 0 puts a LIGHTNING INDEXER beside it (learned sparse
    attention): from the layer's input x and the same normed query latent,

        q_I = c_q W_Iq -> index_heads of index_dim, the first qk_rope_dim rotated
        k_I = LayerNorm(x W_Ik), one key a position, rotated likewise; cached
        w   = x W_Iw / sqrt(index_heads)
        I(t, p) = sum_j w_t,j relu(q_I,t,j . k_I,p) / sqrt(index_dim)

    and query t attends the min(index_topk, t + 1) positions p <= t of
    largest I(t, p) alone (ties: the lower position; ops/index_select.py). A
    token step scores the row's live positions (ops/index_score.py), selects
    as indices and attends those positions, fetched
    (ops/sparse_latent_decode.py: one fetch a position, because such a
    layer's cache keeps `c` and `k_r` of a position in one row; which layout a
    cache has is read off its leaves); a chunk, cached or not, scores in blocks
    and masks the blocks' scores by the selection. A sequence or a cache no
    longer than `index_topk` selects everything: the dense forms run, and the
    indexer only writes its keys. Sows `dsa_scored` and `dsa_selected` (the
    positions a token step scored and attended, over its rows) into `stats`,
    and into `picks`, where the caller makes it mutable, the step's
    `selected` [B, index_topk] and `selected_count` [B].

    Parameters: `to_q_latent`, `q_norm`, `to_q`, `to_kv_latent`, `kv_norm`,
    `to_kv` [kv_lora_rank, heads * (qk_nope_dim + v_dim)], `to_out`, and of the
    indexer `index_q`, `index_k`, `index_k_norm` (gain and bias), `index_w`; no
    other biases; matrices stored in `param_dtype`, the gains in float32.
    """

    dim: int
    seq_len: int
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    norm_eps: float = 1e-6
    attn_impl: str = "auto"
    softmax_mult: float = 1.0
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        _known_impl(self.attn_impl)
        super().__post_init__()

    @nn.compact
    def __call__(self, x, key_mask=None, rotary=None, cache=None, deterministic=True,
                 rotary_cs=None, start=False):
        assert key_mask is None and rotary is None and rotary_cs is not None, (
            "latent attention is causal, unpadded, under a rotate-half table")
        b, n, _ = x.shape
        h, dn, dr, dv, rank = (self.heads, self.qk_nope_dim, self.qk_rope_dim, self.v_dim,
                               self.kv_lora_rank)
        sm_scale = (dn + dr) ** -0.5 * self.softmax_mult
        dense = lambda width, name: nn.Dense(
            width, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype, name=name)
        norm = lambda name: nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)
        to_kv = self.param("to_kv", nn.initializers.lecun_normal(),
                           (rank, h * (dn + dv)), self.param_dtype).astype(self.dtype)
        index = 0 if cache is None else cache["index"]
        with jax.named_scope("mla_proj"):
            c_q = norm("q_norm")(dense(self.q_lora_rank, "to_q_latent")(x))
            q = dense(h * (dn + dr), "to_q")(c_q)
            q = q.reshape(b, n, h, dn + dr).transpose(0, 2, 1, 3)  # [b, h, n, dn + dr]
            c, k_r = jnp.split(dense(rank + dr, "to_kv_latent")(x), [rank], axis=-1)
            c = norm("kv_norm")(c)  # [b, n, rank]
            cos, sin = (lax.dynamic_slice_in_dim(t, index, n, axis=0) for t in rotary_cs)
            q_n, q_r = q[..., :dn], apply_rotary_half(cos, sin, q[..., dn:])
            k_r = apply_rotary_half(cos, sin, k_r)  # [b, n, dr]: one head
            # as the cache keeps a position: a row of both (an indexed layer's), or two leaves
            in_rows = cache is not None and decode_cache.ROWS in cache
            chunk = ({decode_cache.ROWS: jnp.concatenate([c, k_r], axis=-1)} if in_rows else
                     {decode_cache.LATENT: c, decode_cache.ROPE: k_r.transpose(0, 2, 1)})
        if self.index_topk:
            with jax.named_scope("dsa_index_proj"):
                hi, di = self.index_heads, self.index_dim
                turn = lambda t: jnp.concatenate(
                    [apply_rotary_half(cos, sin, t[..., :dr]), t[..., dr:]], axis=-1)
                q_i = turn(dense(hi * di, "index_q")(c_q).reshape(b, n, hi, di)
                           .transpose(0, 2, 1, 3))  # [b, hi, n, di]
                k_i = turn(nn.LayerNorm(epsilon=1e-6, dtype=self.dtype, name="index_k_norm")(
                    dense(di, "index_k")(x)))  # [b, n, di]: one key
                w_i = dense(hi, "index_w")(x).astype(jnp.float32) * (hi * di) ** -0.5
                chunk[decode_cache.INDEX_K] = k_i

        new_cache = None
        held = chunk  # what is attended: the chunk, or the cache with the chunk in it
        if cache is not None:
            held, _ = decode_cache.write(cache, chunk, None)
            new_cache = {**held, "index": index + n}
        # rows of both (`rope` None: `split_rows` where two operands are wanted), or the two leaves
        latent, rope = ((held[decode_cache.ROWS], None) if in_rows else
                        (held[decode_cache.LATENT], held[decode_cache.ROPE]))
        # with an indexer: whether some query may have more positions than it attends
        sparse = bool(self.index_topk) and self.index_topk < (
            n if cache is None or start else latent.shape[1])
        if cache is not None and n == 1:
            with jax.named_scope("mla_proj"):
                w = to_kv.reshape(rank, h, dn + dv)
                q_c = jnp.einsum("bhd,rhd->bhr", q_n[:, :, 0], w[..., :dn])
            if sparse:
                lengths = jnp.broadcast_to(index + 1, (b,))
                with jax.named_scope("dsa_index"):
                    scores = index_scores(q_i[:, :, 0], w_i[:, 0], held[decode_cache.INDEX_K],
                                          lengths)
                with jax.named_scope("dsa_select"):
                    chosen, count = selected_mask(scores, lengths, self.index_topk)
                    picked = selected_indices(chosen, self.index_topk)
                with jax.named_scope("mla_attend"):
                    o_c = sparse_latent_decode_attention(
                        q_c, q_r[:, :, 0], latent, rope, picked, count, sm_scale=sm_scale)
                with jax.named_scope("dsa_select"):
                    for name, value in (("dsa_scored", jnp.sum(lengths)),
                                        ("dsa_selected", jnp.sum(chosen, dtype=jnp.int32))):
                        self.sow("stats", name, value, reduce_fn=lambda _, new: new,
                                 init_fn=lambda: None)
                if self.is_mutable_collection("picks"):
                    for name, value in (("selected", picked), ("selected_count", count)):
                        self.sow("picks", name, value, reduce_fn=lambda _, new: new,
                                 init_fn=lambda: None)
            else:
                with jax.named_scope("mla_attend"):
                    if rope is None:  # a cache no longer than the selection: cut whole
                        latent, rope = split_rows(latent, rank, dr)
                    o_c = latent_decode_attention(
                        q_c, q_r[:, :, 0], latent, rope, jnp.broadcast_to(index + 1, (b,)),
                        sm_scale=sm_scale)
            with jax.named_scope("mla_proj"):
                out = jnp.einsum("bhr,rhv->bhv", o_c, w[..., dn:]).reshape(b, 1, h * dv)
        elif sparse or (cache is not None and not start):
            # new tokens against what the cache holds and themselves, or a
            # sequence longer than a query attends: the absorbed form in blocks
            with jax.named_scope("mla_proj"):
                w = to_kv.reshape(rank, h, dn + dv)
                q_c = jnp.einsum("bhnd,rhd->bhnr", q_n, w[..., :dn])
            o_c = _chunk_attend(
                q_c, q_r, latent, rope, index, sm_scale=sm_scale, **(dict(
                    indexer=(q_i, w_i, held[decode_cache.INDEX_K]), topk=self.index_topk)
                    if sparse else {}))
            with jax.named_scope("mla_proj"):
                out = jnp.einsum("bhnr,rhv->bnhv", o_c, w[..., dn:]).reshape(b, n, h * dv)
        else:
            # the chunk is attended by itself alone: it starts the sequence
            with jax.named_scope("mla_proj"):
                kv = jnp.dot(c, to_kv).reshape(b, n, h, dn + dv).transpose(0, 2, 1, 3)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (b, h, n, dr))], axis=-1)
                v = kv[..., dn:]
            flash = self.attn_impl == "flash" or (
                self.attn_impl == "auto" and n >= AUTO_FLASH_MIN_SEQ)
            if flash:
                with jax.named_scope("mla_proj"):
                    v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
                out = flash_attention(jnp.concatenate([q_n, q_r], -1), k, v,
                                      causal=True, sm_scale=sm_scale)[..., :dv]
            else:
                with jax.named_scope("mla_attend"):
                    mask = jnp.tril(jnp.ones((n, n), bool))[None, None]
                    scaled = jnp.concatenate([q_n, q_r], -1)
                    if self.softmax_mult != 1.0:  # the dense path scales by 1/sqrt(width)
                        scaled = scaled * jnp.asarray(self.softmax_mult, scaled.dtype)
                    out = dense_attention(scaled, k, v, mask=mask)
            out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dv)
        with jax.named_scope("mla_proj"):
            return dense(self.dim, "to_out")(out), new_cache


CHUNK_KEYS = 1024  # cached positions a step of `_chunk_attend`'s loops takes


def _chunk_attend(q_c, q_r, latent, rope, start, *, sm_scale, indexer=None, topk=0):
    """[B, H, n, R]: the absorbed form for n queries a row at positions
    `start .. start + n - 1` (`start` a traced scalar: the rows stand in
    lockstep) over `latent` [B, L, R] and `rope` [B, dr, L] (or, `rope` None,
    over rows [B, L, >= R + dr] of both: each block's two operands are cut
    from the block, never from the leaf), which hold those
    positions and all before them: query t sees p <= t. `indexer = (q_i [B,
    Hi, n, Di], w_i [B, n, Hi] float32, keys [B, L, Di])` with `topk`: query t
    sees of those the min(topk, t + 1) positions of largest index score
    alone. Both passes walk blocks of `CHUNK_KEYS` positions and stop at the
    last block a query of the chunk can see; a last block that would
    overhang the arrays starts earlier and counts only what is new in it.
    The index pass writes a chunk's scores [B, n, L] float32, whole (the
    selection needs a row's every score); the attention pass keeps a running
    softmax in float32 and no score past its block."""
    b, h, n, _ = q_c.shape
    total = latent.shape[1]
    size = min(CHUNK_KEYS, total)
    blocks = (start + n + size - 1) // size
    at = start + jnp.arange(n)  # the queries' positions
    block_at = lambda j: jnp.minimum(j * size, total - size)

    chosen = None
    if indexer is not None:
        q_i, w_i, keys = indexer

        def score(j, scores):
            lo = block_at(j)
            k = lax.dynamic_slice_in_dim(keys, lo, size, axis=1)
            s = jnp.einsum("bhnd,bkd->bnhk", q_i, k, preferred_element_type=jnp.float32)
            s = jnp.sum(jnp.maximum(s, 0.0) * w_i[..., None], axis=2)
            return lax.dynamic_update_slice_in_dim(scores, s, lo, axis=2)

        with jax.named_scope("dsa_index"):
            scores = lax.fori_loop(0, blocks, score,
                                   jnp.full((b, n, total), -jnp.inf, jnp.float32))
        with jax.named_scope("dsa_select"):
            chosen, _ = selected_mask(scores, jnp.broadcast_to(at + 1, (b, n)), topk)

    def attend(j, carry):
        m, l, acc = carry
        lo = block_at(j)
        c = lax.dynamic_slice_in_dim(latent, lo, size, axis=1)
        c, k_r = (split_rows(c, q_c.shape[-1], q_r.shape[-1]) if rope is None else
                  (c, lax.dynamic_slice_in_dim(rope, lo, size, axis=2)))
        pos = lo + jnp.arange(size)
        if chosen is None:
            seen = (pos[None, :] <= at[:, None])[None]
        else:
            seen = lax.dynamic_slice_in_dim(chosen, lo, size, axis=2)
        seen = (seen & (pos >= j * size))[:, None]  # [b, 1, n, size]
        s = (jnp.einsum("bhnr,bkr->bhnk", q_c, c, preferred_element_type=jnp.float32)
             + jnp.einsum("bhnd,bdk->bhnk", q_r, k_r, preferred_element_type=jnp.float32))
        s = jnp.where(seen, s * sm_scale, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)  # a query that has seen nothing yet
        p = jnp.exp(s - safe)
        corr = jnp.exp(m - safe)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhnk,bkr->bhnr", p.astype(c.dtype), c,
                                      preferred_element_type=jnp.float32)
        return m_new, l, acc

    with jax.named_scope("mla_attend"):
        m = jnp.full((b, h, n, 1), -jnp.inf, jnp.float32)
        _, l, acc = lax.fori_loop(
            0, blocks, attend, (m, jnp.zeros_like(m), jnp.zeros(q_c.shape, jnp.float32)))
        return (acc / l).astype(q_c.dtype)


def delta_rule_chunked(q, k, v, g, beta, chunk: int = 64):
    """The gated delta rule over n tokens from an EMPTY state, a chunk at a
    time: `(o [b, n, h, d_v], S [b, h, d_k, d_v])`, float32 throughout.

    q, k [b, n, h, d_k], v [b, n, h, d_v]; g [b, n, h] the LOG of the decay
    alpha, beta [b, n, h]. With Gamma_t the decay's running product inside a
    chunk and S_0 the state it starts from, the chunk's updates solve

        (I + A) U = diag(beta) V - diag(beta Gamma) K S_0,
        A[t, j] = beta_t (k_t . k_j) Gamma_t / Gamma_j   for j < t,

    so T = (I + A)^-1 is made once a chunk by forward substitution (the WY /
    UT transform of arXiv:2412.06464), `u = T diag(beta) V` and `w = T
    diag(beta Gamma) K` need no state, and a `lax.scan` over the chunks
    carries S alone: U = u - w S_0, o_t = Gamma_t S_0^T q_t + sum_{j <= t}
    (Gamma_t / Gamma_j) (k_j . q_t) U_j, S_C = Gamma_C S_0 + sum_j (Gamma_C /
    Gamma_j) k_j U_j^T. A tail chunk is padded with tokens that change
    nothing (k = v = 0, beta = 0, alpha = 1)."""
    b, n, h, dk = q.shape
    pad = (-n) % chunk
    mm = lambda spec, x, y: jnp.einsum(spec, x, y, precision="highest")

    def chunks(t):  # [b, n, h, ...] -> [b, h, c, chunk, ...]
        t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, -1, chunk) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)

    q, k, v, g, beta = (chunks(t) for t in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)  # log Gamma [b, h, c, chunk]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    ratio = jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], 0.0)
    decay = jnp.where(lower, jnp.exp(ratio), 0.0)  # Gamma_t / Gamma_j, j <= t
    kb, vb = k * beta[..., None], v * beta[..., None]
    m = -jnp.where(strict, mm("...td,...jd->...tj", kb, k) * decay, 0.0)

    def substitute(i, m):  # row i of (I + A)^-1 - I from the rows above it
        row = jnp.where(jnp.arange(chunk) < i, m[..., i, :], 0.0)
        return m.at[..., i, :].set(row + jnp.sum(row[..., :, None] * m, axis=-2))

    t_inv = lax.fori_loop(1, chunk, substitute, m) + jnp.eye(chunk, dtype=jnp.float32)
    u = mm("...tj,...jd->...td", t_inv, vb)
    w = mm("...tj,...jd->...td", t_inv, kb * jnp.exp(gamma)[..., None])
    qk = jnp.where(lower, mm("...td,...jd->...tj", q, k) * decay, 0.0)
    q_in = q * jnp.exp(gamma)[..., None]
    k_out = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    total = jnp.exp(gamma[..., -1])  # Gamma_C [b, h, c]

    def one(s, xs):
        u_i, w_i, qk_i, q_i, k_i, total_i = xs
        new = u_i - mm("bhtk,bhkv->bhtv", w_i, s)
        o = mm("bhtk,bhkv->bhtv", q_i, s) + mm("bhtj,bhjv->bhtv", qk_i, new)
        return s * total_i[..., None, None] + mm("bhtk,bhtv->bhkv", k_i, new), o

    per_chunk = lambda t: jnp.moveaxis(t, 2, 0)
    s, o = lax.scan(one, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                    tuple(per_chunk(t) for t in (u, w, qk, q_in, k_out, total)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, -1, v.shape[-1])[:, :, :n]
    return o.transpose(0, 2, 1, 3), s


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32, log_uniform=False):
    """The inverse softplus of a step size drawn from (0.001, 0.1): uniformly,
    or (`log_uniform`, the state-space mixer's) uniformly in its logarithm."""
    low, high = (math.log(0.001), math.log(0.1)) if log_uniform else (0.001, 0.1)
    dt = jax.random.uniform(key, shape, dtype, low, high)
    dt = jnp.exp(dt) if log_uniform else dt
    return dt + jnp.log(-jnp.expm1(-dt))  # the inverse of softplus


class GatedDeltaAttention(nn.Module):
    """Causal LINEAR attention by the gated delta rule (Gated DeltaNet,
    arXiv:2412.06464; beta in (0, 2), arXiv:2411.12537): per row and head a
    float32 state S [key_dim, value_dim] in place of keys and values (in the
    cache a row's heads lie side by side: `decode_cache.pack_state`).

        q~ | k~ | v~ = x W_qkv          [H x key_dim | H x key_dim | H x value_dim]
        q', k', v' = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                     causal depthwise convolution over time, `conv_width` taps
        q = l2norm(q') / sqrt(key_dim),  k = l2norm(k'),  v = v'        per head
        beta = 2 sigmoid(x W_b),  alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))
        S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
        o_t = S_t^T q_t;  y_t = rmsnorm(o_t; gain [value_dim]) silu(x W_g)
        out = concat_h(y_t) W_o

    Two forms that tests hold to each other. Without a cache, and for a
    prefill chunk written into one, the CHUNKED form from an empty state
    (`delta_rule_chunked`; a prefill of further tokens against a state is not
    built), which also leaves the state and the convolution's ring (its last
    `conv_width - 1` inputs) in the cache. With a one-token step, the
    RECURRENCE against the cache (ops/delta_step.py: one pass over the
    state, in place). The cache is the `recurrent` kind of layer
    (models/decode_cache.py).

    The three projections that feed the recurrence, W_a and W_b give float32
    (bf16 operands, float32 accumulation, nothing rounded after), and the
    convolution, the norms, alpha and beta are float32: what is summed into a
    state for thousands of steps is not rounded on its way there.

    Parameters: `to_qkv`, `to_ab` [dim, 2 H] (beta's columns, then alpha's),
    `to_gate`, `to_out`, `conv` [conv_width, columns of to_qkv] in
    `param_dtype`; `A_log`, `dt_bias` [H] and `o_norm` [value_dim] float32.
    """

    dim: int
    seq_len: int
    heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, key_mask=None, rotary=None, cache=None, deterministic=True):
        assert key_mask is None and rotary is None, "linear attention is causal and unpadded"
        b, n, _ = x.shape
        h, dk, dv, taps = self.heads, self.key_dim, self.value_dim, self.conv_width
        width = h * (2 * dk + dv)
        matrix = lambda name, shape: self.param(
            name, nn.initializers.lecun_normal(), shape, self.param_dtype)
        dense = lambda cols, name: nn.Dense(
            cols, use_bias=False, dtype=self.dtype, param_dtype=self.param_dtype, name=name)
        to_qkv, to_ab = matrix("to_qkv", (self.dim, width)), matrix("to_ab", (self.dim, 2 * h))
        conv = matrix("conv", (taps, width)).astype(jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        o_gain = self.param("o_norm", nn.initializers.ones, (dv,))
        step = cache is not None and n == 1
        with jax.named_scope("delta_proj"):
            x = x.astype(self.dtype)
            wide = lambda w: jnp.dot(x, w.astype(self.dtype), preferred_element_type=jnp.float32)
            qkv, ab = wide(to_qkv), wide(to_ab)
            gate = dense(h * dv, "to_gate")(x)
            beta = 2.0 * jax.nn.sigmoid(ab[..., :h])
            g = -jnp.exp(a_log) * jax.nn.softplus(ab[..., h:] + dt_bias)  # log alpha
            # the convolution's inputs: the ring (zeros before a sequence
            # starts), then this call's
            ring = (cache[decode_cache.CONV].astype(jnp.float32) if step
                    else jnp.zeros((b, taps - 1, width), jnp.float32))
            window = jnp.concatenate([ring, qkv], axis=1)  # [b, taps - 1 + n, width]
            mixed = sum(conv[j] * window[:, j:j + n] for j in range(taps))
            q, k, v = jnp.split(jax.nn.silu(mixed), [h * dk, 2 * h * dk], axis=-1)
            q, k = (t.reshape(b, n, h, dk) for t in (q, k))
            unit = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            q, k, v = unit(q) * dk**-0.5, unit(k), v.reshape(b, n, h, dv)
        if step:
            with jax.named_scope("delta_step"):
                o, state = delta_step(cache[decode_cache.STATE], q[:, 0], k[:, 0], v[:, 0],
                                      jnp.exp(g[:, 0]), beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("delta_chunk"):
                o, state = delta_rule_chunked(q, k, v, g, beta)
                state = decode_cache.pack_state(state)
        new_cache = None
        if cache is not None:
            new_cache = {**cache, decode_cache.STATE: state,
                         decode_cache.CONV: window[:, n:].astype(cache[decode_cache.CONV].dtype),
                         decode_cache.INDEX: cache[decode_cache.INDEX] + n}
        with jax.named_scope("delta_proj"):
            o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.norm_eps) * o_gain
            y = o.astype(self.dtype) * jax.nn.silu(gate.reshape(b, n, h, dv))
            return dense(self.dim, "to_out")(y.reshape(b, n, h * dv)), new_cache


def ssm_chunked(x, dt, a, b, c, d, chunk: int = 128):
    """A Mamba-2 layer's recurrence over n tokens from an EMPTY state, a chunk
    at a time (the SSD form, arXiv:2405.21060): `(y [b, n, H, P], S [b, H, N,
    P])`, float32 throughout.

    x [b, n, H, P], dt [b, n, H] (after its softplus), a, d [H] (a < 0), b, c
    [b, n, G, N], head i reading group i // (H / G). With l_t = dt_t A the log
    of a token's decay and Lambda_t its running sum inside a chunk,

        y_t = exp(Lambda_t) S_0^T C_t + sum_{j <= t} exp(Lambda_t - Lambda_j)
              (C_t . B_j) dt_j x_j + D x_t
        S_C = exp(Lambda_C) S_0 + sum_j exp(Lambda_C - Lambda_j) B_j (dt_j x_j)^T

    so inside a chunk the work is the decay-masked product C B^T (one a group)
    against the chunk's dt x, and a `lax.scan` over the chunks carries S alone;
    everything of a chunk is made inside its step, a chunk's [chunk, chunk]
    masks at a time. A tail chunk is padded with tokens that change nothing
    (dt = 0: decay 1, dt x = 0)."""
    bsz, n, h, p = x.shape
    g, n_state = b.shape[2], b.shape[3]
    r = h // g
    pad = (-n) % chunk
    mm = lambda spec, u, v: jnp.einsum(spec, u, v, precision="highest")

    def chunks(t, *shape):  # [b, n, ...] -> [c, b, chunk, ...]
        t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((bsz, -1, chunk) + shape), 1, 0)

    a, d = a.astype(jnp.float32).reshape(g, r), d.astype(jnp.float32).reshape(g, r)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, xs):  # s [b, G, R, N, P]
        x_i, dt_i, b_i, c_i = xs  # [b, chunk, G, R, P], [b, chunk, G, R], [b, chunk, G, N] x 2
        lam = jnp.cumsum(dt_i * a, axis=1)  # Lambda [b, chunk, G, R]
        dtx = dt_i[..., None] * x_i
        ratio = jnp.where(lower[None, :, :, None, None],
                          lam[:, :, None] - lam[:, None, :], -jnp.inf)  # [b, t, j, G, R]
        mask = jnp.exp(ratio) * mm("btgn,bjgn->btjg", c_i, b_i)[..., None]
        y = (mm("btjgr,bjgrp->btgrp", mask, dtx)
             + jnp.exp(lam)[..., None] * mm("btgn,bgrnp->btgrp", c_i, s)
             + d[..., None] * x_i)
        out = jnp.exp(lam[:, -1:] - lam)[..., None] * dtx  # what reaches the chunk's end
        new = (jnp.exp(lam[:, -1])[..., None, None] * s + mm("bjgn,bjgrp->bgrnp", b_i, out))
        return new, y

    s, y = lax.scan(one, jnp.zeros((bsz, g, r, n_state, p), jnp.float32),
                    (chunks(x, g, r, p), chunks(dt, g, r), chunks(b, g, n_state),
                     chunks(c, g, n_state)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, -1, h, p)[:, :n]
    return y, s.reshape(bsz, h, n_state, p)


class Mamba2Mixer(nn.Module):
    """A Mamba-2 state-space mixer (arXiv:2405.21060, as `nemotron_h` builds
    it): per row and head a float32 state S [state_dim, head_dim] in place of
    keys and values (in the cache a row's heads lie side by side:
    `decode_cache.pack_state`), `groups` pairs of B and C each shared by heads
    / groups heads.

        z | xBC | dt = x W_in        [H P | H P + 2 G N | H], no bias
        xBC = silu(conv(xBC) + bias) causal depthwise convolution over time,
                                     `conv_width` taps
        x | B | C = xBC              [H x P | G x N | G x N]
        dt = softplus(dt + dt_bias),  A = -exp(A_log)             one a head
        S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T;  y_t = S_t^T C_t + D x_t
        y = rmsnorm(y silu(z); gain [H P])       the norm in G groups of H P / G
        out = y W_out

    Two forms that tests hold to each other. Without a cache, and for a
    prefill chunk written into one, the CHUNKED form from an empty state
    (`ssm_chunked`; a prefill of further tokens against a state is not built),
    which also leaves the state and the convolution's ring (its last
    `conv_width - 1` inputs) in the cache. With a one-token step, the
    RECURRENCE against the cache (ops/ssm_step.py: one pass over the state,
    in place). The cache is the `recurrent` kind of layer
    (models/decode_cache.py), its index a scalar or per row: neither form
    reads it.

    W_in gives float32 (bf16 operands, float32 accumulation, nothing rounded
    after), and the convolution, dt, the recurrence, the gate and the norm are
    float32: what is summed into a state for thousands of steps is not rounded
    on its way there.

    Parameters: `to_in`, `to_out` and `conv` [conv_width, H P + 2 G N] in
    `param_dtype`; `conv_bias`, `A_log`, `D`, `dt_bias` [H] and `norm` [H P]
    float32.
    """

    dim: int
    seq_len: int
    heads: int
    head_dim: int
    groups: int
    state_dim: int
    conv_width: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, key_mask=None, rotary=None, cache=None, deterministic=True):
        assert key_mask is None and rotary is None, "a state-space mixer is causal and unpadded"
        bsz, n, _ = x.shape
        h, p, g, n_state, taps = (self.heads, self.head_dim, self.groups, self.state_dim,
                                  self.conv_width)
        inner, width = h * p, h * p + 2 * g * n_state
        matrix = lambda name, shape: self.param(
            name, nn.initializers.lecun_normal(), shape, self.param_dtype)
        to_in = matrix("to_in", (self.dim, inner + width + h))
        conv = matrix("conv", (taps, width)).astype(jnp.float32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (width,))
        a = -jnp.exp(self.param("A_log", _a_log_init, (h,)))
        dt_bias = self.param("dt_bias", functools.partial(_dt_bias_init, log_uniform=True), (h,))
        skip = self.param("D", nn.initializers.ones, (h,))
        gain = self.param("norm", nn.initializers.ones, (inner,))
        step = cache is not None and n == 1
        with jax.named_scope("ssm_proj"):
            x = x.astype(self.dtype)
            z, xbc, dt = jnp.split(
                jnp.dot(x, to_in.astype(self.dtype), preferred_element_type=jnp.float32),
                [inner, inner + width], axis=-1)
            dt = jax.nn.softplus(dt + dt_bias)
            # the convolution's inputs: the ring (zeros before a sequence
            # starts), then this call's
            ring = (cache[decode_cache.CONV].astype(jnp.float32) if step
                    else jnp.zeros((bsz, taps - 1, width), jnp.float32))
            window = jnp.concatenate([ring, xbc], axis=1)  # [b, taps - 1 + n, width]
            mixed = jax.nn.silu(sum(conv[j] * window[:, j:j + n] for j in range(taps)) + conv_bias)
            xs, b, c = jnp.split(mixed, [inner, inner + g * n_state], axis=-1)
            xs = xs.reshape(bsz, n, h, p)
            b, c = (t.reshape(bsz, n, g, n_state) for t in (b, c))
            if step:
                operands = ssm_step_operands(xs[:, 0], dt[:, 0], a, skip)
        if step:
            with jax.named_scope("ssm_step"):
                y, state = ssm_step(cache[decode_cache.STATE], *operands, b[:, 0], c[:, 0])
        else:
            with jax.named_scope("ssm_chunk"):
                y, state = ssm_chunked(xs, dt, a, b, c, skip, chunk=self.chunk)
                state = decode_cache.pack_state(state)
        new_cache = None
        if cache is not None:
            new_cache = {**cache, decode_cache.STATE: state,
                         decode_cache.CONV: window[:, n:].astype(cache[decode_cache.CONV].dtype),
                         decode_cache.INDEX: cache[decode_cache.INDEX] + n}
        with jax.named_scope("ssm_proj"):
            y = (y.reshape(bsz, n, inner) * jax.nn.silu(z)).reshape(bsz, n, g, inner // g)
            y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + self.norm_eps)
            y = (y.reshape(bsz, n, inner) * gain).astype(self.dtype)
            return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                            param_dtype=self.param_dtype, name="to_out")(y), new_cache


def cca_tail_dim(heads: int, kv_heads: int, dim_head: int) -> int:
    """Columns of a convolved layer's `tail` in the cache: the last position's
    c and c' (`ConvLatentAttention`), and the shifted half of its v'."""
    return (2 * (heads + kv_heads) + kv_heads // 2) * dim_head


def _a_position_before(t, last):
    """t [B, n, ...] a position later: row i holds t[:, i - 1], row 0 `last`
    [B, ...] (the position before the chunk's first: zeros, or a cache's tail)."""
    return jnp.concatenate([last.reshape(t[:, :1].shape).astype(t.dtype), t[:, :-1]], axis=1)


def _shifted_values(vf, last, now: int):
    """A convolved layer's values from its value projection vf [B, n, K d]: the
    first `now` columns (K / 2 heads) this position's, the rest the position
    before's."""
    return jnp.concatenate([vf[..., :now], _a_position_before(vf[..., now:], last)], axis=-1)


def _qk_mean(qh, kh):
    """(m_q [B, n, H, d], m_k [B, n, K, d]) of the latents before the
    convolutions, qh [B, n, H, d] and kh [B, n, K, d]: m_q[h] the mean of q~[h]
    and its group's k~, m_k[j] the mean of its group's m_q."""
    b, n, h, dh = qh.shape
    per = h // kh.shape[2]
    m_q = (qh + jnp.repeat(kh, per, axis=2)) / 2
    return m_q, m_q.reshape(b, n, h // per, per, dh).mean(3)


def _taps_init(key, shape, dtype=jnp.float32):
    """A two-tap depthwise convolution that starts as the identity."""
    return jnp.zeros(shape, dtype).at[1].set(1)


class ConvLatentAttention(nn.Module):
    """Causal attention in a COMPRESSED latent whose q and k pass two causal
    convolutions over the sequence (compressed convolutional attention,
    arXiv:2510.04476, as `model_type: zaya` builds it): `heads` query heads over
    `kv_heads` K/V heads of `dim_head`, all narrower than the stream (heads x
    dim_head < dim), so the cache holds 2 x kv_heads x dim_head numbers a
    position. With u the normed input and anything at t - 1 < 0 zero:

        q~ | k~ | v' = u W_qkv            [H d | K d | K d], no bias
        v_t = [v'_t, the first K / 2 heads ; v'_(t-1), the last K / 2]
        c = [q~ ; k~]                      (H + K) d channels, H + K heads
        c'_t = b0 + w0[1] c_t + w0[0] c_(t-1)                       depthwise
        c''_t[g] = b1[g] + c'_t[g] W1[1, g] + c'_(t-1)[g] W1[0, g]  a head a group
        m_q[h] = (q~[h] + k~[h // P]) / 2;  m_k[j] = mean of its group's m_q
        q[h] = unit(c''[h] + m_q[h]);  k[j] = tau[j] unit(c''[H + j] + m_k[j])
                                           unit(t) = t / sqrt(mean(t^2) + eps)
        rotate-half over the tables' columns (a partial rotary: the first of
        each head), causal softmax at 1 / sqrt(d) over grouped heads, W_out

    everything from `v_t` to `k[j]` under the scope `cca_mix`, in float32 on
    c and c' rounded to the compute dtype (what the cache's tail holds of them).

    Uncached, and for a chunk that STARTS its rows' sequences (`start`: a
    prefill, written from position 0), the chunk attends itself: the flash
    kernel from `AUTO_FLASH_MIN_SEQ` tokens under `attn_impl="auto"`, dense
    below. Any other cached chunk goes on from each row's own `index`: the
    position before its first is the cache's `tail` (models/decode_cache.py,
    kind `cca`: c, c' and the shifted half of v' of the row's last position),
    its K/V are written along the row's lanes, and it attends the row's live
    positions: a one-token step through the kernel `decode_grouped`
    (ops/grouped_decode.py), a longer chunk (a prefill in chunks) through
    XLA's product over the whole leaf, both under `global_attend`. Either way
    the chunk's last position is the new tail.

    Parameters: `to_qkv`, `to_out`, `conv0` [2, C] and `conv1` [2, H + K, d, d]
    in `param_dtype`; `conv0_bias`, `conv1_bias` [C] and `tau` [K] float32.
    """

    dim: int
    seq_len: int
    heads: int
    kv_heads: int
    dim_head: int
    norm_eps: float = 1e-5
    attn_impl: str = "auto"
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, key_mask=None, rotary=None, cache=None, deterministic=True,
                 rotary_cs=None, start=False):
        assert key_mask is None and rotary is None, "a convolved layer is causal and unpadded"
        _known_impl(self.attn_impl)
        b, n, _ = x.shape
        h, hkv, dh = self.heads, self.kv_heads, self.dim_head
        assert h % hkv == 0 and hkv % 2 == 0, f"{h} query heads over {hkv} K/V heads in two halves"
        per, groups, width, now = h // hkv, h + hkv, (h + hkv) * dh, (hkv - hkv // 2) * dh
        f32 = jnp.float32
        qkv = nn.Dense(width + hkv * dh, use_bias=False, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="to_qkv")(x)
        conv0 = self.param("conv0", _taps_init, (2, width), self.param_dtype).astype(f32)
        conv0_bias = self.param("conv0_bias", nn.initializers.zeros, (width,))
        conv1 = self.param("conv1", nn.initializers.lecun_normal(), (2, groups, dh, dh),
                           self.param_dtype).astype(f32)
        conv1_bias = self.param("conv1_bias", nn.initializers.zeros, (width,))
        tau = self.param("tau", nn.initializers.ones, (hkv,))
        resumed = cache is not None and not start
        index = None if cache is None else cache[decode_cache.INDEX]
        with jax.named_scope("cca_mix"):
            c, vf = qkv[..., :width], qkv[..., width:]
            if resumed:  # the position before the chunk's first: the row's tail
                last = jnp.split(cache[decode_cache.TAIL], [width, 2 * width], axis=-1)
            else:
                last = [jnp.zeros((b, w), self.dtype) for w in (width, width, hkv * dh - now)]
            v = _shifted_values(vf, last[2], now)
            c32 = c.astype(f32)
            c1 = (conv0_bias + conv0[1] * c32
                  + conv0[0] * _a_position_before(c32, last[0])).astype(self.dtype)
            # float32 operands that hold compute-dtype numbers: the chip's one
            # pass multiplies them exactly and sums in float32 (the CPU has no
            # bf16 x bf16 = f32 product over a batch of groups)
            c1g = c1.reshape(b, n, groups, dh).astype(f32)
            mix = lambda t, w: jnp.einsum("bngi,gio->bngo", t, w)
            c2 = (conv1_bias.reshape(groups, dh) + mix(c1g, conv1[1])
                  + mix(_a_position_before(c1g, last[1]), conv1[0]))
            m_q, m_k = _qk_mean(c32[..., :h * dh].reshape(b, n, h, dh),
                                c32[..., h * dh:].reshape(b, n, hkv, dh))
            unit = lambda t: t * lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + self.norm_eps)
            q = unit(c2[:, :, :h] + m_q).astype(self.dtype)
            k = (unit(c2[:, :, h:] + m_k) * tau[:, None]).astype(self.dtype)
            tail = jnp.concatenate([c[:, -1], c1[:, -1], vf[:, -1, now:]], axis=-1)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v.reshape(b, n, hkv, dh)))
        if rotary_cs is not None:
            with jax.named_scope("rotary"):
                if resumed:  # [B, 1, n, rot], over the heads: each row's own positions
                    at = index[:, None] + jnp.arange(n, dtype=index.dtype)
                    cos, sin = (jnp.take(t, at, axis=0, mode="clip")[:, None] for t in rotary_cs)
                else:
                    cos, sin = (t[:n] for t in rotary_cs)
                q, k = apply_rotary_half(cos, sin, q), apply_rotary_half(cos, sin, k)

        new_cache = None
        if cache is not None:
            written = decode_cache.write_rows(cache, {"k": k, "v": v}, start)
            new_cache = {**cache, **written, decode_cache.TAIL: tail.astype(
                cache[decode_cache.TAIL].dtype),
                decode_cache.INDEX: jnp.full_like(index, n) if start else index + n}
        if resumed:
            ck, cv = written["k"], written["v"]
            q = q.reshape(b, hkv, per * n, dh)  # a K/V head's group, position fastest
            with jax.named_scope("global_attend"):
                if n == 1:
                    out = grouped_decode_attention(q, ck, cv, index + n, n=n)
                else:
                    at = jnp.tile(index[:, None] + jnp.arange(n, dtype=index.dtype), (1, per))
                    live = jnp.arange(ck.shape[2], dtype=index.dtype) <= at[:, None, :, None]
                    out = dense_attention(q, ck, cv, mask=live)
            out = out.reshape(b, h, n, dh)
        elif self.attn_impl == "flash" or (self.attn_impl == "auto" and n >= AUTO_FLASH_MIN_SEQ):
            out = flash_attention(q, k, v, causal=True)
        else:
            k, v = (jnp.repeat(t, per, axis=1) for t in (k, v))
            out = dense_attention(q, k, v, mask=jnp.tril(jnp.ones((n, n), bool))[None, None])
        out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
        return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="to_out")(out), new_cache
