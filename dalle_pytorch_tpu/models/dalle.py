"""DALL-E: joint text+image autoregressive token transformer.

TPU-native re-design of the reference `DALLE`
(`/root/reference/dalle_pytorch/dalle_pytorch.py:354-707`). Functional
differences from the reference's torch design, on purpose:

  * the frozen VAE is NOT owned by this module. JAX separates parameters
    from code, so the train/generate pipelines compose
    `vae.get_codebook_indices` / `vae.decode` (under `stop_gradient`) with
    this module explicitly — the better TPU pattern is precomputing image
    tokens offline anyway. The constructor takes the VAE's geometry
    (`num_image_tokens`, `image_fmap_size`) instead of the model.
  * generation is a `lax.scan` over positions (see `generate_images`), not
    a Python loop.

Semantics preserved (with reference lines):
  * per-position unique padding tokens for text (`:389,606-609`): token id 0
    at text position p becomes id num_text_tokens_base + p; the embedding
    table is extended by text_seq_len ids;
  * <bos> = id 0 prepended (`:612`), sequence truncated to
    text_seq_len + image_seq_len (`:644-646`);
  * text/image logits range masks and the fork's inverse-rotated mask
    (`:450-464,662-675`);
  * classifier-free-guidance null conditioning: zero out text ids with
    probability null_cond_prob (`:600-604`), two-forward blend at sampling
    (`:575-585`);
  * "stable" tricks: 0.1x + 0.9 stop_grad(x) input anchor (`:648-650`) and
    DivideMax output norm (`:657-658`);
  * split text/image cross-entropy with configurable coefficients
    (`:693-706`), including the fork's inverse (image->text) objective and
    its 3-token sequence-accuracy metric (`:697-699`). For the inverse mode
    the reference splits the loss at `text_seq_len`, which equals the
    image/text boundary only when image_seq_len == text_seq_len (the fork's
    experimental configs); we split at the actual boundary `image_seq_len`.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.transformer import LAYER_RESIDUALS, Transformer, DivideMax
from dalle_pytorch_tpu.obs import scopes
from dalle_pytorch_tpu.obs.tracing import host_span
from dalle_pytorch_tpu.ops.sampling import top_k_filter, gumbel_sample

NEG_MASK_VALUE = -float(np.finfo(np.float32).max)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy with integer labels, fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


class AxialPositionalEmbedding(nn.Module):
    """Row+col additive positional embedding over a 2-D grid, flattened.

    Equivalent of the reference's AxialPositionalEmbedding dependency
    (`dalle_pytorch.py:392`).
    """

    dim: int
    row: int
    col: int

    @nn.compact
    def __call__(self, n: int) -> jnp.ndarray:
        rows = self.param("rows", nn.initializers.normal(1.0), (self.row, 1, self.dim))
        cols = self.param("cols", nn.initializers.normal(1.0), (1, self.col, self.dim))
        pos = (rows + cols).reshape(self.row * self.col, self.dim)
        return pos[:n]


class DALLE(nn.Module):
    dim: int
    depth: int
    num_image_tokens: int
    image_fmap_size: int
    num_text_tokens: int = 10000  # base count, before unique-pad extension
    text_seq_len: int = 256
    heads: int = 8
    dim_head: int = 64
    reversible: bool = False
    reversible_impl: str = "remat"
    # what a remat layer keeps for its backward (`resolve_remat_policy` has
    # the ladder and its bytes): by default the flash kernels' residuals and
    # the feed-forward's two products, B x N x ((5 + 2 x ff_mult) x dim x 2
    # + heads x 4) bytes a layer (a depth-64 trunk keeps 64 layers' worth);
    # "flash_residuals" keeps the kernels' alone, None or "nothing_saveable"
    # the layer's input alone, for a deep trunk or a stack at its memory's edge
    remat_policy: Optional[str] = LAYER_RESIDUALS
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Optional[Sequence[str]] = None
    loss_img_weight: float = 7.0  # upstream knob; default img_loss_coeff
    stable: bool = False
    sandwich_norm: bool = False
    shift_tokens: bool = True
    rotary_emb: bool = True
    shared_attn_ids: Optional[Sequence[int]] = None
    shared_ff_ids: Optional[Sequence[int]] = None
    share_input_output_emb: bool = False
    # fork's multi-objective coefficients (`config/config.yaml:21-24`).
    # img_loss_coeff=None defaults to loss_img_weight, making the upstream
    # knob `(loss_text + w*loss_img)/(w+1)` (`dalle_pytorch.py:702-706`) work.
    text_loss_coeff: float = 1.0
    img_loss_coeff: Optional[float] = None
    text_loss_coeff_inv: float = 7.0
    img_loss_coeff_inv: float = 1.0
    attn_impl: str = "auto"  # "dense" | "flash" | "ring" | "auto"
    sp_mesh: Any = None  # Mesh with "sp" axis for attn_impl="ring"
    # trainer mesh handed down to the uncached flash kernel
    # (models/attention.py `train_mesh`): set by training/pipeline.py when
    # the step is partitioned over more than one device
    train_mesh: Any = None
    # serving mesh handed down to the cached flash-decode dispatch
    # (models/attention.py): set by the sharded continuous engine so the
    # Pallas kernel splits per head over `decode_heads_axis` — the same
    # axis the engine's KV-cache shardings use
    decode_mesh: Any = None
    decode_heads_axis: str = "tp"
    # decode-time policy-sparse KV tile width (None = DECODE_SPARSE_BLOCK,
    # models/attention.py): the serving engine clones the model with it
    # under --decode_sparsity=policy so kernel tile boundaries and the
    # host-derived block bitmaps agree; the bitmaps themselves ride the
    # cache pytree as traced data (policy flips never recompile)
    decode_sparse_block: Optional[int] = None
    # KV-cache storage dtype for the serving/decode caches: None keeps
    # K/V at `dtype` (bit-identical legacy behavior); "int8" stores
    # quantized pages with per-(position, head) fp32 scales, dequantized
    # inside the decode kernels (ops/pallas_decode.py)
    kv_dtype: Any = None
    # layer executor: "unrolled" | "scan" (one compiled layer body,
    # ~depth× smaller program; see models/transformer.py docstring)
    executor: str = "unrolled"
    # vocab-chunked CE for the forward objective: avoids materializing
    # [B, N, total_tokens] logits (ops/losses.py)
    fused_ce: bool = False
    dtype: Any = jnp.float32

    @property
    def total_text_tokens(self) -> int:
        return self.num_text_tokens + self.text_seq_len

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size**2

    @property
    def total_seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        return self.total_text_tokens + self.num_image_tokens

    def transformer_kwargs(self) -> dict:
        """Trunk Transformer constructor args — pure config math, usable
        on an UNBOUND DALLE too (e.g. to rebuild the trunk module for
        `pipeline_trunk_apply` outside this module's apply)."""
        return dict(
            dim=self.dim,
            depth=self.depth,
            seq_len=self.total_seq_len,
            causal=True,
            heads=self.heads,
            dim_head=self.dim_head,
            attn_dropout=self.attn_dropout,
            ff_dropout=self.ff_dropout,
            attn_types=self.attn_types,
            image_fmap_size=self.image_fmap_size,
            stable=self.stable,
            sandwich_norm=self.sandwich_norm,
            shift_tokens=self.shift_tokens,
            rotary_emb=self.rotary_emb,
            shared_attn_ids=self.shared_attn_ids,
            shared_ff_ids=self.shared_ff_ids,
            reversible=self.reversible,
            reversible_impl=self.reversible_impl,
            remat_policy=self.remat_policy,
            attn_impl=self.attn_impl,
            sp_mesh=self.sp_mesh,
            decode_mesh=self.decode_mesh,
            train_mesh=self.train_mesh,
            decode_heads_axis=self.decode_heads_axis,
            decode_sparse_block=self.decode_sparse_block,
            executor=self.executor,
            dtype=self.dtype,
        )

    def setup(self):
        self.text_emb = nn.Embed(self.total_text_tokens, self.dim, dtype=self.dtype)
        self.image_emb = nn.Embed(self.num_image_tokens, self.dim, dtype=self.dtype)

        if not self.rotary_emb:
            self.text_pos_emb = nn.Embed(self.text_seq_len + 1, self.dim, dtype=self.dtype)
            self.image_pos_emb = AxialPositionalEmbedding(
                self.dim, self.image_fmap_size, self.image_fmap_size
            )

        self.transformer = Transformer(**self.transformer_kwargs())

        if self.stable:
            self.norm_by_max = DivideMax(axis=-1)

        self.logits_norm = nn.LayerNorm(dtype=self.dtype)
        if not self.share_input_output_emb:
            self.logits_dense = nn.Dense(self.total_tokens, dtype=self.dtype)
        else:
            self.logits_bias = self.param(
                "logits_bias", nn.initializers.zeros, (self.total_tokens,)
            )

        # logits-range masks (reference `:450-464`) are computed on the fly
        # from iotas in _logits_blocked — a [total_seq, total_tokens] bool
        # constant would bake ~20MB into the executable for nothing.

    def _logits_blocked(self, seq_len: int, inverse: bool) -> jnp.ndarray:
        """[seq_len, total_tokens] bool, True = BLOCKED (reference `:450-464`).

        Text positions may only emit text-vocab ids and image positions
        image-vocab ids; `inverse` rotates the rows by text_seq_len since
        the image occupies the front of the sequence (`:463`).
        """
        rows = jnp.arange(seq_len)
        if inverse:
            rows = (rows + self.text_seq_len) % self.total_seq_len
        vocab = jnp.arange(self.total_tokens)[None, :]
        is_text_row = (rows < self.text_seq_len)[:, None]
        is_text_vocab = vocab < self.total_text_tokens
        return is_text_row != is_text_vocab

    def _head_input(self, out: jnp.ndarray) -> jnp.ndarray:
        """What the logits head multiplies: `out`, normalized."""
        if self.stable:
            out = self.norm_by_max(out)
        return self.logits_norm(out)

    def to_logits(self, out: jnp.ndarray) -> jnp.ndarray:
        out = self._head_input(out)
        if self.share_input_output_emb:
            kernel, bias = self._logits_kernel()
            return out @ kernel.astype(out.dtype) + bias.astype(out.dtype)
        return self.logits_dense(out)

    def embed_text(self, text: jnp.ndarray, null_cond_prob: float = 0.0):
        """Unique-pad remap + <bos>; returns (padded_ids [B, T+1], embeddings)."""
        b = text.shape[0]
        assert text.shape[-1] == self.text_seq_len, (
            f"text length {text.shape[-1]} != text_seq_len {self.text_seq_len}"
        )
        if null_cond_prob > 0:
            rng = self.make_rng("null_cond")
            null = jax.random.uniform(rng, (b, 1)) < null_cond_prob
            text = jnp.where(null, 0, text)

        text_range = jnp.arange(self.text_seq_len) + (
            self.total_text_tokens - self.text_seq_len
        )
        text = jnp.where(text == 0, text_range, text)
        text = jnp.pad(text, ((0, 0), (1, 0)))  # <bos> = 0

        tokens = self.text_emb(text)
        if not self.rotary_emb:
            tokens = tokens + self.text_pos_emb(jnp.arange(text.shape[1]))
        return text, tokens

    def _fused_forward_loss(self, out, text, image, seq_len):
        """Forward-mode split CE via the vocab-chunked kernel — identical
        numerics to the dense path (tests/test_dalle.py parity) without
        the [B, N, V] logits. Not measured on the chip: `flagship.train`
        runs the dense loss (`_split_loss`; PERF.md section 5)."""
        from dalle_pytorch_tpu.ops.losses import chunked_masked_ce, split_weighted_mean

        h, kernel, bias, offsetted_image = self._fused_head(out, image)
        labels = jnp.concatenate([text[:, 1:], offsetted_image], axis=1)
        split = self.text_seq_len
        row_is_text = jnp.arange(seq_len) < self.text_seq_len
        ct = self.text_loss_coeff
        ci = self.loss_img_weight if self.img_loss_coeff is None else self.img_loss_coeff
        with jax.named_scope("loss"):
            per_pos = chunked_masked_ce(
                h, kernel, bias, labels,
                row_is_text=row_is_text,
                num_text_vocab=self.total_text_tokens,
            )
            loss = split_weighted_mean(per_pos, split, ct, ci)
        return loss, None

    def _logits_kernel(self):
        """(kernel [D, V], bias [V] or None) of the logits head, shared by
        both fused-CE paths."""
        if self.share_input_output_emb:
            kernel = jnp.concatenate(
                [self.text_emb.embedding, self.image_emb.embedding], axis=0
            ).T
            return kernel, self.logits_bias
        p = self.variables["params"]["logits_dense"]
        return p["kernel"], p.get("bias")

    def _logits_block(self, h, image_vocab: bool):
        """Logits of the rows `h` over the text columns alone, or over the
        image columns alone: `to_logits`' product and precision, on the one
        block of the kernel the logits mask leaves a row."""
        split = self.total_text_tokens
        cols = slice(split, None) if image_vocab else slice(None, split)
        if self.share_input_output_emb:
            emb = self.image_emb if image_vocab else self.text_emb
            kernel, bias = emb.embedding.T.astype(h.dtype), self.logits_bias
        else:
            kernel, bias = self._logits_kernel()
            # cast whole, then cut: XLA casts the kernel once for both blocks
            # either way, and a cast it moves there itself loses its name
            kernel = kernel.astype(h.dtype)[:, cols]
        logits = h @ kernel
        return logits if bias is None else logits + bias[cols].astype(h.dtype)

    def _fused_head(self, out, image):
        """Shared fused-CE prologue: normalized head input + logits kernel
        + vocab-offset image labels. Keeping it in one place keeps the two
        objectives' numerics in lockstep with the dense path."""
        assert image is not None, "when training, image must be supplied"
        kernel, bias = self._logits_kernel()
        return self._head_input(out), kernel, bias, image + self.total_text_tokens

    def _fused_inverse_loss(self, out, text, image, seq_len):
        """Inverse-mode (image->text) split CE via the vocab-chunked kernel.

        Numerics match the dense inverse path (tests/test_dalle.py parity):
        image-first row layout, the fork's drop-last-image-position quirk
        (`:686-687`), inverse loss coefficients, and the 3-token sequence
        accuracy — the argmax needs real logits, but only for THREE text
        positions, so a tiny [B, 3, V] dense block replaces the full
        [B, N, V] materialization."""
        from dalle_pytorch_tpu.ops.losses import chunked_masked_ce, split_weighted_mean

        h, kernel, bias, offsetted_image = self._fused_head(out, image)
        labels = jnp.concatenate([offsetted_image[:, 1:], text], axis=1)
        split = self.image_seq_len
        # image-first layout: rows >= image_seq_len are text rows
        row_is_text = jnp.arange(seq_len) >= split
        ci, ct = self.img_loss_coeff_inv, self.text_loss_coeff_inv
        with jax.named_scope("loss"):
            per_pos = chunked_masked_ce(
                h, kernel, bias, labels,
                row_is_text=row_is_text,
                num_text_vocab=self.total_text_tokens,
            )
            loss = split_weighted_mean(
                per_pos, split, ci, ct, drop_last_of_first=True
            )

        # 3-token sequence accuracy (`:697-699`) on dense logits for rows
        # [split, split+3) only — text rows, where every image-vocab column
        # is blocked anyway, so only the text-vocab kernel slice is needed
        h3 = h[:, split : split + 3]
        logits3 = jnp.einsum(
            "bnd,dv->bnv", h3,
            kernel[:, : self.total_text_tokens].astype(h3.dtype),
            preferred_element_type=jnp.float32,
        )
        if bias is not None:
            logits3 = logits3 + bias[: self.total_text_tokens].astype(jnp.float32)
        pred3 = jnp.argmax(logits3, axis=-1)
        accuracy = jnp.mean(
            jnp.all(
                pred3 == labels[:, split : split + 3], axis=-1
            ).astype(jnp.float32)
        )
        return loss, accuracy

    def __call__(
        self,
        text: jnp.ndarray,
        image: Optional[jnp.ndarray] = None,
        return_loss: bool = False,
        inverse_mapping: bool = False,
        reverse_model: bool = False,
        null_cond_prob: float = 0.0,
        deterministic: bool = True,
        trunk_fn=None,
    ):
        """text: [B, text_seq_len] int ids; image: [B, <=image_seq_len] codebook ids.

        Raw-pixel image input is handled by the pipeline (frozen VAE encode)
        before this call — see module docstring.

        `trunk_fn` (optional) substitutes the transformer trunk:
        embeddings -> trunk_fn(tokens) -> head. Used to run the trunk
        under a different executor from OUTSIDE the module — e.g.
        pipeline-parallel via `transformer.make_pipeline_trunk` (build
        the closure OUTSIDE apply; flax intercepts module construction
        inside a parent scope) with the trunk params sharded over a pp
        mesh (see tests/test_gpipe.py). Deterministic forward only.
        """
        text, tokens = self.embed_text(text, null_cond_prob)

        if image is not None and image.shape[1] > 0:
            image_emb = self.image_emb(image)
            if not self.rotary_emb:
                image_emb = image_emb + self.image_pos_emb(image_emb.shape[1])
            if inverse_mapping:
                tokens = jnp.concatenate([image_emb, tokens], axis=1)
            else:
                tokens = jnp.concatenate([tokens, image_emb], axis=1)

        seq_len = tokens.shape[1]
        if seq_len > self.total_seq_len:  # drop the final token's input slot
            tokens = tokens[:, : self.total_seq_len]
            seq_len = self.total_seq_len

        if self.stable:
            alpha = 0.1
            tokens = tokens * alpha + jax.lax.stop_gradient(tokens) * (1 - alpha)

        if trunk_fn is not None:
            assert not reverse_model, "trunk_fn callers own the layer order"
            # loud, like the reverse_model assert: the pipeline block is
            # hard-wired deterministic, so dropout would silently vanish.
            # This is a DESIGN CONSTRAINT of the pp trunk (documented at
            # make_pipeline_trunk): train with attn_dropout=ff_dropout=0
            # under pp, or use dp/fsdp/tp for dropout training.
            assert deterministic, (
                "trunk_fn (pipeline parallelism) supports deterministic "
                "execution only — set attn_dropout=ff_dropout=0, or train "
                "under dp/fsdp/tp instead"
            )
            out = trunk_fn(tokens)
        else:
            out = self.transformer(
                tokens, reverse_model=reverse_model, deterministic=deterministic
            )

        if return_loss and self.fused_ce and not self.is_initializing():
            # vocab-chunked CE: never materializes [B, N, V] logits
            # (ops/losses.py); init takes the dense path. The inverse
            # objective's 3-token accuracy argmax uses a [B, 3, V] dense
            # block instead of full logits.
            if inverse_mapping:
                return self._fused_inverse_loss(out, text, image, seq_len)
            return self._fused_forward_loss(out, text, image, seq_len)

        if return_loss:
            return self._split_loss(out, text, image, inverse_mapping)

        logits = self.to_logits(out)
        lmask = self._logits_blocked(seq_len, inverse_mapping)[None]
        with jax.named_scope("logits_mask"):
            return jnp.where(lmask, NEG_MASK_VALUE, logits.astype(jnp.float32))

    def _split_loss(self, out, text, image, inverse_mapping):
        """Split text/image cross-entropy over the logits the mask leaves
        alive (`_logits_blocked`): a text row's over the text columns, an
        image row's over the image columns, labels local to the block. A
        blocked entry is `exp(NEG_MASK_VALUE - max)` = 0 of its row's sum
        and takes no gradient, so this is the loss over the masked
        [B, N, V] logits, which are never made."""
        assert image is not None, "when training, image must be supplied"
        assert image.shape[1] == self.image_seq_len, (
            f"the loss takes all {self.image_seq_len} image tokens, "
            f"got {image.shape[1]}"
        )
        if self.is_initializing():
            self.to_logits(out[:, :1])  # makes the head's parameters
        h = self._head_input(out)
        if inverse_mapping:
            # image first, then text: labels rotate image forward one step and
            # append the full bos-padded text (`:686-687`)
            split = self.image_seq_len  # see module docstring re: fork's quirk
            image_rows, image_labels = slice(None, split - 1), image[:, 1:]
            text_rows = slice(split, None)
            ct, ci = self.text_loss_coeff_inv, self.img_loss_coeff_inv
        else:
            split = self.text_seq_len
            text_rows = slice(None, split)
            image_rows, image_labels = slice(split, None), image
            ct = self.text_loss_coeff
            ci = self.loss_img_weight if self.img_loss_coeff is None else self.img_loss_coeff
        text_labels = text[:, 1:]

        with jax.named_scope("logits_text"):
            logits_text = self._logits_block(h[:, text_rows], image_vocab=False)
        with jax.named_scope("logits_image"):
            logits_image = self._logits_block(h[:, image_rows], image_vocab=True)
        with jax.named_scope("loss"):
            loss_text = cross_entropy(logits_text, text_labels)
            loss_img = cross_entropy(logits_image, image_labels)
            loss = (ct * loss_text + ci * loss_img) / (ct + ci)
            accuracy = None
            if inverse_mapping:
                # text ids start at 0: the block's argmax is the global one
                pred3 = jnp.argmax(logits_text[:, :3], axis=-1)
                accuracy = jnp.mean(
                    jnp.all(pred3 == text_labels[:, :3], axis=-1).astype(jnp.float32)
                )
        return loss, accuracy

    # ------------------------------------------------ cached decode methods

    def decode_prefill(self, text: jnp.ndarray, cache: dict):
        """Run the text prefix (bos + text) through the transformer, filling
        the decode cache. Returns (last-position logits [B, V], cache) — the
        logits for image slot 0."""
        _, tokens = self.embed_text(text, null_cond_prob=0.0)
        out, cache = self.transformer(tokens, cache=cache)
        logits = self.to_logits(out[:, -1:])  # only the last row is needed
        return logits[:, 0].astype(jnp.float32), cache

    def decode_image_step(self, img_token: jnp.ndarray, image_pos, cache: dict):
        """Feed one sampled image token (grid index `image_pos`, traced —
        a scalar for lockstep decode or [B] for per-row slot positions);
        returns (next-position logits [B, V], cache)."""
        emb = self.image_emb(img_token[:, None].astype(jnp.int32))
        if not self.rotary_emb:
            table = self.image_pos_emb(self.image_seq_len)
            clipped = jnp.clip(image_pos, 0, self.image_seq_len - 1)
            if jnp.ndim(image_pos) == 1:
                row = jax.vmap(
                    lambda p: jax.lax.dynamic_slice_in_dim(table, p, 1, axis=0)
                )(clipped)  # [B, 1, dim]
                emb = emb + row
            else:
                row = jax.lax.dynamic_slice_in_dim(table, clipped, 1, axis=0)
                emb = emb + row[None]
        out, cache = self.transformer(emb, cache=cache)
        return self.to_logits(out)[:, 0].astype(jnp.float32), cache

    def decode_resume(self, text: jnp.ndarray, image_tokens: jnp.ndarray,
                      image_pos, cache: dict):
        """Teacher-forced re-prefill of prompt + generated image prefix in
        ONE cached forward — the decode-state migration fast path: a row
        resuming at position k pays one parallel prefill instead of k
        sequential decode steps.

        `image_tokens` is the [B, image_seq_len] generated-token buffer
        (zeros beyond each row's prefix), `image_pos` [B] the per-row
        resume positions k. The forward runs the SAME per-position math
        as the incremental path — embeddings as `decode_image_step`,
        batch token-shift (value-equal to the streaming ring shift),
        causal cached attention from position 0 — over the fixed length
        text_len + image_seq_len - 1 (the last image token's K/V is never
        read: decode at the final position attends only below it). K/V
        beyond a row's k is garbage from the zero padding; decode never
        reads past the stamped index and overwrites those positions as it
        advances, the same stale-content argument the slot reuse and
        paging paths already rely on. Shift rings are rebuilt per row at
        the window BELOW text_len + k (`shift_ring_from_prefill_at` via
        the cache's `ring_end` leaf, stripped from the result). Returns
        (pending logits for each row's position k [B, V], cache) — for
        k = 0 this degenerates to exactly `decode_prefill`.
        """
        _, tokens = self.embed_text(text, null_cond_prob=0.0)
        text_len = tokens.shape[1]  # text_seq_len + 1 (<bos>)
        # image tokens 0..image_seq_len-2, embedded exactly as
        # decode_image_step embeds token j at grid position j
        img_tok = image_tokens[:, : self.image_seq_len - 1].astype(jnp.int32)
        img = self.image_emb(img_tok)
        if not self.rotary_emb:
            img = img + self.image_pos_emb(self.image_seq_len)[
                None, : self.image_seq_len - 1
            ]
        seq = jnp.concatenate([tokens, img.astype(tokens.dtype)], axis=1)
        image_pos = jnp.asarray(image_pos, jnp.int32)
        # [B] global resume positions: `shift_with_ring` rebuilds each row's
        # rings below its own (the output cache comes back without the leaf)
        cache = decode_cache.with_side(cache, ring_end=text_len + image_pos)
        out, cache = self.transformer(seq, cache=cache)
        # pending logits for per-row position k live at global position
        # text_len - 1 + k (the output of feeding token k-1; k = 0 reads
        # the last text position, exactly decode_prefill's slot-0 logits)
        sel = jax.vmap(
            lambda o, p: jax.lax.dynamic_slice_in_dim(o, p, 1, axis=0)
        )(out, text_len - 1 + image_pos)  # [B, 1, dim]
        row = self.to_logits(sel)[:, 0].astype(jnp.float32)
        return row, cache


def _init_cache(model: DALLE, batch: int, dtype=None, **store) -> dict:
    """A zeroed decode cache of `model`'s geometry, sized total_seq_len + 1
    so a sampler can uniformly feed every sampled token (the final write
    lands in the spare slot and its logits are discarded). The trunk says
    which layout its executor takes; `store` is `per_row=` / `pages=`."""
    trunk = Transformer(**model.transformer_kwargs(), parent=None)
    return trunk.init_cache(
        batch,
        model.total_seq_len + 1,
        model.dtype if dtype is None else dtype,
        kv_dtype=getattr(model, "kv_dtype", None),
        **store,
    )


def init_decode_cache(model: DALLE, batch: int, dtype=None) -> dict:
    """Fixed-shape lockstep decode cache for `generate_images_cached`."""
    return _init_cache(model, batch, dtype)


def _primed_image_tokens(
    model: DALLE,
    batch: int,
    init_image_tokens: Optional[jnp.ndarray],
    num_init_img_tokens: Optional[int],
):
    """Image-token buffer with the optional priming prefix written in.

    The reference primes generation with the first 43.75% of a source
    image's tokens by default (`dalle_pytorch.py:537-546`). Returns
    (tokens [B, image_seq_len], primed_len).
    """
    image_seq_len = model.image_seq_len
    img_tokens = jnp.zeros((batch, image_seq_len), dtype=jnp.int32)
    primed = 0
    if init_image_tokens is not None:
        primed = (
            int(0.4375 * image_seq_len)
            if num_init_img_tokens is None
            else num_init_img_tokens
        )
        assert primed < image_seq_len
        img_tokens = img_tokens.at[:, :primed].set(init_image_tokens[:, :primed])
    return img_tokens, primed


@functools.lru_cache(maxsize=32)
def _jitted_sampler(fn_builder, model, static_key):
    """One compiled sampler per (entry point, model, sampling params).

    Without this, every `generate_images*` call dispatches its prefill and
    setup ops eagerly — one dispatch per op instead of one per program.

    A builder may carry `_donate_argnums` (the continuous-batching slot
    ops donate their state argument: the caller always replaces its state
    with the return value, and without donation every chunk/prefill/release
    dispatch would keep TWO copies of the whole slot KV cache alive and
    pay a full-cache copy).
    """
    return scopes.remembering(jax.jit(
        fn_builder(model, static_key),
        donate_argnums=getattr(fn_builder, "_donate_argnums", ()),
    ))


def _program(name: str):
    """Name the function a builder returns: `jax.jit` calls the compiled
    module `jit_<name>`, which is what a device trace and the compile cache
    show. One name per builder, set here and nowhere else; the sharded
    engine jits the same builders' functions, so its ladder reads the same."""

    def wrap(builder):
        @functools.wraps(builder)
        def build(model, key):
            fn = builder(model, key)
            fn.__name__ = name
            return fn

        return build

    return wrap


_warned_eager_sampler = False


def _jit_sample(fn_builder, model, static_key, *args):
    try:
        jitted = _jitted_sampler(fn_builder, model, static_key)
    except TypeError:  # unhashable model field (list attn_types, custom mesh)
        global _warned_eager_sampler
        if not _warned_eager_sampler:
            _warned_eager_sampler = True
            import warnings

            warnings.warn(
                "DALLE model is unhashable (list-valued field or custom "
                "sp_mesh?) — sampling falls back to EAGER dispatch, which "
                "is drastically slower on remote devices. Use tuples for "
                "attn_types/shared_*_ids to get the jit-cached sampler.",
                stacklevel=3,
            )
        return fn_builder(model, static_key)(*args)
    with host_span("sample.dispatch", program=jitted.name):
        return jitted(*args)


def generate_images_cached(
    model: DALLE,
    variables,
    rng: jax.Array,
    text: jnp.ndarray,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    cond_scale: float = 1.0,
    init_image_tokens: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
    vae=None,
    vae_params=None,
):
    """KV-cached autoregressive sampling: O(seq) attention per generated
    token instead of `generate_images`' full re-forward (the reference's
    `use_cache=True` path, `dalle_pytorch.py:652-653`, `attention.py:71-76`).

    Prefills the text prefix once, then `lax.scan`s single-token decode
    steps against the fixed-shape cache (KV + token-shift rings).
    Classifier-free guidance (cond_scale != 1) stacks a null-text stream
    along the batch axis — one model call serves both — and blends logits
    per step (`dalle_pytorch.py:575-585`). The whole pipeline (prefill +
    decode scan) runs as ONE jitted program, cached per model/params.

    Pass a `DiscreteVAE` module + its params as `vae`/`vae_params` to
    fuse the pixel decode into the SAME program — returns (tokens,
    pixels) from one dispatch instead of sampling then decoding in two.
    """
    static_key = (filter_thres, temperature, cond_scale, num_init_img_tokens,
                  vae)
    if init_image_tokens is None and vae is None:
        return _jit_sample(
            _cached_sampler_builder, model, static_key, variables, rng, text
        )
    return _jit_sample(
        _cached_sampler_builder, model, static_key,
        variables, rng, text, init_image_tokens, vae_params,
    )


@_program("sample_cached")
def _cached_sampler_builder(model, key):
    filter_thres, temperature, cond_scale, num_init, vae = key

    def fn(variables, rng, text, init_image_tokens=None, vae_params=None):
        toks = _generate_images_cached_impl(
            model, variables, rng, text,
            filter_thres=filter_thres, temperature=temperature,
            cond_scale=cond_scale,
            init_image_tokens=init_image_tokens,
            num_init_img_tokens=num_init,
        )
        if vae is None:
            return toks
        pixels = vae.apply(
            {"params": vae_params}, toks, method=type(vae).decode
        )
        return toks, pixels

    return fn


def _generate_images_cached_impl(
    model: DALLE,
    variables,
    rng: jax.Array,
    text: jnp.ndarray,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    cond_scale: float = 1.0,
    init_image_tokens: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
):
    b = text.shape[0]
    image_seq_len = model.image_seq_len
    use_null = cond_scale != 1.0
    img_tokens, primed = _primed_image_tokens(
        model, b, init_image_tokens, num_init_img_tokens
    )

    def blend(row):
        if not use_null:
            return row
        cond, null = row[:b], row[b:]
        return null + (cond - null) * cond_scale

    if use_null:
        # null conditioning == all-pad text (`:602-604`), stacked on batch
        text = jnp.concatenate([text, jnp.zeros_like(text)], axis=0)
    row, cache = model.apply(
        variables,
        text,
        init_decode_cache(model, text.shape[0]),
        method=DALLE.decode_prefill,
    )

    # image-range logits mask (rows text_seq_len.. of `_logits_mask` are all
    # identical: only image-vocab ids are allowed)
    blocked = jnp.asarray(
        np.arange(model.total_tokens) < model.total_text_tokens
    )[None]

    def step(carry, i):
        img_tokens, cache, row, rng = carry
        with jax.named_scope("rng_split"):
            rng, sample_rng = jax.random.split(rng)
        with jax.named_scope("sample"):
            masked = jnp.where(blocked, NEG_MASK_VALUE, blend(row))
            filtered = top_k_filter(masked, thres=filter_thres)
            sample = gumbel_sample(sample_rng, filtered, temperature=temperature)
            sample = (sample - model.total_text_tokens).astype(jnp.int32)
            prev = jax.lax.dynamic_index_in_dim(img_tokens, i, axis=1, keepdims=False)
            new = jnp.where(i < primed, prev, sample)
            img_tokens = jax.lax.dynamic_update_slice(img_tokens, new[:, None], (0, i))
        feed = jnp.concatenate([new, new], axis=0) if use_null else new
        row, cache = model.apply(
            variables, feed, i, cache, method=DALLE.decode_image_step
        )
        return (img_tokens, cache, row, rng), None

    carry = (img_tokens, cache, row, rng)
    (img_tokens, _, _, _), _ = jax.lax.scan(step, carry, jnp.arange(image_seq_len))
    return img_tokens


def generate_images_cached_batched(
    model: DALLE,
    variables,
    text: jnp.ndarray,
    seeds: jnp.ndarray,
    temperatures: jnp.ndarray,
    keep_k: jnp.ndarray,
    cond_scale: float = 1.0,
    vae=None,
    vae_params=None,
):
    """KV-cached sampling with PER-SAMPLE sampling parameters.

    The serving engine's decode path: one compiled program per
    (model, batch shape, cond_scale), with each batch row carrying its own
    traced `seeds[i]` / `temperatures[i]` / `keep_k[i]` so heterogeneous
    requests coalesce into one fixed-shape dispatch
    (`dalle_pytorch_tpu/serving/engine.py` pads partial batches up to the
    nearest compiled shape and discards the padded rows).

    Row i's RNG stream is derived ONLY from (seeds[i], decode step) — never
    from batch composition or row position — so a request produces
    identical tokens whichever micro-batch it lands in (pinned by
    tests/test_serving_e2e.py). `keep_k` counts logits to KEEP over the
    full vocab row (the engine converts the CLI's fractional `top_k`
    threshold with the same `max(int((1-thres)*V), 1)` rule as
    `top_k_filter`). Like the static-parameter sampler, pass `vae`/
    `vae_params` to fuse pixel decode into the same program.
    """
    static_key = (cond_scale, vae)
    return _jit_sample(
        _batched_sampler_builder, model, static_key,
        variables, text,
        jnp.asarray(seeds, jnp.int32),
        jnp.asarray(temperatures, jnp.float32),
        jnp.asarray(keep_k, jnp.int32),
        vae_params,
    )


@_program("sample_cached_batched")
def _batched_sampler_builder(model, key):
    cond_scale, vae = key

    def fn(variables, text, seeds, temperatures, keep_k, vae_params=None):
        toks = _generate_images_cached_batched_impl(
            model, variables, text, seeds, temperatures, keep_k,
            cond_scale=cond_scale,
        )
        if vae is None:
            return toks
        pixels = vae.apply(
            {"params": vae_params}, toks, method=type(vae).decode
        )
        return toks, pixels

    return fn


def _generate_images_cached_batched_impl(
    model: DALLE,
    variables,
    text: jnp.ndarray,
    seeds: jnp.ndarray,
    temperatures: jnp.ndarray,
    keep_k: jnp.ndarray,
    cond_scale: float = 1.0,
):
    from dalle_pytorch_tpu.ops.sampling import (
        top_k_filter_per_row, gumbel_sample_per_row, per_row_step_keys,
    )

    b = text.shape[0]
    image_seq_len = model.image_seq_len
    use_null = cond_scale != 1.0
    img_tokens = jnp.zeros((b, image_seq_len), dtype=jnp.int32)

    def blend(row):
        if not use_null:
            return row
        cond, null = row[:b], row[b:]
        return null + (cond - null) * cond_scale

    if use_null:
        text = jnp.concatenate([text, jnp.zeros_like(text)], axis=0)
    row, cache = model.apply(
        variables,
        text,
        init_decode_cache(model, text.shape[0]),
        method=DALLE.decode_prefill,
    )

    blocked = jnp.asarray(
        np.arange(model.total_tokens) < model.total_text_tokens
    )[None]

    def step(carry, i):
        img_tokens, cache, row = carry
        with jax.named_scope("sample"):
            masked = jnp.where(blocked, NEG_MASK_VALUE, blend(row))
            filtered = top_k_filter_per_row(masked, keep_k)
            # (seed, image position) keyed RNG — shared derivation with the
            # continuous-batching chunk decode (ops/sampling.py), so the two
            # engines sample bit-identical streams per row
            step_keys = per_row_step_keys(seeds, jnp.full((b,), i, jnp.int32))
            sample = gumbel_sample_per_row(step_keys, filtered, temperatures)
            sample = (sample - model.total_text_tokens).astype(jnp.int32)
            img_tokens = jax.lax.dynamic_update_slice(img_tokens, sample[:, None], (0, i))
        feed = jnp.concatenate([sample, sample], axis=0) if use_null else sample
        row, cache = model.apply(
            variables, feed, i, cache, method=DALLE.decode_image_step
        )
        return (img_tokens, cache, row), None

    carry = (img_tokens, cache, row)
    (img_tokens, _, _), _ = jax.lax.scan(step, carry, jnp.arange(image_seq_len))
    return img_tokens


# ------------------------------------------------ continuous batching (slots)
#
# The micro-batch sampler above flushes a batch and runs the ENTIRE
# image_seq_len decode scan before anything else can touch the device; a
# request arriving just after a flush waits a whole pass for its first
# token. The slot API below instead keeps ONE persistent fixed-shape decode
# state of `max_batch` cache slots, advanced in chunks of K tokens by one
# jitted step; a host-side allocator (serving/engine.py) admits new prompts
# into free slots (batched prefill-into-slots) and retires finished rows at
# chunk boundaries — vLLM-style token-boundary admission, with the same
# fixed-shape-compilation discipline as the rest of the serving stack
# (three compiled slot programs: prefill at batch `prefill_batch`, chunk
# at max_batch, slot release — R pending admissions cost
# ceil(R / prefill_batch) dispatches, not R).
#
# Per-row state threaded through the stack: per-slot cache `index`
# (models/attention.py per-row cached path), per-slot token-shift ring
# positions (ops/shift.py), per-slot image position / active mask /
# seed / temperature / top-k here. RNG is keyed by (seed, image position)
# via ops/sampling.py:per_row_step_keys — the same derivation the
# micro-batch sampler uses — so a request's tokens are bit-identical
# whether served alone, padded, or admitted mid-flight (pinned by
# tests/test_continuous.py).


def init_slot_state(model: DALLE, max_batch: int, dtype=None) -> dict:
    """Persistent decode state for `max_batch` cache slots.

    Free slots hold zeros; `prefill_into_slots` overwrites admitted slots
    wholesale (including every cache position, so no state leaks between
    the consecutive occupants of a slot), and `active` gates which rows
    advance in `decode_image_chunk`.
    """
    s = int(max_batch)
    return {
        "cache": _init_cache(model, s, dtype, per_row=True),
        # pending next-position logits per slot (what the next sample
        # draws from; written by prefill, refreshed every decode step)
        "row": jnp.zeros((s, model.total_tokens), jnp.float32),
        "img_tokens": jnp.zeros((s, model.image_seq_len), jnp.int32),
        "img_pos": jnp.zeros((s,), jnp.int32),
        "active": jnp.zeros((s,), jnp.bool_),
        "seeds": jnp.zeros((s,), jnp.int32),
        "temps": jnp.ones((s,), jnp.float32),
        "keep_k": jnp.ones((s,), jnp.int32),
    }


def prefill_into_slots(
    model: DALLE,
    variables,
    state: dict,
    texts: jnp.ndarray,
    slots,
    seeds,
    temperatures,
    keep_ks,
    block_bitmap=None,
):
    """Admit up to R prompts into their cache slots in ONE donated dispatch.

    `texts` is [R, text_seq_len]; `slots`/`seeds`/`temperatures`/`keep_ks`
    are [R] (traced data — ONE compiled program per prefill batch size R
    regardless of which slots are filled). Runs the text prefill at batch R
    — the same `decode_prefill` the micro-batch sampler runs, so per-row
    numerics match the lockstep path bit-for-bit (batch-composition
    invariance is already the serving stack's contract) — and scatters each
    resulting K/V row (+ token-shift rings, pending logits, per-slot
    sampling params) into its slot of the persistent state.

    Fewer than R real prompts: pad by REPEATING a real (slot, prompt) pair —
    the duplicate rows re-write the same slot with identical content, so
    padding costs compute but never correctness (the same trade the
    micro-batch engine makes with its padded batch rungs). Duplicate slots
    among the real rows are the caller's bug.

    `state` is DONATED: its buffers are invalid after the call — always
    replace your reference with the return value (as the slot ops below
    all do). This keeps exactly one slot cache alive instead of two.

    `block_bitmap` ([depth, R, nb] int32) arms decode-sparsity for the
    prefill forward too: masked layers route through the block-sparse
    flash kernel instead of the dense pattern path (text-prefix tiles are
    always live, and text rows under the shipped policies are exactly
    causal). Selects the "sparse"-keyed compiled program.
    """
    texts = jnp.asarray(texts, jnp.int32)
    prefill_batch = int(texts.shape[0])
    args = (
        variables, state, texts,
        jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.asarray(temperatures, jnp.float32), jnp.asarray(keep_ks, jnp.int32),
    )
    if block_bitmap is None:
        return _jit_sample(
            _prefill_slots_builder, model, (prefill_batch,), *args
        )
    return _jit_sample(
        _prefill_slots_builder, model, (prefill_batch, "sparse"),
        *args, jnp.asarray(block_bitmap, jnp.int32),
    )


def _prefill_rows(model, variables, texts, block_bitmap=None):
    """The batch-R text prefill the admission programs run, the same
    `decode_prefill` as the micro-batch sampler's: (pending logits [R, V],
    the fresh cache). `block_bitmap` rides the fresh cache through the
    forward and is stripped from what comes back: the persistent state
    carries no bitmap leaves."""
    cache0 = init_decode_cache(model, texts.shape[0])
    if block_bitmap is not None:
        cache0 = decode_cache.with_side(cache0, block_bitmap=block_bitmap)
    rows, cache_r = model.apply(
        variables, texts, cache0, method=DALLE.decode_prefill
    )
    return rows, decode_cache.without_side(cache_r, decode_cache.BLOCK_BITMAP)


def _resume_rows(model, variables, texts, img_tokens, img_pos):
    """`_prefill_rows` for rows that arrive mid-decode (`decode_resume`)."""
    return model.apply(
        variables, texts, img_tokens, img_pos,
        init_decode_cache(model, texts.shape[0]),
        method=DALLE.decode_resume,
    )


def _admit_rows(model, state, cache, rows, slots, seeds, temperatures, keep_ks,
                img_tokens=None, img_pos=None):
    """The state after admitting R rows into `slots`: the scattered `cache`,
    each row's pending logits, its token buffer (zeros, or a resumed row's
    generated prefix) and its per-slot control state. Padded rows repeat a
    real (slot, value) pair, so whichever duplicate lands last is
    identical."""
    out = dict(state)
    out["cache"] = cache
    row_buf = state["row"]
    tok_buf = state["img_tokens"]
    fresh = img_tokens is None  # a prefill: no tokens yet, position 0
    zero_row = jnp.zeros((1, model.image_seq_len), jnp.int32) if fresh else None
    for r in range(rows.shape[0]):
        row_buf = jax.lax.dynamic_update_slice(
            row_buf, rows[r : r + 1].astype(row_buf.dtype), (slots[r], 0)
        )
        tok_buf = jax.lax.dynamic_update_slice(
            tok_buf, zero_row if fresh else img_tokens[r : r + 1], (slots[r], 0)
        )
    out["row"] = row_buf
    out["img_tokens"] = tok_buf
    out["img_pos"] = state["img_pos"].at[slots].set(0 if fresh else img_pos)
    out["active"] = state["active"].at[slots].set(True)
    out["seeds"] = state["seeds"].at[slots].set(seeds)
    out["temps"] = state["temps"].at[slots].set(temperatures)
    out["keep_k"] = state["keep_k"].at[slots].set(keep_ks)
    return out


@_program("slots_prefill")
def _prefill_slots_builder(model, key):
    del key  # (prefill batch[, "sparse"]): the arguments' shapes say both

    def fn(variables, state, texts, slots, seeds, temperatures, keep_ks,
           *block_bitmap):
        rows, cache_r = _prefill_rows(model, variables, texts, *block_bitmap)
        cache = decode_cache.scatter_rows(state["cache"], cache_r, slots)
        return _admit_rows(
            model, state, cache, rows, slots, seeds, temperatures, keep_ks
        )

    return fn


_prefill_slots_builder._donate_argnums = (1,)  # state


def resume_into_slots(
    model: DALLE,
    variables,
    state: dict,
    texts: jnp.ndarray,
    img_tokens: jnp.ndarray,
    img_pos,
    slots,
    seeds,
    temperatures,
    keep_ks,
):
    """Admit up to R MID-DECODE rows into their cache slots in ONE
    donated dispatch (decode-state migration, serving/migrate.py).

    Like `prefill_into_slots`, but each row arrives with a generated
    image prefix: `img_tokens` [R, image_seq_len] (zeros beyond the
    prefix) and `img_pos` [R] resume positions. `DALLE.decode_resume`
    re-prefills prompt + prefix in one teacher-forced forward — K/V,
    shift rings (per-row window), pending logits and position all land
    exactly where the incremental decode would have left them, so the
    next chunk dispatch continues from position k instead of 0. Padding,
    donation and scatter semantics match `prefill_into_slots`.
    """
    texts = jnp.asarray(texts, jnp.int32)
    prefill_batch = int(texts.shape[0])
    return _jit_sample(
        _resume_slots_builder, model, (prefill_batch,),
        variables, state, texts,
        jnp.asarray(img_tokens, jnp.int32), jnp.asarray(img_pos, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.asarray(temperatures, jnp.float32), jnp.asarray(keep_ks, jnp.int32),
    )


@_program("slots_resume")
def _resume_slots_builder(model, key):
    del key  # (prefill batch,)

    def fn(variables, state, texts, img_tokens, img_pos, slots, seeds,
           temperatures, keep_ks):
        rows, cache_r = _resume_rows(model, variables, texts, img_tokens, img_pos)
        cache = decode_cache.scatter_rows(state["cache"], cache_r, slots)
        return _admit_rows(
            model, state, cache, rows, slots, seeds, temperatures, keep_ks,
            img_tokens, img_pos,
        )

    return fn


_resume_slots_builder._donate_argnums = (1,)  # state


def release_slots(model: DALLE, state: dict, mask) -> dict:
    """Deactivate the slots where `mask` is True (jitted, fixed shape;
    `state` is donated — replace your reference with the return value)."""
    return _jit_sample(
        _release_builder, model, (), state, jnp.asarray(mask, jnp.bool_)
    )


@_program("slots_release")
def _release_builder(model, key):
    del model, key

    def fn(state, mask):
        return {**state, "active": state["active"] & ~mask}

    return fn


_release_builder._donate_argnums = (0,)  # state


def decode_image_chunk(
    model: DALLE, variables, state: dict, chunk: int, block_bitmap=None
):
    """Advance every live slot by up to `chunk` tokens (one jitted program
    per (model, chunk)).

    Each of the `chunk` steps samples one token per live row from its
    pending logits — per-row (seed, image-position) RNG, per-row
    temperature/top-k — writes it at the row's own image position, and
    feeds it back through the transformer at the row's own cache position.
    Rows that hit `image_seq_len` mid-chunk freeze (their cache, tokens,
    and position stop advancing) until the host retires them at the chunk
    boundary; inactive slots compute along as padding but persist nothing.

    `state` is DONATED (see `prefill_into_slots`) — replace your reference
    with the return value.

    `block_bitmap` ([depth, max_batch, nb] int32) arms decode-time policy
    sparsity: injected into every layer's attention cache for the scan
    (models/attention.py routes masked rows through the block-sparse
    flash kernel) and stripped from the result. Traced data — re-deriving
    it every chunk never recompiles; its presence selects a separate
    compiled program (the "sparse" static-key marker), warmed like any
    other rung.
    """
    if block_bitmap is None:
        return _jit_sample(
            _chunk_builder, model, (int(chunk),), variables, state
        )
    return _jit_sample(
        _chunk_builder, model, (int(chunk), "sparse"),
        variables, state, jnp.asarray(block_bitmap, jnp.int32),
    )


@_program("slots_chunk")
def _chunk_builder(model, key):
    chunk = key[0]
    return _make_chunk_fn(model, chunk, paged=False, sparse="sparse" in key)


def _make_chunk_fn(model, chunk, paged, sparse=False):
    """One chunk program body, shared by the slotted and paged layouts so
    the decode semantics (sampling, liveness gating, position threading)
    cannot drift between them — only the cache plumbing differs: the paged
    variant takes the host-built page table as an extra traced argument,
    injects it into every layer's attention cache for the duration of the
    scan, and strips it from the result (the table is host state, not part
    of the donated device state)."""
    from dalle_pytorch_tpu.ops.sampling import (
        gumbel_sample_per_row, per_row_step_keys, top_k_filter_per_row,
    )

    text_len = model.text_seq_len + 1  # <bos> + text prefix
    image_seq_len = model.image_seq_len
    blocked = jnp.asarray(
        np.arange(model.total_tokens) < model.total_text_tokens
    )[None]

    def run(variables, state, cache0):
        active = state["active"]
        seeds = state["seeds"]
        temps = state["temps"]
        keep_k = state["keep_k"]

        def step(carry, _):
            cache, row, img_tokens, img_pos = carry
            live = active & (img_pos < image_seq_len)

            with jax.named_scope("sample"):
                masked = jnp.where(blocked, NEG_MASK_VALUE, row)
                filtered = top_k_filter_per_row(masked, keep_k)
                keys = per_row_step_keys(seeds, img_pos)
                sample = gumbel_sample_per_row(keys, filtered, temps)
                sample = (sample - model.total_text_tokens).astype(jnp.int32)

                written = jax.vmap(
                    lambda r, t, p: jax.lax.dynamic_update_slice(r, t[None], (p,))
                )(img_tokens, sample, jnp.clip(img_pos, 0, image_seq_len - 1))
                img_tokens = jnp.where(live[:, None], written, img_tokens)

            # stamp every layer's cache index from the per-slot position,
            # then run one decode step at per-row positions
            cache = decode_cache.set_index(cache, img_pos + text_len)
            new_row, cache = model.apply(
                variables, sample, img_pos, cache,
                method=DALLE.decode_image_step,
            )
            row = jnp.where(live[:, None], new_row, row)
            img_pos = jnp.where(live, img_pos + 1, img_pos)
            return (cache, row, img_tokens, img_pos), None

        carry = (
            cache0, state["row"], state["img_tokens"], state["img_pos"],
        )
        return jax.lax.scan(step, carry, None, length=chunk)[0]

    def fn(variables, state, *side):
        # the side leaves ride the cache through the scan and are stripped
        # from the result: the persistent donated state keeps its shape
        names = ("page_table",) if paged else ()
        names += ("block_bitmap",) if sparse else ()
        cache0 = decode_cache.with_side(state["cache"], **dict(zip(names, side)))
        cache, row, img_tokens, img_pos = run(variables, state, cache0)
        return {
            **state,
            "cache": decode_cache.without_side(cache, *names),
            "row": row,
            "img_tokens": img_tokens,
            "img_pos": img_pos,
        }

    return fn


_chunk_builder._donate_argnums = (1,)  # state


# ------------------------------------------------- paged KV cache (blocks)
#
# The slotted state above pins max_batch * (total_seq_len + 1) cache
# positions whether or not a row holds tokens — HBM spent on worst-case
# padding bounds concurrency. The paged ops below move K/V into a pool of
# fixed-size pages plus host-owned per-row page tables
# (serving/paging.py): admission maps pages, identical caption prefixes
# SHARE immutable prefill pages (content-hash prefix cache; a repeat
# prompt admits with zero transformer dispatches via its cached sidecar),
# and released rows return pages to the pool. The page table is a traced
# argument to every dispatch — ONE compiled program regardless of which
# pages are mapped — and the state stays donated exactly like the slotted
# ops. models/attention.py reads the paged cache either through a gathered
# contiguous view (bit-for-bit identical to the slotted path — the parity
# contract tests/test_paging.py pins) or the paged Pallas kernel
# (ops/pallas_decode.py).


def init_paged_slot_state(
    model: DALLE, max_batch: int, n_pages: int, page_size: int, dtype=None
) -> dict:
    """Persistent paged decode state: same per-row control state as
    `init_slot_state`, with K/V in a page pool instead of per-slot lanes.
    Page 0 is the serving layer's reserved garbage page (never allocated),
    so the pool must be sized n_pages >= usable pages + 1."""
    s = int(max_batch)
    return {
        "cache": _init_cache(
            model, s, dtype, pages=(int(n_pages), int(page_size))
        ),
        "row": jnp.zeros((s, model.total_tokens), jnp.float32),
        "img_tokens": jnp.zeros((s, model.image_seq_len), jnp.int32),
        "img_pos": jnp.zeros((s,), jnp.int32),
        "active": jnp.zeros((s,), jnp.bool_),
        "seeds": jnp.zeros((s,), jnp.int32),
        "temps": jnp.ones((s,), jnp.float32),
        "keep_k": jnp.ones((s,), jnp.int32),
    }


def prefill_into_slots_paged(
    model: DALLE,
    variables,
    state: dict,
    texts: jnp.ndarray,
    slots,
    seeds,
    temperatures,
    keep_ks,
    page_rows,
    partial_dst,
    page_size: int,
    block_bitmap=None,
):
    """Paged-layout batched admission: the same batch-R text prefill as
    `prefill_into_slots`, scattered into PAGES instead of slot lanes.

    `page_rows` is [R, n_text_pages] — the physical page for each of row
    r's text blocks (host-allocated; shared prefix blocks may point at
    pages other rows/the prefix cache also map, in which case this dispatch
    rewrites them with bit-identical content — prefill K/V is a
    deterministic, batch-composition-invariant function of the text).
    `partial_dst` is [R]: an EXTRA destination page for each row's last
    text block — the prefix cache's immutable snapshot of the divergence
    block, which the row goes on to mutate in its own copy while the cache
    keeps this one (copy-on-write at registration time). Page 0 (garbage)
    disables the extra write for rows the host isn't registering.

    Returns (state, sidecar): `state` donated/replaced as usual; `sidecar`
    is {"row": [R, V] pending logits, "rings": row-major shift rings} —
    everything a later full-prefix admission needs to skip the transformer
    entirely (`admit_cached_prefix`).
    """
    texts = jnp.asarray(texts, jnp.int32)
    prefill_batch = int(texts.shape[0])
    page_rows = jnp.asarray(page_rows, jnp.int32)
    n_text_pages = int(page_rows.shape[1])
    args = (
        variables, state, texts,
        jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.asarray(temperatures, jnp.float32), jnp.asarray(keep_ks, jnp.int32),
        page_rows, jnp.asarray(partial_dst, jnp.int32),
    )
    if block_bitmap is None:
        return _jit_sample(
            _prefill_slots_paged_builder, model,
            (prefill_batch, int(page_size), n_text_pages), *args,
        )
    return _jit_sample(
        _prefill_slots_paged_builder, model,
        (prefill_batch, int(page_size), n_text_pages, "sparse"),
        *args, jnp.asarray(block_bitmap, jnp.int32),
    )


@_program("slots_prefill_paged")
def _prefill_slots_paged_builder(model, key):
    page_size = key[1]  # (prefill batch, page size, text pages[, "sparse"])

    def fn(variables, state, texts, slots, seeds, temperatures, keep_ks,
           page_rows, partial_dst, *block_bitmap):
        rows, cache_r = _prefill_rows(model, variables, texts, *block_bitmap)
        cache = decode_cache.scatter_rows(
            state["cache"], cache_r, slots, pages=(page_rows, page_size, partial_dst)
        )
        out = _admit_rows(
            model, state, cache, rows, slots, seeds, temperatures, keep_ks
        )
        sidecar = {
            "row": rows.astype(jnp.float32),
            "rings": decode_cache.extract_rings(cache_r),
        }
        return out, sidecar

    return fn


_prefill_slots_paged_builder._donate_argnums = (1,)  # state


def resume_into_slots_paged(
    model: DALLE,
    variables,
    state: dict,
    texts: jnp.ndarray,
    img_tokens: jnp.ndarray,
    img_pos,
    slots,
    seeds,
    temperatures,
    keep_ks,
    page_rows,
    page_size: int,
):
    """Paged-layout mid-decode admission: the same teacher-forced
    re-prefill as `resume_into_slots`, scattered into PAGES.

    `page_rows` is [R, pages_per_row]: the physical page for each of row
    r's blocks — real pages up to the block covering the row's resume
    position, the garbage page beyond (the fixed-shape scatter writes
    every block; writes past the prefix land in the garbage page exactly
    like released rows' stale writes, and `ensure` maps real pages ahead
    of decode as usual). Resume rows never share prefix-cache pages: the
    dispatch rewrites every mapped page, and a row's own mid-decode K/V
    must not overwrite content other rows map (the host allocates fresh
    pages — `PagedKVManager.admit_resume`).
    """
    texts = jnp.asarray(texts, jnp.int32)
    prefill_batch = int(texts.shape[0])
    page_rows = jnp.asarray(page_rows, jnp.int32)
    n_pages_row = int(page_rows.shape[1])
    return _jit_sample(
        _resume_slots_paged_builder, model,
        (prefill_batch, int(page_size), n_pages_row),
        variables, state, texts,
        jnp.asarray(img_tokens, jnp.int32), jnp.asarray(img_pos, jnp.int32),
        jnp.asarray(slots, jnp.int32), jnp.asarray(seeds, jnp.int32),
        jnp.asarray(temperatures, jnp.float32), jnp.asarray(keep_ks, jnp.int32),
        page_rows,
    )


@_program("slots_resume_paged")
def _resume_slots_paged_builder(model, key):
    page_size = key[1]  # (prefill batch, page size, pages a row)

    def fn(variables, state, texts, img_tokens, img_pos, slots, seeds,
           temperatures, keep_ks, page_rows):
        rows, cache_r = _resume_rows(model, variables, texts, img_tokens, img_pos)
        cache = decode_cache.scatter_rows(
            state["cache"], cache_r, slots, pages=(page_rows, page_size, None)
        )
        return _admit_rows(
            model, state, cache, rows, slots, seeds, temperatures, keep_ks,
            img_tokens, img_pos,
        )

    return fn


_resume_slots_paged_builder._donate_argnums = (1,)  # state


def slice_prefix_sidecar(model: DALLE, sidecar: dict, r: int):
    """Row `r` of a batched prefill sidecar (all leaves are row-major) —
    ONE compiled program per sidecar structure, so registering a prefix on
    a warm server never compiles."""
    return _jit_sample(
        _slice_sidecar_builder, model, (), sidecar, jnp.int32(r)
    )


@_program("sidecar_slice")
def _slice_sidecar_builder(model, key):
    del model, key

    def fn(sidecar, r):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, r, axis=0, keepdims=False),
            sidecar,
        )

    return fn


def admit_cached_prefix(
    model: DALLE,
    state: dict,
    slot: int,
    sidecar: dict,
    seed,
    temperature,
    keep_k,
    partial_src,
    partial_dst,
    page_size: int,
):
    """Admit a FULL prefix-cache hit into `slot` with zero transformer
    dispatches: the prefix's K/V pages are already mapped into the row's
    page table by the host; this op restores the non-page-addressable
    remainder — pending logits + shift rings from the cached sidecar, the
    per-slot sampling params — and copy-on-writes the divergence block
    (`partial_src` = the cache's immutable snapshot page, `partial_dst` =
    the row's private copy the decode will mutate; configs whose text
    prefix ends exactly on a page boundary skip the copy statically).

    `state` is DONATED — replace your reference with the return value.
    """
    return _jit_sample(
        _admit_prefix_builder, model, (int(page_size),),
        state, jnp.int32(slot), sidecar,
        jnp.int32(seed), jnp.float32(temperature), jnp.int32(keep_k),
        jnp.int32(partial_src), jnp.int32(partial_dst),
    )


@_program("prefix_admit")
def _admit_prefix_builder(model, key):
    (page_size,) = key
    has_partial = (model.text_seq_len + 1) % page_size != 0

    def fn(state, slot, sidecar, seed, temperature, keep_k,
           partial_src, partial_dst):
        out = dict(state)
        out["cache"] = decode_cache.restore_prefix(
            state["cache"], sidecar["rings"], slot,
            page_copy=(partial_src, partial_dst) if has_partial else None,
        )
        out["row"] = jax.lax.dynamic_update_slice(
            state["row"],
            sidecar["row"][None].astype(state["row"].dtype),
            (slot, 0),
        )
        out["img_tokens"] = jax.lax.dynamic_update_slice(
            state["img_tokens"],
            jnp.zeros((1, model.image_seq_len), jnp.int32),
            (slot, 0),
        )
        out["img_pos"] = state["img_pos"].at[slot].set(0)
        out["active"] = state["active"].at[slot].set(True)
        out["seeds"] = state["seeds"].at[slot].set(seed)
        out["temps"] = state["temps"].at[slot].set(temperature)
        out["keep_k"] = state["keep_k"].at[slot].set(keep_k)
        return out

    return fn


_admit_prefix_builder._donate_argnums = (0,)  # state


def decode_image_chunk_paged(
    model: DALLE, variables, state: dict, chunk: int, page_table,
    block_bitmap=None,
):
    """Paged-layout chunk step: identical decode semantics to
    `decode_image_chunk` (one shared program body — see `_make_chunk_fn`),
    with every row's K/V reads and writes indirected through `page_table`
    [max_batch, n_pages] (host numpy, traced data: ONE compiled program no
    matter which pages are mapped). `state` is DONATED; the page table is
    not (it is host-owned and tiny). `block_bitmap` arms policy sparsity
    exactly as in `decode_image_chunk` — on this layout the table-gated
    paged kernels skip dead PAGES through the same indirection."""
    if block_bitmap is None:
        return _jit_sample(
            _chunk_paged_builder, model, (int(chunk),),
            variables, state, jnp.asarray(page_table, jnp.int32),
        )
    return _jit_sample(
        _chunk_paged_builder, model, (int(chunk), "sparse"),
        variables, state, jnp.asarray(page_table, jnp.int32),
        jnp.asarray(block_bitmap, jnp.int32),
    )


@_program("slots_chunk_paged")
def _chunk_paged_builder(model, key):
    chunk = key[0]
    return _make_chunk_fn(model, chunk, paged=True, sparse="sparse" in key)


_chunk_paged_builder._donate_argnums = (1,)  # state


def forward_with_cond_scale(
    model: DALLE, variables, text, image, cond_scale: float = 1.0, rngs=None
):
    """Two-forward classifier-free-guidance blend (`dalle_pytorch.py:575-585`)."""
    logits = model.apply(variables, text, image, rngs=rngs)
    if cond_scale == 1:
        return logits
    null_rngs = dict(rngs or {})
    null_rngs["null_cond"] = jax.random.PRNGKey(0)  # prob=1 -> rng irrelevant
    null_logits = model.apply(
        variables, text, image, null_cond_prob=1.0, rngs=null_rngs
    )
    return null_logits + (logits - null_logits) * cond_scale


def generate_images(
    model: DALLE,
    variables,
    rng: jax.Array,
    text: jnp.ndarray,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    cond_scale: float = 1.0,
    init_image_tokens: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
):
    """Jit-cached wrapper over the full-reforward sampling oracle."""
    static_key = (filter_thres, temperature, cond_scale, num_init_img_tokens)
    if init_image_tokens is None:
        return _jit_sample(
            _full_sampler_builder, model, static_key, variables, rng, text
        )
    return _jit_sample(
        _full_sampler_builder, model, static_key,
        variables, rng, text, init_image_tokens,
    )


@_program("sample_full")
def _full_sampler_builder(model, key):
    filter_thres, temperature, cond_scale, num_init = key

    def fn(variables, rng, text, init_image_tokens=None):
        return _generate_images_impl(
            model, variables, rng, text,
            filter_thres=filter_thres, temperature=temperature,
            cond_scale=cond_scale,
            init_image_tokens=init_image_tokens,
            num_init_img_tokens=num_init,
        )

    return fn


def _generate_images_impl(
    model: DALLE,
    variables,
    rng: jax.Array,
    text: jnp.ndarray,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
    cond_scale: float = 1.0,
    init_image_tokens: Optional[jnp.ndarray] = None,
    num_init_img_tokens: Optional[int] = None,
):
    """Autoregressively sample image codebook indices for `text`.

    Equivalent of `DALLE.generate_images` (`dalle_pytorch.py:517-567`) up to
    VAE decode, which the caller applies to the returned [B, image_seq_len]
    indices. Priming follows the reference's 43.75% default (`:542`).

    Implementation: `lax.scan` over image positions; each step runs a full
    forward over the fixed-shape token buffer (causality makes the suffix
    garbage irrelevant). This path is the correctness oracle for the
    KV-cached fast path, `generate_images_cached`, which is what production
    callers should use.
    """
    b = text.shape[0]
    image_seq_len = model.image_seq_len
    img_tokens, primed = _primed_image_tokens(
        model, b, init_image_tokens, num_init_img_tokens
    )

    def step(carry, i):
        img_tokens, rng = carry
        with jax.named_scope("rng_split"):
            rng, sample_rng = jax.random.split(rng)
        logits = forward_with_cond_scale(
            model, variables, text, img_tokens, cond_scale=cond_scale
        )
        with jax.named_scope("sample"):
            pos_logits = logits[:, model.text_seq_len + i]
            filtered = top_k_filter(pos_logits, thres=filter_thres)
            sample = gumbel_sample(sample_rng, filtered, temperature=temperature)
            sample = (sample - model.total_text_tokens).astype(jnp.int32)
            keep = i < primed
            prev = jax.lax.dynamic_index_in_dim(img_tokens, i, axis=1, keepdims=False)
            new = jnp.where(keep, prev, sample)
            img_tokens = jax.lax.dynamic_update_slice(img_tokens, new[:, None], (0, i))
        return (img_tokens, rng), None

    (img_tokens, _), _ = jax.lax.scan(
        step, (img_tokens, rng), jnp.arange(image_seq_len)
    )
    return img_tokens


def generate_texts(
    model: DALLE,
    variables,
    rng: jax.Array,
    text_prefix: jnp.ndarray,
    prefix_len: int,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
):
    """Jit-cached wrapper over autoregressive text completion.

    `prefix_len` is passed as a traced argument (it only feeds an `i <
    prefix_len` comparison), so varying prompt lengths reuse one compile.
    """
    static_key = (filter_thres, temperature)
    return _jit_sample(
        _text_sampler_builder, model, static_key,
        variables, rng, text_prefix, jnp.int32(prefix_len),
    )


@_program("sample_text")
def _text_sampler_builder(model, key):
    filter_thres, temperature = key

    def fn(variables, rng, text_prefix, prefix_len):
        return _generate_texts_impl(
            model, variables, rng, text_prefix, prefix_len,
            filter_thres=filter_thres, temperature=temperature,
        )

    return fn


def _generate_texts_impl(
    model: DALLE,
    variables,
    rng: jax.Array,
    text_prefix: jnp.ndarray,
    prefix_len: int,
    filter_thres: float = 0.5,
    temperature: float = 1.0,
):
    """Autoregressive text completion (`dalle_pytorch.py:470-515`).

    text_prefix: [B, text_seq_len] with ids after position `prefix_len`
    ignored/overwritten. Returns [B, text_seq_len] token ids.

    Note: a sampled id 0 is treated as padding on subsequent steps (the
    unique-pad remap applies to it, and decoding strips it) — consistent
    with the training distribution, where a raw 0 never appears
    mid-sequence; the model sampling 0 means "end of caption".
    """

    def step(carry, i):
        text, rng = carry
        with jax.named_scope("rng_split"):
            rng, sample_rng = jax.random.split(rng)
        logits = model.apply(variables, text)  # image part absent
        with jax.named_scope("sample"):
            pos_logits = logits[:, i]  # position i predicts text token i (bos shift)
            filtered = top_k_filter(pos_logits, thres=filter_thres)
            sample = gumbel_sample(
                sample_rng, filtered, temperature=temperature
            ).astype(jnp.int32)
            keep = i < prefix_len
            prev = jax.lax.dynamic_index_in_dim(text, i, axis=1, keepdims=False)
            new = jnp.where(keep, prev, sample)
            text = jax.lax.dynamic_update_slice(text, new[:, None], (0, i))
        return (text, rng), None

    (text, _), _ = jax.lax.scan(
        step, (text_prefix.astype(jnp.int32), rng), jnp.arange(model.text_seq_len)
    )
    return text
