"""Host-side decode-sparsity policy: static attention layouts reduced to
per-row KV-tile bitmaps for the block-sparse flash-decode kernel.

The model's own sparse attention patterns (`axial_row`/`axial_col`/
`conv_like`/`sparse` — ops/masks.py) say which KV positions a decode step
can ever read, but until now they bought nothing at decode time: pattern-
masked rows fell back to dense attention over the whole cache. This module
precomputes, per layer and per image position, the BLOCK-level shadow of
each pattern (tile width = the model's `decode_sparse_block`), and the
engine ships the per-slot rows of that table into every chunk dispatch as
traced data (`models/decode_cache.py:with_side`). Policy semantics:

  * conservative by construction — a tile any pattern row in the chunk
    window touches is read whole (`ops/masks.py:mask_to_block_bitmap`),
    and the chunk's bitmap is the UNION over its `chunk_tokens` query
    positions (the bitmap is constant across the in-program scan; the
    kernel's per-step causal/length mask trims inside live tiles);
  * the text prefix (<bos> + text tokens) is ALWAYS live — every shipped
    pattern lets image rows read all text, and prefill/quality both
    depend on it;
  * "full" layers get all-ones rows (pure length-skip, i.e. exactly the
    non-sparse flash kernel);
  * inactive slots get all-ones rows: they compute as padding whose
    outputs are discarded, and all-ones keeps their math identical to
    the non-sparse program (bit-parity pins stay checkable row-wise).

Everything here is host numpy; nothing traces or compiles. The ONLY
compile-relevant quantity is the tile width baked into the model clone
(`decode_sparse_block`) — the bitmaps themselves are data, so admission,
retirement, and even swapping the whole policy never trigger a compile
(the Vortex lesson, PAPERS.md: programmable sparsity must be data).
"""

from __future__ import annotations

import numpy as np

from dalle_pytorch_tpu.models.attention import DECODE_SPARSE_BLOCK
from dalle_pytorch_tpu.models.transformer import Transformer, _build_static_mask
from dalle_pytorch_tpu.ops.masks import mask_to_block_bitmap


class DecodeSparsityPolicy:
    """Per-(layer, image-position) KV-tile liveness tables for one model.

    Parameters mirror what the engine knows at boot: the (already cloned)
    model carrying `decode_sparse_block`, and the chunk size its decode
    programs advance by. `max_batch` only sizes the emitted tables.
    """

    def __init__(self, model, chunk_tokens: int, max_batch: int):
        self.max_batch = int(max_batch)
        self.chunk = max(int(chunk_tokens), 1)
        self.text_len = model.text_seq_len + 1  # <bos> + text prefix
        self.image_seq_len = model.image_seq_len
        self.max_len = model.total_seq_len + 1
        block = (
            DECODE_SPARSE_BLOCK
            if getattr(model, "decode_sparse_block", None) is None
            else model.decode_sparse_block
        )
        # mirror the kernel's block_k clamp (tiny test geometries)
        self.block = max(min(int(block), self.max_len), 1)
        self.n_blocks = -(-self.max_len // self.block)
        self.depth = model.depth

        trunk = Transformer(**model.transformer_kwargs(), parent=None)
        type_per_layer = [layer.kind for layer in trunk.plan()]

        # per-layer [image_seq_len, n_blocks] bool: tile liveness for a
        # chunk STARTING at image position p (union over the window).
        # Layers sharing (attn_type, seed-irrelevant) could share tables,
        # but "sparse" layers seed by layer index, so compute per layer
        # and dedup by attn_type only where that is sound ("full"/axial/
        # conv tables are layer-independent).
        self._windows: list[np.ndarray | None] = []  # None = all-ones
        table_cache: dict[str, np.ndarray] = {}
        for ind, t in enumerate(type_per_layer):
            if t == "full":
                self._windows.append(None)
                continue
            key = t if t != "sparse" else f"sparse_{ind}"
            if key not in table_cache:
                mask = np.asarray(
                    _build_static_mask(
                        t, model.total_seq_len, model.image_fmap_size, ind
                    )
                )
                # size to the cache geometry exactly like the dense
                # path's mask_rows_at: True-pad up to max_len, then crop
                if mask.shape[0] < self.max_len:
                    pad = self.max_len - mask.shape[0]
                    mask = np.pad(mask, ((0, pad), (0, pad)),
                                  constant_values=True)
                mask = mask[: self.max_len, : self.max_len]
                rows = mask_to_block_bitmap(
                    mask, self.block, n_blocks=self.n_blocks,
                    always_live=self.text_len,
                )
                # union over each chunk window [p, p + chunk)
                img_rows = rows[self.text_len :][: self.image_seq_len]
                win = np.zeros(
                    (self.image_seq_len, self.n_blocks), dtype=bool
                )
                for off in range(self.chunk):
                    hi = self.image_seq_len - off
                    if hi <= 0:
                        break
                    # win[p] |= rows[p + off]; positions whose window runs
                    # past the last image row simply union fewer rows
                    win[:hi] |= img_rows[off : off + hi]
                table_cache[key] = win
            self._windows.append(table_cache[key])

    # ------------------------------------------------------------ tables

    def chunk_bitmaps(self, img_pos, active) -> np.ndarray:
        """[depth, max_batch, n_blocks] int32 for one chunk dispatch.

        `img_pos`/`active` are the engine's host mirrors of each slot's
        image position and liveness. Inactive slots (and "full" layers)
        get all-ones rows — identical math to the non-sparse program."""
        pos = np.clip(
            np.asarray(img_pos, np.int64)[: self.max_batch],
            0, self.image_seq_len - 1,
        )
        act = np.asarray(active, bool)[: self.max_batch]
        out = np.ones(
            (self.depth, self.max_batch, self.n_blocks), dtype=np.int32
        )
        for li, win in enumerate(self._windows):
            if win is None:
                continue
            rows = win[pos]  # [B, n_blocks] bool
            out[li, : len(pos)] = np.where(act[:, None], rows, True)
        return out

    def prefill_bitmaps(self, prefill_batch: int) -> np.ndarray:
        """[depth, R, n_blocks] all-ones: text rows under every shipped
        pattern read (at most) the causal text prefix, and tiles above the
        prefill length are dead via the kernel's length AND — so all-ones
        is exact, and keeps prefill numerics identical to the non-sparse
        flash path."""
        return np.ones(
            (self.depth, int(prefill_batch), self.n_blocks), dtype=np.int32
        )

    # -------------------------------------------------------- accounting

    def count_tiles(self, img_pos, active) -> tuple[int, int]:
        """(read, skipped) KV tiles for one chunk dispatch, summed over
        active rows and layers (per head the counts are identical, so
        heads are left out of the unit). `skipped` counts only tiles the
        LENGTH skip would have read — i.e. the policy's own savings on
        top of PR 4's length skip — which is the number the bench and the
        fleet counters compare against dense-causal flash."""
        pos = np.clip(
            np.asarray(img_pos, np.int64)[: self.max_batch],
            0, self.image_seq_len - 1,
        )
        act = np.asarray(active, bool)[: self.max_batch]
        if not act.any():
            return 0, 0
        lengths = np.minimum(
            pos[act] + self.text_len + self.chunk, self.max_len
        )
        llb = np.maximum(lengths - 1, 0) // self.block  # last live tile
        in_range = (
            np.arange(self.n_blocks)[None, :] <= llb[:, None]
        )  # [A, nb]
        read = skipped = 0
        for win in self._windows:
            if win is None:
                read += int(in_range.sum())
                continue
            live = win[pos[act]] & in_range
            read += int(live.sum())
            skipped += int((in_range & ~live).sum())
        return read, skipped

    def detail(self) -> dict:
        """Static policy summary for /healthz."""
        dead_frac = 0.0
        patterned = [w for w in self._windows if w is not None]
        if patterned:
            dead_frac = float(
                np.mean([1.0 - w.mean() for w in patterned])
            )
        return {
            "block": self.block,
            "n_blocks": self.n_blocks,
            "patterned_layers": len(patterned),
            "depth": self.depth,
            "static_dead_tile_frac": round(dead_frac, 4),
        }
