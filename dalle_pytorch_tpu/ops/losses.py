"""Memory-efficient (vocab-chunked) cross-entropy for the DALLE head.

The straightforward loss path materializes `[B, N, V]` fp32 logits twice
(forward + softmax-minus-onehot backward); at the flagship geometry
(B16 x N1280 x V18448) that is ~1.5 GB per materialization (arithmetic;
on the chip the dense loss is `loss_pct.train` 4.11 of the flagship step,
24.9 ms, PERF.md section 5; this path is not measured there). This module
computes the same split cross-entropy by scanning the vocabulary in
chunks: each chunk's logits live only in registers/VMEM-sized transients,
and `jax.checkpoint` on the scan body makes the backward recompute chunk
logits instead of saving them.

Semantics match `DALLE.__call__`'s loss exactly (reference
`dalle_pytorch.py:450-464,694-706`): per-position vocab blocking (text
rows emit text vocab only, image rows image vocab only; the NEG-masked
entries contribute nothing to the logsumexp) and per-position text/image
loss weighting.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEG = np.float32(-1e30)  # np, NOT jnp: a module-level jax Array would be
# hoisted into every fused-CE executable as a runtime constant argument,
# and the jit C++ fastpath drops hoisted const args after 2 calls on
# jax 0.9.0 ("Execution supplied N buffers but compiled program expected
# M") — still so on the CPU and on the v5e (PR 21: with jnp here,
# tests/test_dalle.py::TestFusedCEMultiStep fails on both)


def chunked_masked_ce(
    h: jnp.ndarray,
    kernel: jnp.ndarray,
    bias: Optional[jnp.ndarray],
    labels: jnp.ndarray,
    *,
    row_is_text: jnp.ndarray,
    num_text_vocab: int,
    chunk: int = 2048,
) -> jnp.ndarray:
    """Per-position CE of `softmax(h @ kernel + bias)` vs `labels`.

    h: [B, N, D] (any float dtype; matmul accumulates fp32)
    kernel: [D, V]; bias: [V] or None
    labels: [B, N] int ids into V
    row_is_text: [N] bool — True rows may only emit ids < num_text_vocab,
        False rows only ids >= num_text_vocab (the reference's logits
        mask, applied on the fly per chunk instead of via a [N, V] where).
    Returns per-position loss [B, N] (caller applies weights/averaging).
    """
    B, N, D = h.shape
    V = kernel.shape[1]
    # don't pad tiny vocabularies up to a full `chunk` (a 90-entry test
    # vocab would otherwise compute 2048 logit columns); lane-align to 128
    chunk = min(chunk, max(128, -(-V // 128) * 128))
    n_chunks = -(-V // chunk)
    pad = n_chunks * chunk - V
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, pad)))
        if bias is not None:
            bias = jnp.pad(bias, (0, pad), constant_values=0.0)

    # scan carry: streaming logsumexp (m, s) + gathered gold logit
    m0 = jnp.full((B, N), NEG, jnp.float32)
    s0 = jnp.zeros((B, N), jnp.float32)
    g0 = jnp.zeros((B, N), jnp.float32)

    kernel_chunks = kernel.reshape(D, n_chunks, chunk).transpose(1, 0, 2)
    bias_chunks = (
        bias.reshape(n_chunks, chunk)
        if bias is not None
        else jnp.zeros((n_chunks, chunk), jnp.float32)
    )

    text_rows = row_is_text[None, :]  # [1, N]

    @jax.checkpoint
    def body(carry, inp):
        m, s, g = carry
        ci, kc, bc = inp
        base = ci * chunk
        # [B, N, chunk] fp32 — the only logits transient that ever exists
        with jax.named_scope("logits_chunk"):  # the head's matmul, not the loss
            logits = jnp.einsum(
                "bnd,dc->bnc", h, kc.astype(h.dtype),
                preferred_element_type=jnp.float32,
            ) + bc.astype(jnp.float32)
        ids = base + jnp.arange(chunk)
        id_is_text = (ids < num_text_vocab)[None, None, :]
        id_is_real = (ids < V)[None, None, :]
        allowed = (text_rows[..., None] == id_is_text) & id_is_real
        logits = jnp.where(allowed, logits, NEG)

        cmax = logits.max(axis=-1)
        m_new = jnp.maximum(m, cmax)
        # guard exp(NEG - NEG): scale both by finite m_new
        s = s * jnp.exp(m - m_new) + jnp.exp(logits - m_new[..., None]).sum(-1)
        in_chunk = (labels >= base) & (labels < base + chunk)
        local = jnp.clip(labels - base, 0, chunk - 1)
        gold_c = jnp.take_along_axis(logits, local[..., None], axis=-1)[..., 0]
        g = jnp.where(in_chunk, gold_c, g)
        return (m_new, s, g), None

    (m, s, g), _ = lax.scan(
        body,
        (m0, s0, g0),
        (jnp.arange(n_chunks), kernel_chunks, bias_chunks),
    )
    logz = m + jnp.log(s)
    return logz - g


def split_weighted_mean(
    per_pos: jnp.ndarray,
    split: int,
    first_weight: float,
    second_weight: float,
    drop_last_of_first: bool = False,
):
    """((w1 * mean(first part) + w2 * mean(second part)) / (w1 + w2)).

    `drop_last_of_first` reproduces the inverse-mapping quirk where the
    image segment excludes its final position (reference `:686-687`).
    """
    first = per_pos[:, : split - 1] if drop_last_of_first else per_pos[:, :split]
    second = per_pos[:, split:]
    return (first_weight * first.mean() + second_weight * second.mean()) / (
        first_weight + second_weight
    )
