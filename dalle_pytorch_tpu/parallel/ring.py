"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has NO sequence/context parallelism (SURVEY.md §5.7) — it
scales sequence length with attention *sparsity* instead. This module goes
beyond parity: the sequence is sharded over the `sp` mesh axis, each device
holds one block of queries, and key/value blocks rotate around the ring via
`ppermute` over ICI while a streaming (flash-style) log-sum-exp
accumulator builds the exact softmax — O(n/P) memory per device, compute
overlapped with neighbor communication by XLA's async collective
scheduling.

Use `ring_attention` inside `shard_map` (axis name "sp"), or the
`ring_attention_sharded` convenience wrapper for a full [B, H, N, D] array
sharded along N.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard blocks q,k,v: [B, H, n_local, D]; returns [B, H, n_local, D].

    Shard i owns global positions [i*n_local, (i+1)*n_local). Must run
    inside shard_map over `axis_name`.
    """
    n_shards = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, n_local, d = q.shape
    scale = d**-0.5 if scale is None else scale

    q = q * scale
    q_pos = idx * n_local + jnp.arange(n_local)

    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def step(carry, s):
        k_blk, v_blk, m, l, acc = carry
        kv_idx = (idx - s) % n_shards
        k_pos = kv_idx * n_local + jnp.arange(n_local)

        scores = jnp.einsum(
            "bhid,bhjd->bhij", q, k_blk, preferred_element_type=jnp.float32
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG)

        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum(
            "bhij,bhjd->bhid", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        # rotate kv blocks one hop around the ring (ICI neighbor exchange)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m_new, l, acc), None

    # derive the accumulators from q so they carry q's varying manual axes
    # (shard_map's vma typing rejects invariant carries updated with
    # varying values)
    m0 = jnp.full_like(q[..., :1], _NEG, dtype=jnp.float32)
    l0 = jnp.zeros_like(q[..., :1], dtype=jnp.float32)
    acc0 = jnp.zeros_like(q, dtype=jnp.float32)

    (_, _, _, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n_shards)
    )
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention_sharded(
    mesh: Mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    batch_axes=("dp", "fsdp"),
    seq_axis: str = "sp",
) -> jnp.ndarray:
    """Wrapper: q,k,v [B, H, N, D] with N sharded over `seq_axis`.

    The batch axis is sharded over `batch_axes` when its size divides their
    product, else replicated — so abstract traces with unsharded batches
    (model.init with batch 1, small eval forwards) still compile; training
    batches (sized by the data loader to dp*fsdp) get the real sharding.
    """
    dp_extent = 1
    for a in batch_axes:
        dp_extent *= mesh.shape.get(a, 1)
    b_axes = batch_axes if q.shape[0] % dp_extent == 0 else None
    spec = P(b_axes, None, seq_axis, None)
    fn = shard_map(
        partial(ring_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
