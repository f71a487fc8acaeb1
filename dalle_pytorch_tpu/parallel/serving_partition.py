"""PartitionSpec rules for the SERVING decode-state pytree.

`parallel/partition.py` answers "how do the *parameters* shard" for
training (and the sharded serving engine reuses it verbatim — params are
params). This module answers the serving-only half of the question: how
the continuous engine's persistent decode state — the slot (or paged) KV
cache, the pending-logits rows, and the per-row host-side control
scalars — spreads over a `make_mesh` device mesh so a model or a batch
too big for one chip's HBM still serves from ONE engine.

Sharding scheme (the natural splits of the decode data path):

  KV cache k/v        [..., B|P, H, L, D]  -> heads over `tp`
      Attention is head-independent, so a head split needs no collective
      inside the attention read/write itself — the same cut SNIPPETS.md
      [1] makes for its shard_map-wrapped flash/paged kernels, and the
      one `ops/pallas_decode.py:sharded_flash_decode_attention` uses.
      Works for both layouts: slotted lanes [B, H, max_len, dh] and the
      paged pool [P, H, page_size, dh] (scan executor adds a leading
      depth axis, which stays unsharded so one scan step touches exactly
      one layer's shards).
  KV scales k/v_scale [..., B|P, H, L]    -> heads over `tp`
      int8-cache per-(position, head) fp32 scales ride with the heads
      they scale; the page axis (paged pool) stays whole, like k/v.
  pending logits      [S, V]              -> vocab over `tp`
      Matches the logits head's (fsdp, tp) column split, so the head's
      output lands already distributed.
  shift rings         [.., B, fmap, dim]  -> replicated (tiny)
  per-row scalars     [S]                 -> replicated
      img_pos / active / seeds / temps / keep_k / cache index are bytes
      per row and feed host-side retirement decisions — replicating them
      keeps `harvest`/`step_chunk`'s chunk-boundary `device_get` a local
      read on every process.

Every assignment passes through the same divisibility fallback as
`partition.py:_divisible`: an axis that does not divide the dimension
drops to replicated rather than erroring, so a 2-head toy model on an
8-way mesh still runs (just without the head split).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.parallel.partition import _divisible, partition_params

#: mesh axis the KV heads / vocab columns shard over (the "model" axis of
#: the 4-axis `make_mesh` vocabulary); batch/slot rows would shard over
#: "dp" but the slot ops index slots host-side, so state rows replicate
SERVING_MODEL_AXIS = "tp"

#: decode-state leaves that are per-row control state — replicated so the
#: chunk-boundary host snapshot stays a local read
_ROW_SCALAR_KEYS = frozenset(
    {"img_pos", "active", "seeds", "temps", "keep_k", "img_tokens", decode_cache.INDEX}
)


def decode_state_spec(path, leaf, model_axis: str = SERVING_MODEL_AXIS) -> P:
    """PartitionSpec for ONE decode-state leaf, before the divisibility
    fallback. Covers both the slotted (`init_slot_state`) and paged
    (`init_paged_slot_state`) layouts — the tree keys are shared."""
    key = decode_cache.leaf_name(path)
    rank = getattr(leaf, "ndim", 0)
    if key in (decode_cache.K, decode_cache.V):
        # [B|P, H, L, dh] (unrolled) or [depth, B|P, H, L, dh] (scan):
        # heads sit at rank-3 in both layouts
        assert rank in (4, 5), f"unexpected cache leaf {key} rank {rank}"
        return P(*([None] * (rank - 3)), model_axis)
    if key in decode_cache.SCALE_KEYS:
        # int8-cache per-(position, head) fp32 scales: [B, H, L] slotted /
        # [P, H, page_size] paged (scan adds depth) — heads at rank-2, so
        # the scales split WITH the heads they scale and the head-split
        # shard_map kernel reads its shard's scales locally
        assert rank in (3, 4), f"unexpected scale leaf {key} rank {rank}"
        return P(*([None] * (rank - 2)), model_axis)
    if key in decode_cache.RING_KEYS or key in _ROW_SCALAR_KEYS:
        return P()  # rings are [B, fmap, dim]-ish: too small to shard
    if key == "row":
        # pending next-token logits [S, total_tokens]: vocab columns over
        # the model axis, matching the logits head's (fsdp, tp) split
        return P(None, model_axis)
    return P()  # anything unrecognized replicates (safe, never wrong)


def decode_state_shardings(
    state: Any, mesh: Mesh, model_axis: str = SERVING_MODEL_AXIS
) -> Any:
    """Decode-state pytree -> NamedSharding pytree (same structure), with
    non-dividing axis assignments dropped to replicated."""

    def one(path, leaf):
        spec = decode_state_spec(path, leaf, model_axis)
        spec = _divisible(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, state)


def serving_variables_shardings(variables: Any, mesh: Mesh) -> Any:
    """Shardings for the engine's `variables` dict ({"params": ...}):
    params tensor-shard per `partition.py`'s training rules (to_qkv /
    ff-up column-parallel over tp, to_out / ff-down row-parallel,
    embeddings vocab-parallel); any non-"params" collections replicate."""
    out = {}
    for name, tree in variables.items():
        if name == "params":
            out[name] = partition_params(tree, mesh)
        else:
            out[name] = jax.tree_util.tree_map(
                lambda _leaf: NamedSharding(mesh, P()), tree
            )
    return out


def replicated_shardings(tree: Any, mesh: Mesh) -> Any:
    """Fully-replicated shardings for host-ish pytrees (VAE params: the
    pixel decode is tiny next to the trunk, and replicating it keeps the
    fused decode collective-free)."""
    return jax.tree_util.tree_map(
        lambda _leaf: NamedSharding(mesh, P()), tree
    )
