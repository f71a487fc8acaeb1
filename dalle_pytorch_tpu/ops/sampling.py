"""Token sampling helpers for autoregressive decoding.

Functional equivalents of the reference's sampling utilities
(`/root/reference/dalle_pytorch/dalle_pytorch.py:55-71`): top-k filtering
keyed by a *fraction* threshold and gumbel-max sampling. The filter keeps
what is not below the k-th largest logit, and only that one value is
needed: `kth_largest` finds it exactly by counting (a radix select over
keys whose integer order is the floats'), so nothing is sorted and shapes
stay static under jit.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

#: counting passes of one selection: a bit of the 32-bit key a pass
PASSES = 32
_SIGN = 0x80000000

#: what each selection traced so far took, by (rows, vocabulary, k): the path
#: (`"max"`: k == 1, no pass; `"count"`) and the counting passes built. k is
#: None where it is a traced array, a k a row. Tests pin it the way they pin
#: `pallas_attention.tiles_chosen`.
selections: dict = {}


def forget() -> None:
    """Drop the record of the selections traced so far (tests)."""
    selections.clear()


def _ordered(bits: jnp.ndarray) -> jnp.ndarray:
    """A float32's bits as int32 -> the int32 whose signed order is the
    floats' (-0.0 just under +0.0), and back: non-negatives keep their bits,
    negatives flip all but the sign, which is its own inverse."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def kth_largest(logits: jnp.ndarray, k) -> jnp.ndarray:
    """The k-th largest value along the last axis, exactly: [..., 1] float32.

    `k` is a Python int or an int array shaped like the leading axes (a k a
    row), 1 <= k <= V. The key's unsigned value `key + 2**31` is fixed from
    the top bit down: a pass counts, a row, the entries at or above the
    prefix with the next bit set and keeps the bit where the count still
    reaches k, so the result is the largest t with `count(x >= t) >= k`: the
    k-th largest entry itself, ties and all. Narrower floats are widened
    first (exact). On the chip the keys stay in VMEM over the passes and a
    pass is one fused compare-and-count: 0.11 ms at [48, 100,352] where
    `lax.top_k`'s sort took 5.0 (`scripts/chip_sampling.py`; PERF.md, PR 36).
    """
    static = isinstance(k, int)
    path = ("max", 0) if static and k == 1 else ("count", PASSES)
    selections[(math.prod(logits.shape[:-1]), logits.shape[-1], k if static else None)] = path
    if path[0] == "max":
        return jnp.max(logits, axis=-1, keepdims=True).astype(jnp.float32)
    keys = _ordered(lax.bitcast_convert_type(logits.astype(jnp.float32), jnp.int32))
    want = jnp.asarray(k, jnp.int32)

    def one_pass(p, prefix):
        bit = jnp.uint32(_SIGN) >> p.astype(jnp.uint32)
        cand = lax.bitcast_convert_type((prefix | bit) ^ jnp.uint32(_SIGN), jnp.int32)
        count = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= want, prefix | bit, prefix)

    prefix = lax.fori_loop(0, PASSES, one_pass, jnp.zeros(logits.shape[:-1], jnp.uint32))
    key = lax.bitcast_convert_type(prefix ^ jnp.uint32(_SIGN), jnp.int32)
    return lax.bitcast_convert_type(_ordered(key), jnp.float32)[..., None]


def top_k_filter(logits: jnp.ndarray, thres: float = 0.5) -> jnp.ndarray:
    """Keep the top max(int((1-thres)*V), 1) logits; set the rest to -inf.

    Matches the reference's `top_k(logits, thres)` semantics where `thres`
    is the fraction of the vocabulary to drop (default 0.5; generation CLI
    uses 0.9). Entries equal to the k-th largest are kept.
    """
    num_logits = logits.shape[-1]
    k = max(int((1.0 - thres) * num_logits), 1)
    return jnp.where(logits < kth_largest(logits, k), -jnp.inf, logits)


def gumbel_sample(
    rng: jax.Array, logits: jnp.ndarray, temperature: float = 1.0
) -> jnp.ndarray:
    """Sample token ids via the gumbel-max trick: argmax(logits/T + G)."""
    g = jax.random.gumbel(rng, logits.shape, dtype=jnp.float32)
    return jnp.argmax(logits.astype(jnp.float32) / temperature + g, axis=-1)


def top_k_filter_per_row(logits: jnp.ndarray, keep_k: jnp.ndarray) -> jnp.ndarray:
    """Per-row top-k: row i keeps its keep_k[i] largest logits, -inf elsewhere.

    `keep_k` is a traced [B] int array, so heterogeneous requests batch into
    one compiled program (the serving micro-batcher's requirement). The same
    selection as `top_k_filter`, with a k a row.
    """
    k = jnp.clip(keep_k, 1, logits.shape[-1]).astype(jnp.int32)
    return jnp.where(logits < kth_largest(logits, k), -jnp.inf, logits)


def per_row_step_keys(seeds: jnp.ndarray, positions: jnp.ndarray) -> jax.Array:
    """Per-row sampling keys for decode step(s): fold (seed, position).

    Row i's stream is a pure function of (seeds[i], positions[i]) — its own
    request seed and its own IMAGE position — never of batch composition,
    slot index, or wall-clock step. This is the single derivation shared by
    the micro-batch sampler (`models/dalle.py:
    _generate_images_cached_batched_impl`, where every row sits at the same
    position) and the continuous-batching chunk decode (where rows sit at
    DIFFERENT positions), so a request's tokens are bit-identical whichever
    engine — and whichever mid-flight admission point — serves it.
    """
    base = jax.vmap(lambda s: jax.random.fold_in(jax.random.PRNGKey(0), s))(
        seeds
    )
    return jax.vmap(jax.random.fold_in)(base, positions)


def gumbel_sample_per_row(
    keys: jax.Array, logits: jnp.ndarray, temperature: jnp.ndarray
) -> jnp.ndarray:
    """Gumbel-max with a per-row PRNG key [B, ...] and temperature [B].

    Temperatures are clamped away from zero; callers wanting greedy decode
    pass a tiny temperature (the argmax then dominates the gumbel noise).
    """
    g = jax.vmap(
        lambda k, row: jax.random.gumbel(k, row.shape, dtype=jnp.float32)
    )(keys, logits)
    t = jnp.maximum(temperature.astype(jnp.float32), 1e-4)[:, None]
    return jnp.argmax(logits.astype(jnp.float32) / t + g, axis=-1)
