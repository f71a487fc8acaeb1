"""Operations and bytes that each kernel's mathematics requires, from shapes,
and the table of peaks. Kept with the benchmark so that no PR that claims a
gain can move the yardstick; recomputed work (remat) is never counted.

Every function returns `(operations, bytes)` for ONE call.
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    """The published peaks of a listed device; an unlisted one raises."""
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)["devices"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise KeyError(f"no published peak for device kind {device_kind!r} (listed: {sorted(table)})")


def _pairs(seq: int, causal: bool) -> float:
    return seq * (seq + 1) / 2 if causal else float(seq * seq)


def flash_fwd(batch, heads, seq, dim_head, causal=True, itemsize=2, **_):
    """Scores and weighted values over the (query, key) pairs that causality
    leaves; reads q, k, v, writes o and the fp32 log-sum-exp.

    >>> flash_fwd(1, 1, 4, 8)   # 10 pairs x 2 matmuls x 2 x 8
    (320.0, 272)
    """
    rows = batch * heads
    ops = 4.0 * rows * _pairs(seq, causal) * dim_head
    return ops, rows * seq * (4 * dim_head * itemsize + 4)


def flash_dq(batch, heads, seq, dim_head, causal=True, itemsize=2, **_):
    """s = qk^T, dp = do v^T, dq = ds k: three matmuls over the live pairs;
    reads q, k, v, do, lse, delta, writes dq.

    >>> flash_dq(1, 1, 4, 8)
    (480.0, 352)
    """
    rows = batch * heads
    ops = 6.0 * rows * _pairs(seq, causal) * dim_head
    return ops, rows * seq * (5 * dim_head * itemsize + 8)


def flash_dkv(batch, heads, seq, dim_head, causal=True, itemsize=2, **_):
    """s, dp, dv = p^T do, dk = ds^T q: four matmuls over the live pairs;
    reads q, k, v, do, lse, delta, writes dk and dv.

    >>> flash_dkv(1, 1, 4, 8)
    (640.0, 416)
    """
    rows = batch * heads
    ops = 8.0 * rows * _pairs(seq, causal) * dim_head
    return ops, rows * seq * (6 * dim_head * itemsize + 8)


def flash_decode(live_positions, heads, dim_head, batch, itemsize=2, **_):
    """One query per row against the K/V its live length requires:
    `live_positions` is the sum of the rows' lengths in that call.

    >>> flash_decode(10, 2, 8, 1)   # 10 positions x 2 heads x (k and v)
    (640.0, 704)
    """
    ops = 4.0 * live_positions * heads * dim_head
    kv = 2 * live_positions * heads * dim_head * itemsize
    return ops, kv + 2 * batch * heads * dim_head * itemsize


def train_step_flops(batch, seq, dim, depth, heads, dim_head, vocab, ff_mult=4, **_):
    """Useful matmul FLOPs of one forward-and-backward step (3 x forward),
    with GEGLU's doubled up-projection, full (not causal-halved) attention
    as MFU is conventionally quoted, and the head.

    >>> train_step_flops(1, 2, 4, 1, 1, 4, 8)   # 3 x 2 x 2 x (48+16+16+128+64+32)
    3648.0
    """
    inner = heads * dim_head
    per_token = (
        dim * 3 * inner + 2 * seq * inner + inner * dim
        + dim * dim * ff_mult * 2 + dim * ff_mult * dim
    )
    return 3.0 * 2 * batch * seq * (depth * per_token + dim * vocab)


def least_seconds(ops: float, nbytes: float, peak: dict):
    """(the least time the chip could take, which bound it is)."""
    t_ops, t_bytes = ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
