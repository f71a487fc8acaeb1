"""Operations and bytes that the language-model cells' kernels require, from
shapes: `costs.py`'s part for grouped K/V heads, a sliding window and the
grouped products of a routed layer. Kept with the benchmark so that no PR
that claims a gain can move the yardstick.

Every function returns `(operations, bytes)` for ONE call. Window and full
layers run the same three kernels on the same shapes, and a trace tells
their calls apart by nothing, so the flash functions take the list of the
layers' kinds and return the MEAN call over it: every layer calls each
kernel as often as every other, so calls x mean is the work of the calls
seen.
"""

from __future__ import annotations


def live_pairs(seq: int, window=None) -> float:
    """(query, key) pairs that causality leaves, and the window where there
    is one: key p is seen by query t iff 0 <= t - p < window.

    >>> live_pairs(4), live_pairs(4, 2), live_pairs(4, 9)
    (10.0, 7.0, 10.0)
    """
    if window is None or window >= seq:
        return seq * (seq + 1) / 2
    return window * (window + 1) / 2 + float(seq - window) * window


def _mean_pairs(seq, window, kinds) -> float:
    kinds = list(kinds) or ["full_attention"]
    return sum(
        live_pairs(seq, window if k == "sliding_attention" else None) for k in kinds
    ) / len(kinds)


def flash_fwd(batch, heads, kv_heads, seq, dim_head, window=None, kinds=(), itemsize=2, **_):
    """Scores and weighted values over the live pairs; reads q once per query
    head and k, v once per K/V head, writes o and the fp32 log-sum-exp.

    >>> flash_fwd(1, 2, 1, 4, 8)   # 2 heads x 10 pairs x 2 matmuls x 2 x 8
    (640.0, 416)
    """
    ops = 4.0 * batch * heads * _mean_pairs(seq, window, kinds) * dim_head
    rows, kv_rows = batch * heads * seq, batch * kv_heads * seq
    return ops, rows * (2 * dim_head * itemsize + 4) + kv_rows * 2 * dim_head * itemsize


def flash_dq(batch, heads, kv_heads, seq, dim_head, window=None, kinds=(), itemsize=2, **_):
    """s, dp, dq: three matmuls over the live pairs; reads q, do, lse, delta
    per query head and k, v per K/V head, writes dq.

    >>> flash_dq(1, 2, 1, 4, 8)
    (960.0, 576)
    """
    ops = 6.0 * batch * heads * _mean_pairs(seq, window, kinds) * dim_head
    rows, kv_rows = batch * heads * seq, batch * kv_heads * seq
    return ops, rows * (3 * dim_head * itemsize + 8) + kv_rows * 2 * dim_head * itemsize


def flash_dkv(batch, heads, kv_heads, seq, dim_head, window=None, kinds=(), itemsize=2, **_):
    """s, dp, dv, dk: four matmuls over the live pairs; reads q, do, lse,
    delta per query head and k, v per K/V head, writes dk and dv.

    >>> flash_dkv(1, 2, 1, 4, 8)
    (1280.0, 576)
    """
    ops = 8.0 * batch * heads * _mean_pairs(seq, window, kinds) * dim_head
    rows, kv_rows = batch * heads * seq, batch * kv_heads * seq
    return ops, rows * (2 * dim_head * itemsize + 8) + kv_rows * 4 * dim_head * itemsize


def gmm(moe_rows, dim, expert_dim, experts_held, out_itemsize=2, itemsize=2, **_):
    """One grouped product of a routed layer over the rows REALLY present
    (`moe_rows`: the mean number of assignments a layer made here per step;
    the buffer's empty rows cost nothing). Every product of the layer, forward
    or transposed, is rows x dim x expert_dim: gate and up are dim ->
    expert_dim, down is expert_dim -> dim, and each `dlhs` is the other's
    shape. Reads the rows and the held experts' matrices once, writes the
    result rows.

    >>> gmm(10, 4, 2, 3)
    (160.0, 168)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, (moe_rows * (dim + expert_dim) + experts_held * dim * expert_dim) * itemsize


def gmm_drhs(moe_rows, dim, expert_dim, experts_held, itemsize=2, **_):
    """`drhs[g] = lhs_g^T dout_g`: the same operations, the rows of both
    operands read once and the held experts' gradients written in float32.

    >>> gmm_drhs(10, 4, 2, 3)
    (160.0, 216)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, moe_rows * (dim + expert_dim) * itemsize + experts_held * dim * expert_dim * 4
