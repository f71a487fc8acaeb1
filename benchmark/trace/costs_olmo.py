"""Operations and bytes that the linear-and-full generation cell's work
requires, from shapes: `costs.py`'s part for the gated delta rule's token
step and the useful operations of a whole token step. Kept with the benchmark
so that no PR that claims a gain can move the yardstick.

`delta_step` returns `(operations, bytes)` for ONE call (one linear layer,
one token step, every row and head); the shapes are the ones
`loops/generate_hybrid.py` gives (`positions`: the mean live length of the
traced token steps). The same whichever implementation is on the path: a
kernel or XLA's fusion owes the same one read and one write of the state.
"""

from __future__ import annotations


def delta_step(batch, lin_heads, dk, dv, state_itemsize=4, **_):
    """One linear layer's state update of one token step: per row and head
    S^T k and S^T q (2 dk dv each), the decay, the outer product and the sum
    (3 dk dv); the state once in and once out in float32, plus q, k, v, o,
    alpha and beta.

    >>> delta_step(2, 3, 4, 8)   # 6 states of 4 x 8: 7 x 32 ops; 2 x 128 + (8 + 16 + 2) x 4 B
    (1344.0, 2160)
    """
    states = batch * lin_heads
    ops = 7.0 * states * dk * dv
    return ops, states * (2 * dk * dv * state_itemsize + (2 * dk + 2 * dv + 2) * 4)


def token_step_flops(batch, dim, heads, head_dim, lin_heads, dk, dv, taps, ff, vocab, kinds,
                     positions, **_):
    """Useful operations of ONE token step: `batch` rows through every
    weight held, the linear layers' convolution and state update, and the
    full layers' attention over the live positions. Nothing recomputed or
    padded counts.

    >>> token_step_flops(1, 8, 2, 4, 2, 2, 4, 4, 6, 10, ["linear", "full"], 5.0)
    2224.0
    """
    width = lin_heads * (2 * dk + dv)
    linear = dim * (width + 2 * lin_heads + lin_heads * dv) + lin_heads * dv * dim + taps * width
    full = dim * 3 * heads * head_dim + heads * head_dim * dim
    per_row = dim * vocab
    other = 0.0
    for kind in kinds:
        per_row += 3 * dim * ff + (linear if kind == "linear" else full)
        if kind == "linear":
            other += delta_step(batch, lin_heads, dk, dv)[0]
        else:
            other += 4.0 * batch * heads * head_dim * positions
    return 2.0 * batch * per_row + other
