"""Operations and bytes that the latent-attention generation cell's work
requires, from shapes: `costs.py`'s part for the latent decode attention, the
grouped products of a routed layer at a token step's sizes, and the useful
operations of a whole token step. Kept with the benchmark so that no PR that
claims a gain can move the yardstick.

Every function but `token_step_flops` returns `(operations, bytes)` for ONE
call; the shapes are the ones `loops/generate_lm.py` gives (`positions`: the
mean live length of the traced token steps; `moe_rows`, `moe_touched`: the
assignments a routed layer made to the experts held, and the held experts
with at least one, per layer and step, as the program counted them).
"""

from __future__ import annotations


def latent_attend(batch, heads, kv_rank, rope, positions, itemsize=2, **_):
    """One layer's latent decode attention of one token step: every head of
    every row against the row's live positions, scores over kv_rank + rope
    numbers and the weighted sum over kv_rank; reads each live position's
    latent and rotary key once. The same work whether a kernel or XLA's
    products do it.

    >>> latent_attend(2, 4, 16, 8, 10.0)   # 2 x 4 x 10 x 2 x (16 + 8 + 16)
    (6400.0, 960.0)
    """
    ops = 2.0 * batch * heads * (2 * kv_rank + rope) * positions
    return ops, batch * positions * (kv_rank + rope) * itemsize


def gmm_touched(moe_rows, moe_touched, dim, expert_dim, itemsize=2, **_):
    """One grouped product of a routed layer at a token step: the rows
    present times dim x expert_dim; reads the matrices of the experts really
    TOUCHED (one with no row owes no read) and the rows, writes the rows.

    >>> gmm_touched(10, 3, 4, 2)
    (160.0, 168)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, (moe_touched * dim * expert_dim + moe_rows * (dim + expert_dim)) * itemsize


def token_step_flops(batch, heads, kv_rank, rope, nope, v_dim, q_rank, dim, vocab, kinds,
                     dense_dim, expert_dim, shared_dim, experts_total, positions, moe_rows, **_):
    """Useful operations of ONE token step: `batch` rows through the held
    weights (each row's 8 choices only where they fell on a held expert:
    `moe_rows` assignments a routed layer), and the latent attention over the
    live positions. Nothing recomputed or padded counts.

    >>> token_step_flops(1, 2, 4, 2, 2, 2, 3, 8, 10, ["dense", "routed"], 6, 4, 4, 8, 5.0, 1.5)
    2096.0
    """
    attention = (dim * q_rank + q_rank * heads * (nope + rope) + dim * (kv_rank + rope)
                 + heads * nope * kv_rank + heads * kv_rank * v_dim + heads * v_dim * dim)
    per_row = dim * vocab
    routed = 0.0
    for kind in kinds:
        per_row += attention
        if kind == "dense":
            per_row += 3 * dim * dense_dim
        else:
            per_row += 3 * dim * shared_dim + dim * experts_total
            routed += moe_rows * 3 * dim * expert_dim
    attend = len(kinds) * latent_attend(batch, heads, kv_rank, rope, positions)[0]
    return 2.0 * (batch * per_row + routed) + attend
