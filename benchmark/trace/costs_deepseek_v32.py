"""Operations and bytes that the learned-sparse-attention generation cell's
work requires, from shapes: `costs.py`'s part for the lightning indexer's
score over a row's live positions, the latent attention over the SELECTED
positions, the grouped products of a routed layer at a token step's sizes,
and the useful operations of a whole token step. Kept with the benchmark so
that no PR that claims a gain can move the yardstick.

`index_score` and `sparse_attend` count ONE TOKEN STEP's work of every layer
(the readers count steps, not calls: the attend has no kernel of its own to
count by); `gmm_touched` ONE call. The shapes are the ones
`loops/generate_deepseek_v32.py` gives (`positions`: the mean live length of
the traced token steps; `index_topk`: the positions a query attends;
`moe_rows`, `moe_touched`: the assignments a routed layer made to the experts
held, and the held experts with at least one, per layer and step, as the
program counted them).
"""

from __future__ import annotations


def index_score(batch, index_heads, index_dim, positions, kinds, itemsize=2, **_):
    """One token step's index scores, every layer: each row's `index_heads`
    queries against the one key of each LIVE position (a product, a relu, a
    weight and a sum a head); reads every live position's key once and writes
    its float32 score.

    >>> index_score(2, 4, 16, 10.0, ["dense", "routed"])   # 2 layers x 2 x 10 x (4 x 34; 36 B)
    (5440.0, 1440.0)
    """
    ops = batch * positions * index_heads * (2.0 * index_dim + 2)
    return len(kinds) * ops, len(kinds) * batch * positions * (index_dim * itemsize + 4.0)


def sparse_attend(batch, heads, kv_rank, rope, index_topk, positions, kinds, itemsize=2, **_):
    """One token step's latent attention, every layer, over the positions
    SELECTED: every head of every row against min(index_topk, live)
    positions, scores over kv_rank + rope numbers and the weighted sum over
    kv_rank; reads each selected position's latent and rotary key once, and
    nothing of the positions left out. A form that reads or multiplies the
    whole cache does more than this and reads under its roofline for it.

    >>> sparse_attend(2, 4, 16, 8, 5, 10.0, ["dense", "routed"])   # 2 x 2 x 4 x 5 x 80; 2 x 2 x 5 x 48
    (6400.0, 960)
    """
    attended = min(index_topk, positions)
    ops = 2.0 * batch * heads * (2 * kv_rank + rope) * attended
    return len(kinds) * ops, len(kinds) * batch * attended * (kv_rank + rope) * itemsize


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
                     dense_dim, expert_dim, shared_dim, experts_total, index_heads, index_dim,
                     index_topk, positions, moe_rows, **_):
    """Useful operations of ONE token step: `batch` rows through the held
    weights (the indexer's three projections among them; each row's 8 choices
    only where they fell on a held expert: `moe_rows` assignments a routed
    layer), the index scores over the live positions and the latent attention
    over the selected ones. Nothing recomputed or padded counts.

    >>> token_step_flops(1, 2, 4, 2, 2, 2, 3, 8, 10, ["dense", "routed"], 6, 4, 4, 8, 2, 4, 3,
    ...                  5.0, 1.5)
    2424.0
    """
    attention = (dim * q_rank + q_rank * heads * (nope + rope) + dim * (kv_rank + rope)
                 + heads * nope * kv_rank + heads * kv_rank * v_dim + heads * v_dim * dim
                 + q_rank * index_heads * index_dim + dim * index_dim + dim * index_heads)
    per_row = dim * vocab
    routed = 0.0
    for kind in kinds:
        per_row += attention
        if kind == "dense":
            per_row += 3 * dim * dense_dim
        else:
            per_row += 3 * dim * shared_dim + dim * experts_total
            routed += moe_rows * 3 * dim * expert_dim
    shapes = dict(batch=batch, positions=positions, kinds=kinds)
    scored = index_score(index_heads=index_heads, index_dim=index_dim, **shapes)[0]
    attended = sparse_attend(heads=heads, kv_rank=kv_rank, rope=rope, index_topk=index_topk,
                             **shapes)[0]
    return 2.0 * (batch * per_row + routed) + scored + attended
