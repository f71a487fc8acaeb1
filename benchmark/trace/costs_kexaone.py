"""Operations and bytes that the window-and-full generation cell's work
requires, from shapes: `costs.py`'s part for a verify step's attention over
the full K/V layers, the grouped products of a routed block at a verify
step's sizes, and the useful operations of a whole verify step. Kept with the
benchmark so that no PR that claims a gain can move the yardstick.

The shapes are the ones `loops/generate_kexaone.py` gives (`positions`: the
mean live length of the turns' steps; `step_positions`: the positions a step
feeds a row, the committed token and its draft; `tokens_per_step`: the
positions of those that STAYED, by the loop's own count; `moe_rows`,
`moe_touched`: the assignments a routed block made to the experts held, and
the held experts with at least one, per block and step, as the program
counted them; `attn`, `kinds`: the trunk's layers; `drafts`: the multi-token
module's blocks, each a full layer with a routed feed-forward).
"""

from __future__ import annotations


def global_attend(batch, heads, kv_heads, head_dim, positions, step_positions, attn, drafts,
                  itemsize=2, **_):
    """ONE verify step's attention over every full K/V layer (the trunk's and
    the module's): each of a row's `step_positions` queries, every head,
    against the row's live positions (scores and the weighted sum: 4 head_dim
    operations a head a position); reads each live position's K and V of the
    `kv_heads` heads once a layer. The same work whether a kernel or XLA's
    products do it.

    >>> global_attend(2, 4, 2, 8, 10.0, 2, ["window", "full"], 1)   # 2 layers
    (10240.0, 2560.0)
    """
    layers = sum(k == "full" for k in attn) + drafts
    ops = 4.0 * batch * step_positions * heads * head_dim * positions
    return layers * ops, layers * batch * positions * 2 * kv_heads * head_dim * itemsize


def gmm_touched(moe_rows, moe_touched, dim, expert_dim, itemsize=2, **_):
    """One grouped product of a routed block at a verify step: the rows
    present times dim x expert_dim; reads the matrices of the experts really
    TOUCHED (one with no row owes no read) and the rows, writes the rows.

    >>> gmm_touched(10, 3, 4, 2)
    (160.0, 168)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, (moe_touched * dim * expert_dim + moe_rows * (dim + expert_dim)) * itemsize


def verify_step_flops(batch, dim, heads, kv_heads, head_dim, window, vocab, attn, kinds, drafts,
                      dense_dim, expert_dim, shared_dim, experts_total, positions,
                      step_positions, tokens_per_step, moe_rows, **_):
    """Useful operations of ONE verify step: the positions that STAYED
    (`tokens_per_step` a row, of the `step_positions` fed) through the held
    weights of trunk and module, each routed block's assignments to held
    experts in the same share (`moe_rows` counts the fed positions'), and the
    attention of those positions over what they see. A rejected draft's work,
    and nothing recomputed or padded, counts.

    >>> verify_step_flops(1, 8, 2, 1, 4, 3, 10, ["window", "full"], ["dense", "routed"], 1,
    ...                   6, 4, 4, 8, 5.0, 2, 1.0, 3.0)
    3648.0
    """
    attention = dim * (heads + 2 * kv_heads) * head_dim + heads * head_dim * dim
    routed_row = 3 * dim * shared_dim + dim * experts_total
    per_row = dim * vocab  # the trunk's head
    blocks, sees = 0, 0.0
    for layer, kind in zip(attn, kinds):
        per_row += attention + (3 * dim * dense_dim if kind == "dense" else routed_row)
        blocks += kind != "dense"
        sees += min(window, positions) if layer == "window" else positions
    # the module: its projection, one full routed block, the head once more
    per_row += drafts * (2 * dim * dim + attention + routed_row + dim * vocab)
    blocks += drafts
    sees += drafts * positions
    stayed = tokens_per_step / step_positions
    routed = blocks * moe_rows * stayed * 3 * dim * expert_dim
    attend = 4.0 * batch * tokens_per_step * heads * head_dim * sees
    return 2.0 * (batch * tokens_per_step * per_row + routed) + attend
