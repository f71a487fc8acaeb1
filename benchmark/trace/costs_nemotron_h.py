"""Operations and bytes that the state-space hybrid generation cell's work
requires, from shapes: `costs.py`'s part for a Mamba-2 layer's token step, the
two grouped products of an ungated routed layer, a token step's attention
over grouped K/V heads, and the useful operations of a whole token step. Kept
with the benchmark so that no PR that claims a gain can move the yardstick.

The shapes are the ones `loops/generate_nemotron_h.py` gives (`positions`:
the mean live length of the turns' token steps over rows of different
lengths; `moe_rows`, `moe_touched`: the assignments a routed layer made to
the experts held, and the held experts with at least one, per layer and step,
as the program counted them; `kinds`: the layers' one sublayer each). The
same whichever implementation is on the path: a kernel or XLA's fusion owes
the same one read and one write of the state.
"""

from __future__ import annotations


def ssm_step(batch, ssm_heads, ssm_head_dim, state, groups, ssm_layers, state_itemsize=4, **_):
    """ONE token step's state update over every Mamba-2 layer: per row, head
    and state element the decay, the outer product's term and their sum (3),
    then the product with C and its sum (2); the state once in and once out in
    float32, plus the per-column operands, B, C and y.

    >>> ssm_step(2, 4, 8, 16, 2, 3)   # 3 layers x 2 rows x 512 elements
    (15360.0, 29184)
    """
    elements = batch * ssm_heads * ssm_head_dim * state
    small = batch * (4 * ssm_heads * ssm_head_dim + 2 * groups * state) * 4
    return ssm_layers * 5.0 * elements, ssm_layers * (2 * elements * state_itemsize + small)


def gmm_touched(moe_rows, moe_touched, dim, expert_dim, itemsize=2, **_):
    """One grouped product of a routed layer at a token step (an ungated
    expert has two: up and down): the rows present times dim x expert_dim;
    reads the matrices of the experts really TOUCHED (one with no row owes no
    read) and the rows, writes the rows.

    >>> gmm_touched(10, 3, 4, 2)
    (160.0, 168)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, (moe_touched * dim * expert_dim + moe_rows * (dim + expert_dim)) * itemsize


def global_attend(batch, heads, kv_heads, head_dim, positions, kinds, itemsize=2, **_):
    """ONE token step's attention over every attention layer: a row's query,
    every head, against the row's live positions (scores and the weighted
    sum: 4 head_dim operations a head a position); reads each live position's
    K and V of the `kv_heads` heads once a layer.

    >>> global_attend(2, 4, 2, 8, 10.0, ["ssm", "attention", "routed"])
    (2560.0, 1280.0)
    """
    layers = sum(k == "attention" for k in kinds)
    ops = 4.0 * batch * heads * head_dim * positions
    return layers * ops, layers * batch * positions * 2 * kv_heads * head_dim * itemsize


def token_step_flops(batch, dim, ssm_heads, ssm_head_dim, groups, state, taps, heads, kv_heads,
                     head_dim, vocab, kinds, expert_dim, shared_dim, experts_total, positions,
                     moe_rows, **_):
    """Useful operations of ONE token step: `batch` rows through every weight
    they meet (a routed layer's held experts by the assignments the program
    counted, `moe_rows` a layer), the Mamba-2 layers' convolution and state
    update, and the attention over the live positions. Nothing recomputed or
    padded counts.

    >>> token_step_flops(1, 8, 2, 4, 1, 4, 4, 2, 1, 4, 10, ["ssm", "attention", "routed"],
    ...                  6, 6, 4, 5.0, 2.0)
    2176.0
    """
    inner, width = ssm_heads * ssm_head_dim, ssm_heads * ssm_head_dim + 2 * groups * state
    ssm = dim * (inner + width + ssm_heads) + inner * dim + taps * width
    attention = dim * (heads + 2 * kv_heads) * head_dim + heads * head_dim * dim
    routed_row = dim * experts_total + 2 * dim * shared_dim
    per_row, other = dim * vocab, 0.0
    for kind in kinds:
        if kind == "ssm":
            per_row += ssm
            other += 5.0 * batch * inner * state
        elif kind == "attention":
            per_row += attention
            other += 4.0 * batch * heads * head_dim * positions
        else:
            per_row += routed_row
            other += 2.0 * moe_rows * 2 * dim * expert_dim
    return 2.0 * batch * per_row + other
