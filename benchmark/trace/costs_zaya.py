"""Operations and bytes that the convolved-latent generation cell's work
requires, from shapes: `costs.py`'s part for a token step's attention over
grouped K/V heads in a compressed latent, the three grouped products of a
top-1 routed layer, and the useful operations of a whole token step. Kept with
the benchmark so that no PR that claims a gain can move the yardstick.

The shapes are the ones `loops/generate_zaya.py` gives (`positions`: the mean
live length of the turns' token steps; `moe_rows`, `moe_touched`: the
assignments a routed layer made, one a row, and the experts with at least one,
per layer and step, as the program counted them). The same whichever
implementation is on the path: a kernel or XLA's product owes the same one read
of each live position's K and V.
"""

from __future__ import annotations


def cca_attend(batch, heads, kv_heads, head_dim, positions, depth, itemsize=2, **_):
    """ONE token step's attention over every layer: a row's query, every head,
    against the row's live positions (scores and the weighted sum: 4 head_dim
    operations a head a position); reads each live position's K and V of the
    `kv_heads` heads once a layer.

    >>> cca_attend(2, 8, 2, 16, 10.0, 3)
    (30720.0, 7680.0)
    """
    ops = 4.0 * batch * heads * head_dim * positions
    return depth * ops, depth * batch * positions * 2 * kv_heads * head_dim * itemsize


def gmm_touched(moe_rows, moe_touched, dim, expert_dim, itemsize=2, **_):
    """One grouped product of a routed layer at a token step (a SwiGLU expert
    has three: gate, up and down): the rows present times dim x expert_dim;
    reads the matrices of the experts really TOUCHED (one with no row owes no
    read) and the rows, writes the rows.

    >>> gmm_touched(10, 3, 4, 2)
    (160.0, 168)
    """
    ops = 2.0 * moe_rows * dim * expert_dim
    return ops, (moe_touched * dim * expert_dim + moe_rows * (dim + expert_dim)) * itemsize


def token_step_flops(batch, dim, depth, heads, kv_heads, head_dim, vocab, expert_dim, experts,
                     router_dim, positions, moe_rows, **_):
    """Useful operations of ONE token step: `batch` rows through every weight
    they meet (the fused projection and the output one, the two convolutions'
    taps, the router's MLP, ONE expert's three matrices by the assignments the
    program counted, `moe_rows` a layer, and the head over the whole
    vocabulary), and the attention over the live positions. Nothing recomputed
    or padded counts.

    >>> token_step_flops(1, 8, 2, 2, 2, 4, 10, 6, 4, 3, 5.0, 1.0)
    2936.0
    """
    groups = heads + kv_heads
    attention = (dim * (heads + 2 * kv_heads) * head_dim + heads * head_dim * dim
                 + 2 * groups * head_dim + 2 * groups * head_dim * head_dim)
    router = dim * router_dim + 2 * router_dim * router_dim + router_dim * experts
    per_row = depth * (attention + router) + dim * vocab
    other = depth * (4.0 * batch * heads * head_dim * positions
                     + 2.0 * moe_rows * 3 * dim * expert_dim)
    return 2.0 * batch * per_row + other
