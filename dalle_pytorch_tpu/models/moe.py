"""A routed feed-forward layer that is told which experts it holds.

The router keeps its published width (`experts_total` outputs, the
`experts_per_token` largest of the softmax, or of the sigmoids,
renormalised); this chip holds
the `experts_held = (first, count)` of them and computes their part of the
result for the tokens routed to them. What the absent experts would add is
left out, as expert parallelism leaves it to the chips that hold them: on
one chip the layer runs without its exchange, and nothing here stands in
for it.

Assignments to held experts are sorted by expert into a buffer of
`buffer_rows` rows (static: a bound on the assignments made here, not a
capacity per expert), the experts' three products run grouped over it
(`ops/grouped_matmul.py`: rows past the assignments cost nothing and hold
nothing that may be read), and the weighted results are summed back per
token. Both moves are gathers, forward and backward (each is the other's
transpose): a scatter-add of 10^5 rows is the slow way on the chip. An
assignment past the buffer would be a dropped token; the layer counts them
(`moe_dropped`) and the callers require 0.

Every pass over the buffer is sized by the rows PRESENT, `kept =
min(assignments, buffer_rows)`, which `route` counts on the device, and not
by the buffer's bound: as the grouped products are. A pass is a loop over
chunks of `CHUNK_ROWS` rows that stops at the last row present (`_fill`) and
writes its chunks in place, into a buffer nobody initialised or over one the
caller has done with; the rows past `kept` hold anything at all, and are
selected around, never multiplied by 0. So walk the tokens' rows into the
buffer (`experts`: forward, and again in its backward, which keeps no copy),
SiLU(gate) x up and its transpose, the sum of the buffer's two cotangents,
and the rows' cotangent with `d weights` beside it (`_rows_cotangent`: one
gather of the tokens' cotangent serves both). The way back sums, per token,
the rows of its present assignments only (`_sum_back`): the tokens stand in
the order of how many they have, so those with a j-th present assignment
are a prefix, and rank j's steps stop where the prefix ends. A full buffer
is walked whole, an empty one not at all. `moe_moved` counts the buffer
rows a pass walks: `kept` rounded up to the chunk.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax

from dalle_pytorch_tpu.ops.grouped_matmul import (
    grouped_matmul, grouped_matmul_dlhs, grouped_matmul_drhs)

CHUNK_ROWS = 2048  # rows a loop step moves: 9 MB of 2,304 bf16


def _chunk(n_rows: int) -> int:
    return min(CHUNK_ROWS, n_rows)


def choose(probs: jnp.ndarray, per_token: int, bias=None, groups: Tuple[int, int] = (1, 1)):
    """`(top [T, k], experts [T, k])`: the experts a token is routed to, by
    `probs + bias` where there is a bias, and their own scores `probs`.
    `groups = (n, kept)` with n > 1 limits the choice: the E outputs stand
    in n groups of E / n, a group's score is the sum of its two largest
    biased scores, the `kept` best groups stay, and the k experts are the
    largest biased scores within those (ties, everywhere: the lower index)."""
    n_group, kept = groups
    if n_group == 1:
        if bias is None:
            return lax.top_k(probs, per_token)
        experts = lax.top_k(probs + bias, per_token)[1]
        return jnp.take_along_axis(probs, experts, axis=-1), experts
    biased = probs if bias is None else probs + bias
    by_group = biased.reshape(biased.shape[0], n_group, -1)
    best = lax.top_k(jnp.sum(lax.top_k(by_group, 2)[0], axis=-1), kept)[1]  # [T, kept]
    stays = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)  # [T, n_group]
    limited = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(biased.shape)
    experts = lax.top_k(limited, per_token)[1]
    return jnp.take_along_axis(probs, experts, axis=-1), experts


def route(probs: jnp.ndarray, per_token: int, held: Tuple[int, int], buffer_rows: int,
          bias=None, groups: Tuple[int, int] = (1, 1), renormalise: bool = True):
    """From router scores [T, E] (a softmax's probabilities, or sigmoids)
    to the buffer's layout. `bias` [E]: the experts are CHOSEN by `probs +
    bias` (a score-correction bias); the weights are the chosen experts' own
    scores all the same. `groups`: a group-limited choice (`choose`).

    Returns a dict: `weights` [T, k] (the chosen experts' scores,
    renormalised over the chosen, or with `renormalise=False` as they are), `experts` [T, k], `assign` [R] (the assignment, t * k +
    slot, that buffer row r holds), `kept` (the rows that hold one), `pos`
    [T, k] (the buffer row of each assignment, R where it has none: not
    held here, or dropped), `group_sizes` [count] (clipped to the buffer),
    the way back by rank (`_ranked`), and the counters `load` [count],
    `rows`, `dropped`, `moved`.
    """
    first, count = held
    buffer_rows = min(buffer_rows, probs.shape[0] * per_token)
    top, experts = choose(probs, per_token, bias, groups)
    weights = top / jnp.sum(top, axis=-1, keepdims=True) if renormalise else top
    local = experts - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    # one sort gives the order (by held expert, the rest last) and, from the
    # sorted keys, each held expert's load without a scatter
    sorted_key, order = lax.sort(
        (key, jnp.arange(key.shape[0], dtype=key.dtype)), num_keys=1, is_stable=True)
    ends = jnp.searchsorted(sorted_key, jnp.arange(1, count + 1, dtype=key.dtype))
    load = jnp.diff(ends, prepend=0)
    rows = ends[-1]
    kept = jnp.minimum(rows, buffer_rows)
    ends = jnp.minimum(ends, buffer_rows)
    pos = jnp.argsort(order)  # where each assignment stands in that order
    pos = jnp.where(pos < kept, pos, buffer_rows).reshape(experts.shape)
    chunk = _chunk(buffer_rows)
    return {
        "weights": weights, "experts": experts,
        "assign": order[:buffer_rows], "kept": kept, "pos": pos,
        "group_sizes": jnp.diff(ends, prepend=0).astype(jnp.int32),
        "back": _ranked(pos, buffer_rows),
        "load": load, "rows": rows, "dropped": rows - kept,
        "moved": jnp.minimum(-(-kept // chunk) * chunk, buffer_rows),
    }


def _ranked(pos, buffer_rows: int):
    """The way back from the buffer, by rank: a token's present assignments
    stand first among its slots (in slot order), and the tokens stand in the
    order of how many they have, so the tokens with a rank-j assignment are
    the first `holders[j]`. `row`, `slot` [k * T]: at j * T + i the buffer
    row (R where there is none) and the assignment of the rank-j present
    assignment of the i-th token in that order; `token` [T] that order's
    inverse (where each token stands in it); `holders` [k]."""
    n_tok, per_token = pos.shape
    slots = jnp.broadcast_to(jnp.arange(per_token, dtype=pos.dtype), pos.shape)
    present = pos < buffer_rows
    _, row, slot = lax.sort(((~present).astype(pos.dtype), pos, slots),
                            dimension=1, num_keys=1, is_stable=True)
    have = jnp.sum(present, axis=1, dtype=pos.dtype)
    _, by_count = lax.sort((-have, jnp.arange(n_tok, dtype=pos.dtype)),
                           num_keys=1, is_stable=True)
    return {
        "row": row[by_count].T.reshape(-1),
        "slot": (by_count[:, None] * per_token + slot[by_count]).T.reshape(-1),
        "token": jnp.argsort(by_count),
        "holders": jnp.sum(have[:, None] > jnp.arange(per_token), axis=0, dtype=pos.dtype),
    }


def _fill(live_rows, buffers, chunk_of, read=False, once=False):
    """`buffers` with their rows below `live_rows` set to what
    `chunk_of(start, size, current)` gives for the rows from `start` on:
    written a chunk at a time, in place, by a loop that stops at the last
    live row; nothing writes the rows past the last chunk. A buffer is a
    `ShapeDtypeStruct`: made here and never initialised (zeroing it would be
    a pass over the buffer); or an array the caller reads no more, written
    over as an elementwise operation would write over its operand. Without
    `read`, `current` is None, and a last chunk that would overhang starts
    earlier and writes some rows again. With `read` (arrays only) `current`
    is the buffers' own rows there, and an overhanging chunk keeps what the
    rows it visits again hold; `once` for a step with a reader of a buffer
    whose result is not written back into it: the rows are then read once,
    behind a barrier, before anything of the chunk is written (XLA would
    else copy the whole buffer at every step)."""
    n_rows = buffers[0].shape[0]
    size = _chunk(n_rows)

    def step(i, held):
        start = jnp.minimum(i * size, n_rows - size)
        if read:
            current = [_window(b, start, size) for b in held]
            if once:
                current = lax.optimization_barrier(current)
            new = start + jnp.arange(size) >= i * size
            chunks = [jnp.where(new.reshape(-1, *[1] * (c.ndim - 1)), c, old)
                      for c, old in zip(chunk_of(start, size, current), current)]
        else:
            chunks = chunk_of(start, size, None)
        return tuple(lax.dynamic_update_slice_in_dim(b, c.astype(b.dtype), start, 0)
                     for b, c in zip(held, chunks))

    made = tuple(lax.empty(b.shape, b.dtype) if isinstance(b, jax.ShapeDtypeStruct) else b
                 for b in buffers)
    return lax.fori_loop(0, -(-live_rows // size), step, made)


def _window(x, start, size):
    return lax.dynamic_slice_in_dim(x, start, size, 0)


def _take(x, index):
    """x[index] for an index that is in bounds: no pass mends or masks it."""
    return x.at[index].get(mode="promise_in_bounds")


def _live(start, size, kept):
    """[size, 1] bool: which of a chunk's rows hold an assignment."""
    return (start + jnp.arange(size) < kept)[:, None]


@functools.partial(jax.jit, inline=True, static_argnames=("per_token", "buffer_rows"))
def _rows_of_tokens(h, assign, kept, over, *, per_token, buffer_rows):
    """[R, D]: row r is the token's that r's assignment belongs to; written
    over the array `over`, which the caller reads no more, where one is given."""
    made = jax.ShapeDtypeStruct((buffer_rows, h.shape[1]), h.dtype) if over is None else over
    return _fill(kept, (made,), lambda start, size, _: (
        _take(h, _window(assign, start, size) // per_token),))[0]


@functools.partial(jax.jit, inline=True, static_argnames=("out_dtype",))
def _sum_back(rows, weights, back, *, out_dtype):
    """[T, D]: each token's sum of its present assignments' buffer rows,
    times their `weights` [T, k] where given: products and sums on the VPU
    in float32 (no MXU pass rounds a weight), in slot order. Rank 0 walks
    every token and starts its sum (0 where it has no assignment here); rank
    j adds to the first `holders[j]` sums; the tokens' own order comes back
    by one gather of T rows."""
    n_rows, n_tok = rows.shape[0], back["token"].shape[0]
    size = _chunk(n_tok)
    chunks = -(-back["holders"].at[0].set(n_tok) // size)
    ends = jnp.cumsum(chunks)

    def step(i, sums):
        rank = jnp.sum(i >= ends, dtype=i.dtype)
        first = (i - (ends[rank] - chunks[rank])) * size
        start = jnp.minimum(first, n_tok - size)
        at = rank * n_tok + start
        row = _window(back["row"], at, size)
        picked = _take(rows, jnp.minimum(row, n_rows - 1)).astype(jnp.float32)
        if weights is not None:
            picked = picked * _take(weights.reshape(-1), _window(back["slot"], at, size))[:, None]
        new = (start + jnp.arange(size) >= first)[:, None]  # not an overhang's second visit
        so_far = jnp.where(new & (rank == 0), 0, _window(sums, start, size))
        picked = jnp.where(new & (row < n_rows)[:, None], picked, 0)
        return lax.dynamic_update_slice_in_dim(sums, so_far + picked, start, 0)

    sums = lax.fori_loop(0, ends[-1], step, lax.empty((n_tok, rows.shape[1]), jnp.float32))
    return _take(sums.astype(out_dtype), back["token"])


@jax.custom_vjp
def to_tokens(rows, weights, assign, kept, pos, back):
    """[T, D]: each token's weighted sum of its assignments' rows."""
    return _sum_back(rows, weights, back, out_dtype=rows.dtype)


def _to_tokens_fwd(rows, weights, assign, kept, pos, back):
    return to_tokens(rows, weights, assign, kept, pos, back), (rows, weights, assign, kept, pos)


@functools.partial(jax.jit, inline=True)
def _rows_cotangent(rows, weights, assign, kept, pos, d_tokens):
    """(d rows [R, D], d weights [T, k]) of `to_tokens`. One walk over the
    rows present gathers each row's token's cotangent once: times the row's
    weight it is the row's cotangent, and its product with the row the
    weight's (found again by `pos`, from R numbers)."""
    per_token = pos.shape[1]

    def chunk_of(start, size, current):
        slot = _window(assign, start, size)
        d_token = _take(d_tokens, slot // per_token).astype(jnp.float32)
        weight = _take(weights.reshape(-1), slot).astype(jnp.float32)[:, None]
        return (jnp.where(_live(start, size, kept), d_token * weight, 0),
                jnp.sum(current[0].astype(jnp.float32) * d_token, axis=-1))

    # the rows' cotangent is written where the rows were
    d_rows, d_weight = _fill(kept, (rows, jnp.zeros(rows.shape[:1], jnp.float32)), chunk_of,
                             read=True, once=True)
    n_rows = rows.shape[0]
    d_weights = jnp.where(pos < n_rows, _take(d_weight, jnp.minimum(pos, n_rows - 1)), 0)
    return d_rows, d_weights.astype(weights.dtype)


def _to_tokens_bwd(res, d_tokens):
    return (*_rows_cotangent(*res, d_tokens), None, None, None, None)


to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def _gated(gate, up):
    return nn.silu(gate) * up


@functools.partial(jax.jit, inline=True)
def _gated_rows(gate, up, kept):
    """[R, F]: SiLU(gate) x up over the rows present."""
    return _fill(kept, (jax.ShapeDtypeStruct(gate.shape, gate.dtype),), lambda start, size, _: (
        _gated(_window(gate, start, size), _window(up, start, size)),))[0]


@functools.partial(jax.jit, inline=True)
def _gated_cotangents(gate, up, d_act, kept):
    """(d gate, d up) of `_gated_rows`, written where gate and up were; 0 in
    a walked row that holds no assignment (`gmm_drhs` sums over a tile's
    rows: 0 x NaN)."""
    def chunk_of(start, size, gate_up):
        live = _live(start, size, kept)
        return [jnp.where(live, d, 0)
                for d in jax.vjp(_gated, *gate_up)[1](_window(d_act, start, size))]

    return _fill(kept, (gate, up), chunk_of, read=True)


@functools.partial(jax.jit, inline=True)
def _sum_of_rows(a, b, kept):
    """[R, D]: a + b over the rows present, written where `a` was."""
    return _fill(kept, (a,), lambda start, size, current: (
        current[0] + _window(b, start, size),), read=True)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def experts(h, assign, kept, back, group_sizes, w_gate, w_up, w_out, buffer_rows):
    """[R, D] from tokens [T, D]: each buffer row's token (`moe_dispatch`),
    then (SiLU(rows @ gate) x (rows @ up)) @ out, each row by its own
    group's matrices (`moe_experts`): three grouped products, and between
    them passes over the rows present."""
    return _experts_fwd(h, assign, kept, back, group_sizes, w_gate, w_up, w_out, buffer_rows)[0]


def _tokens_rows(h, assign, kept, back, buffer_rows, over=None):
    with jax.named_scope("moe_dispatch"):
        return _rows_of_tokens(h, assign, kept, over, buffer_rows=buffer_rows,
                               per_token=back["row"].shape[0] // h.shape[0])


def _experts_fwd(h, assign, kept, back, group_sizes, w_gate, w_up, w_out, buffer_rows):
    rows = _tokens_rows(h, assign, kept, back, buffer_rows)
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, w_gate, group_sizes)
        up = grouped_matmul(rows, w_up, group_sizes)
        act = _gated_rows(gate, up, kept)
        out = grouped_matmul(act, w_out, group_sizes)
    # not the tokens' rows: 1.2 GB that the backward needs last, and gathers again
    return out, (h, assign, kept, back, group_sizes, w_gate, w_up, w_out, gate, up, act)


def _experts_bwd(buffer_rows, res, d_out):
    """Every buffer of the backward is written where one it has done with
    was, and one is made at a time (the barriers): the step's plan is
    within a few percent of the chip's memory."""
    h, assign, kept, back, group_sizes, w_gate, w_up, w_out, gate, up, act = res
    with jax.named_scope("moe_experts"):
        d_act = grouped_matmul_dlhs(w_out, group_sizes, d_out)
        d_w_out = grouped_matmul_drhs(act, w_out, group_sizes, d_out)
        d_gate, d_up = _gated_cotangents(gate, up, d_act, kept)
        # the tokens' rows again, where the result's cotangent was
        d_w_out, d_gate, d_out = lax.optimization_barrier((d_w_out, d_gate, d_out))
    rows = _tokens_rows(h, assign, kept, back, buffer_rows, over=d_out)
    with jax.named_scope("moe_experts"):
        d_w_gate = grouped_matmul_drhs(rows, w_gate, group_sizes, d_gate)
        d_w_up = grouped_matmul_drhs(rows, w_up, group_sizes, d_up)
        # and they are read no more before the buffer's two cotangents are made
        d_w_gate, d_w_up, d_gate, d_up = lax.optimization_barrier((d_w_gate, d_w_up, d_gate, d_up))
        by_gate = grouped_matmul_dlhs(w_gate, group_sizes, d_gate)
        by_up = grouped_matmul_dlhs(w_up, group_sizes, d_up)
        d_rows = _sum_of_rows(by_gate, by_up, kept)
    with jax.named_scope("moe_dispatch"):  # rows have h's dtype, and so has its cotangent
        d_h = _sum_back(d_rows, None, back, out_dtype=h.dtype)
    return d_h, None, None, None, None, d_w_gate, d_w_up, d_w_out


experts.defvjp(_experts_fwd, _experts_bwd)


def _relu2(up):
    return jnp.square(nn.relu(up))


@functools.partial(jax.jit, inline=True)
def _relu2_rows(up, kept):
    """[R, F]: relu(up)^2 over the rows present, written where `up` was."""
    return _fill(kept, (up,), lambda start, size, current: (_relu2(current[0]),), read=True)[0]


def experts_ungated(h, assign, kept, back, group_sizes, w_up, w_out, buffer_rows):
    """`experts` for experts WITHOUT a gate, relu(rows @ up)^2 @ out: two
    grouped products over the same buffer, moves and counters. Forward only
    (no custom VJP: the passes over the rows present are loops that stop where
    the rows end, which reverse-mode differentiation refuses; the train step
    refuses such a model by name first, training/steps.py)."""
    rows = _tokens_rows(h, assign, kept, back, buffer_rows)
    with jax.named_scope("moe_experts"):
        act = _relu2_rows(grouped_matmul(rows, w_up, group_sizes), kept)
        return grouped_matmul(act, w_out, group_sizes)


def _fan_in(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(shape[-2]).astype(dtype)


class RoutedExperts(nn.Module):
    """SwiGLU experts behind a router, the held ones computed; beside them,
    where `shared_dim` is set, one shared expert that every token passes.
    `act="relu2"`: every expert, the shared one too, is relu(x W_up)^2 W_out
    with no gate (no `w_gate`, no `shared_gate`; forward only).

    Parameters: `router` [dim, experts_total] (with `router_dim` the MLP's in
    its place: `router_down` [dim, R], `router_1`, `router_2` [R, R],
    `router_out` [R, experts_total], their biases, `router_gamma` and the gain
    `router_norm` [R], all float32), `w_gate` and `w_up` [count,
    dim, expert_dim], `w_out` [count, expert_dim, dim]; no biases. Gate and
    up are two grouped products, so that every product of the layer, forward
    or transposed, is rows x dim x expert_dim. The shared expert is a dense
    SwiGLU (`shared_gate`, `shared_up` [dim, shared_dim], `shared_out`),
    whole on every chip that shares the layer. `score` says how the router's
    outputs become scores: `softmax` over all of them, or a `sigmoid` of
    each; the chosen ones are renormalised and multiplied by `routed_scale`.
    `score_bias`: a float32 vector `router_bias` [experts_total] is added to
    the scores for the CHOICE alone (the weights stay the scores').
    `groups`: `(groups the outputs stand in, groups a choice is limited to)`,
    `choose`'s. `renormalise=False`: the chosen experts' scores weigh them as
    they are (with one choice a renormalised weight would be 1).
    `router_dim` > 0: the router is an MLP of that width that carries a STATE
    from layer to layer (`mlp_router_probs`); the layer is then called with the
    state of the routed layer before it (None: zeros) and returns `(y, state)`.
    Where the caller makes the collection `picks` mutable the
    chosen experts [T, k] are sown there (`experts`): what a check reads.
    The matrices are stored in `param_dtype`, either router in float32. Sows
    `moe_load` [count], `moe_rows`, `moe_dropped` and `moe_moved` into the
    `stats` collection where the caller makes it mutable.
    """

    dim: int
    expert_dim: int
    experts_total: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    buffer_rows: int
    score: str = "softmax"  # "softmax" | "sigmoid"
    routed_scale: float = 1.0
    shared_dim: int = 0  # 0: no shared expert
    score_bias: bool = False
    groups: Tuple[int, int] = (1, 1)
    act: str = "swiglu"  # "swiglu" | "relu2": the experts' form
    renormalise: bool = True  # the chosen scores, before `routed_scale`
    router_dim: int = 0  # 0: one matrix; else an MLP that wide with a carried state
    norm_eps: float = 1e-6  # of the MLP router's norm
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self):
        count = self.experts_held[1]
        assert self.experts_total % self.groups[0] == 0, "groups of equal size"
        assert self.score in ("softmax", "sigmoid"), f"unknown router score {self.score!r}"
        assert self.act in ("swiglu", "relu2"), f"unknown expert form {self.act!r}"
        gated = self.act == "swiglu"
        matrix = lambda name, *shape: self.param(name, _fan_in, shape, self.param_dtype)
        width = self.router_dim
        if width:  # float32 like the one matrix: `mlp_router_probs`
            for name, shape in (("router_down", (self.dim, width)), ("router_1", (width, width)),
                                ("router_2", (width, width)),
                                ("router_out", (width, self.experts_total))):
                setattr(self, name, self.param(name, _fan_in, shape))
            for name in ("router_down_bias", "router_1_bias", "router_2_bias", "router_gamma"):
                setattr(self, name, self.param(name, nn.initializers.zeros, (width,)))
            self.router_norm = self.param("router_norm", nn.initializers.ones, (width,))
        else:
            self.router = self.param("router", _fan_in, (self.dim, self.experts_total))
        if gated:
            self.w_gate = matrix("w_gate", count, self.dim, self.expert_dim)
        self.w_up = matrix("w_up", count, self.dim, self.expert_dim)
        self.w_out = matrix("w_out", count, self.expert_dim, self.dim)
        self.router_bias = (self.param("router_bias", nn.initializers.zeros,
                                       (self.experts_total,)) if self.score_bias else None)
        if self.shared_dim:
            if gated:
                self.shared_gate = matrix("shared_gate", self.dim, self.shared_dim)
            self.shared_up = matrix("shared_up", self.dim, self.shared_dim)
            self.shared_out = matrix("shared_out", self.shared_dim, self.dim)

    def router_probs(self, h2d: jnp.ndarray) -> jnp.ndarray:
        """Float32 scores [T, experts_total] of tokens [T, dim]."""
        logits = jnp.dot(h2d.astype(jnp.float32), self.router,
                         precision=lax.Precision.HIGHEST)
        if self.score == "sigmoid":
            return jax.nn.sigmoid(logits)
        return jax.nn.softmax(logits, axis=-1)

    def mlp_router_probs(self, h2d: jnp.ndarray, carried=None):
        """`(float32 softmax scores [T, experts_total], state [T, router_dim])`
        of the router that is an MLP (arXiv:2511.17127, as `model_type: zaya`
        builds it), for tokens [T, dim] and the state `carried` [T,
        router_dim] of the routed layer before this one (None: zeros):

            s = h W_down + b_down + gamma * carried      the state handed on
            z = gelu(gelu(rmsnorm(s; g) W_1 + b_1) W_2 + b_2) W_out

        under the scope `router_mlp`, float32 at the highest precision."""
        dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
        gelu = functools.partial(jax.nn.gelu, approximate=False)
        with jax.named_scope("router_mlp"):
            s = dot(h2d.astype(jnp.float32), self.router_down) + self.router_down_bias
            if carried is not None:
                s = s + self.router_gamma * carried
            z = s * lax.rsqrt(jnp.mean(s * s, -1, keepdims=True) + self.norm_eps)
            z = gelu(dot(z * self.router_norm, self.router_1) + self.router_1_bias)
            z = dot(gelu(dot(z, self.router_2) + self.router_2_bias), self.router_out)
            return jax.nn.softmax(z, axis=-1), s

    def shared(self, h: jnp.ndarray) -> jnp.ndarray:
        """[T, dim]: the shared expert of tokens [T, dim]."""
        with jax.named_scope("moe_shared"):
            if self.act == "relu2":
                up, out = (w.astype(h.dtype) for w in (self.shared_up, self.shared_out))
                return jnp.dot(_relu2(jnp.dot(h, up)), out)
            gate, up, out = (w.astype(h.dtype) for w in
                             (self.shared_gate, self.shared_up, self.shared_out))
            return jnp.dot(_gated(jnp.dot(h, gate), jnp.dot(h, up)), out)

    def _scores(self, h2d: jnp.ndarray, carried=None):
        """`(scores [T, experts_total], the MLP router's state [T, router_dim]
        or None)` of whichever router the layer has; `carried` [..., router_dim]."""
        if self.router_dim:
            return self.mlp_router_probs(
                h2d, None if carried is None else carried.reshape(h2d.shape[0], -1))
        with jax.named_scope("moe_router"):
            return self.router_probs(h2d), None

    def choices(self, x: jnp.ndarray, carried=None) -> jnp.ndarray:
        """[B, N, k] the experts the router chooses, largest first (`carried`:
        the MLP router's state of the routed layer before, [B, N, router_dim])."""
        probs = self._scores(x.reshape(-1, x.shape[-1]), carried)[0]
        experts = choose(probs, self.experts_per_token, self.router_bias, tuple(self.groups))[1]
        return experts.reshape(*x.shape[:-1], -1)

    def __call__(self, x: jnp.ndarray, deterministic: bool = True, carried=None):
        h = x.reshape(-1, x.shape[-1])
        probs, state = self._scores(h, carried)
        with jax.named_scope("moe_dispatch"):
            biased = {} if self.router_bias is None else {"bias": self.router_bias}
            if tuple(self.groups) != (1, 1):
                biased["groups"] = tuple(self.groups)
            if not self.renormalise:
                biased["renormalise"] = False
            r = route(probs, self.experts_per_token, tuple(self.experts_held),
                      self.buffer_rows, **biased)
            weights = r["weights"]
            if self.routed_scale != 1.0:
                weights = weights * self.routed_scale
        layout = (h, r["assign"], r["kept"], r["back"], r["group_sizes"])
        if self.act == "relu2":
            rows = experts_ungated(*layout, self.w_up, self.w_out, r["assign"].shape[0])
        else:
            rows = experts(*layout, self.w_gate, self.w_up, self.w_out, r["assign"].shape[0])
        with jax.named_scope("moe_dispatch"):
            y = to_tokens(rows, weights, r["assign"], r["kept"], r["pos"], r["back"])
        if self.shared_dim:
            y = y + self.shared(h)
        for name in ("load", "rows", "dropped", "moved"):
            self.sow("stats", f"moe_{name}", r[name], reduce_fn=lambda _, new: new,
                     init_fn=lambda: None)
        if self.is_mutable_collection("picks"):
            self.sow("picks", "experts", r["experts"], reduce_fn=lambda _, new: new,
                     init_fn=lambda: None)
        if self.router_dim:
            return y.reshape(x.shape), state.reshape(*x.shape[:-1], -1)
        return y.reshape(x.shape)
