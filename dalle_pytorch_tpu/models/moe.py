"""A routed feed-forward layer that is told which experts it holds.

The router keeps its published width (`experts_total` outputs, the
`experts_per_token` largest of the softmax, renormalised); this chip holds
the `experts_held = (first, count)` of them and computes their part of the
result for the tokens routed to them. What the absent experts would add is
left out, as expert parallelism leaves it to the chips that hold them: on
one chip the layer runs without its exchange, and nothing here stands in
for it.

Assignments to held experts are sorted by expert into a buffer of
`buffer_rows` rows (static: a bound on the assignments made here, not a
capacity per expert), the experts' two products run grouped over it
(`ops/grouped_matmul.py`: rows past the assignments cost nothing and hold
nothing that may be read), and the
weighted results are summed back per token. Both moves are gathers, forward
and backward (each is the other's transpose): a scatter-add of 10^5 rows
is the slow way on the chip. An assignment past the buffer would be a
dropped token; the layer counts them (`moe_dropped`) and the callers
require 0.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax

from dalle_pytorch_tpu.ops.grouped_matmul import grouped_matmul


def route(probs: jnp.ndarray, per_token: int, held: Tuple[int, int], buffer_rows: int):
    """From router probabilities [T, E] to the buffer's layout.

    Returns a dict: `weights` [T, k] (the chosen experts' probabilities,
    renormalised), `experts` [T, k], `assign` [R] (the assignment, t * k +
    slot, that buffer row r holds), `live` [R] bool, `pos` [T, k] (the
    buffer row of each assignment, R where it has none: not held here, or
    dropped), `group_sizes` [count] (clipped to the buffer), and the
    counters `load` [count], `rows`, `dropped`.
    """
    first, count = held
    buffer_rows = min(buffer_rows, probs.shape[0] * per_token)
    top, experts = lax.top_k(probs, per_token)
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
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
    return {
        "weights": weights, "experts": experts,
        "assign": order[:buffer_rows],
        "live": jnp.arange(buffer_rows) < kept,
        "pos": jnp.where(pos < kept, pos, buffer_rows).reshape(experts.shape),
        "group_sizes": jnp.diff(ends, prepend=0).astype(jnp.int32),
        "load": load, "rows": rows, "dropped": rows - kept,
    }


def _picked(rows, pos):
    """[T, k, D] float32: each slot's buffer row, 0 where the slot has none
    (pos == R). Selected, never multiplied by 0: rows that hold no
    assignment hold anything at all."""
    n_rows = rows.shape[0]
    picked = rows[jnp.minimum(pos, n_rows - 1).reshape(-1)].reshape(*pos.shape, -1)
    return jnp.where((pos < n_rows)[..., None], picked, 0).astype(jnp.float32)


def _sum_slots(rows, pos, weights):
    """[T, D] float32: sum over slots of weights[t, j] * rows[pos[t, j]]: one
    gather of every slot's row and one weighted sum over the slots, on the
    VPU in float32 (no MXU pass rounds a weight)."""
    picked = _picked(rows, pos)
    if weights is not None:
        picked = picked * weights.astype(jnp.float32)[..., None]
    return jnp.sum(picked, axis=1)


@jax.custom_vjp
def to_rows(h, assign, pos):
    """[R, D]: the token each buffer row's assignment belongs to (a row
    that holds no assignment gets some token's row: nothing reads it)."""
    return h[assign // pos.shape[1]]


def _to_rows_fwd(h, assign, pos):
    return h[assign // pos.shape[1]], pos


def _to_rows_bwd(pos, d_rows):  # rows have h's dtype, and so has its cotangent
    return _sum_slots(d_rows, pos, None).astype(d_rows.dtype), None, None


to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def to_tokens(rows, weights, assign, pos, live):
    """[T, D]: each token's weighted sum of its assignments' rows."""
    return _sum_slots(rows, pos, weights).astype(rows.dtype)


def _to_tokens_fwd(rows, weights, assign, pos, live):
    return (_sum_slots(rows, pos, weights).astype(rows.dtype),
            (rows, weights, assign, pos, live))


def _to_tokens_bwd(res, d_tokens):
    rows, weights, assign, pos, live = res
    per_token = pos.shape[1]
    w_row = jnp.where(live, weights.reshape(-1)[assign], 0.0)
    d_rows = (d_tokens[assign // per_token].astype(jnp.float32) * w_row[:, None]).astype(rows.dtype)
    d_weights = jnp.sum(_picked(rows, pos) * d_tokens.astype(jnp.float32)[:, None, :], axis=-1)
    return d_rows, d_weights.astype(weights.dtype), None, None, None


to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def _fan_in(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(shape[-2]).astype(dtype)


class RoutedExperts(nn.Module):
    """SwiGLU experts behind a softmax router, the held ones computed.

    Parameters: `router` [dim, experts_total], `w_gate` and `w_up` [count,
    dim, expert_dim], `w_out` [count, expert_dim, dim]; no biases. Gate and
    up are two grouped products, so that every product of the layer, forward
    or transposed, is rows x dim x expert_dim. Sows `moe_load` [count],
    `moe_rows` and `moe_dropped`
    into the `stats` collection where the caller makes it mutable.
    """

    dim: int
    expert_dim: int
    experts_total: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    buffer_rows: int
    dtype: Any = jnp.float32

    def setup(self):
        count = self.experts_held[1]
        self.router = self.param("router", _fan_in, (self.dim, self.experts_total))
        self.w_gate = self.param("w_gate", _fan_in, (count, self.dim, self.expert_dim))
        self.w_up = self.param("w_up", _fan_in, (count, self.dim, self.expert_dim))
        self.w_out = self.param("w_out", _fan_in, (count, self.expert_dim, self.dim))

    def router_probs(self, h2d: jnp.ndarray) -> jnp.ndarray:
        """Float32 probabilities [T, experts_total] of tokens [T, dim]."""
        logits = jnp.dot(h2d.astype(jnp.float32), self.router,
                         precision=lax.Precision.HIGHEST)
        return jax.nn.softmax(logits, axis=-1)

    def choices(self, x: jnp.ndarray) -> jnp.ndarray:
        """[B, N, k] the experts the router chooses, largest first."""
        probs = self.router_probs(x.reshape(-1, x.shape[-1]))
        return lax.top_k(probs, self.experts_per_token)[1].reshape(*x.shape[:-1], -1)

    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        h = x.reshape(-1, x.shape[-1])
        with jax.named_scope("moe_router"):
            probs = self.router_probs(h)
        with jax.named_scope("moe_dispatch"):
            r = route(probs, self.experts_per_token, tuple(self.experts_held),
                      self.buffer_rows)
            rows = to_rows(h, r["assign"], r["pos"])
        with jax.named_scope("moe_experts"):
            gate = grouped_matmul(rows, self.w_gate, r["group_sizes"])
            up = grouped_matmul(rows, self.w_up, r["group_sizes"])
            rows = grouped_matmul(nn.silu(gate) * up, self.w_out, r["group_sizes"])
        with jax.named_scope("moe_dispatch"):
            y = to_tokens(rows, r["weights"], r["assign"], r["pos"], r["live"])
        for name in ("load", "rows", "dropped"):
            self.sow("stats", f"moe_{name}", r[name], reduce_fn=lambda _, new: new,
                     init_fn=lambda: None)
        return y.reshape(x.shape)
