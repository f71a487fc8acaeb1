"""Plain float32 reference of the Mellum2 causal language model, one chip's
share of it: a whole period of window and full attention layers over
grouped K/V heads, each followed by a routed SwiGLU layer of which this
chip holds some experts, an untied head over a slice of the vocabulary.

Straightforward `jax.numpy`: no kernels, no cache, nothing imported from the
program. The layer is what the published `config.json` gives (the keys are
read from the configuration file as they are named there):

  h  = rms(x; g1)                      rms(u; g) = u / sqrt(mean(u^2) + eps) * g
  q  = rms(h Wq; gq)  k = rms(h Wk; gk)  v = h Wv      per head, over head_dim
  rotate-half rotary on q and k: `default` on the window layers, YaRN on the
      full ones, angles, cos and sin in float32
  query head j reads K/V head j // (heads / kv_heads); key p is seen by query
      t iff p <= t, and on window layers also t - p < sliding_window
  y  = x + concat_j(softmax(q k^T / sqrt(head_dim)) v) Wo
  h2 = rms(y; g2);  p = softmax(h2 Wr) over ALL the published experts;
      S(t) the num_experts_per_tok largest; w_e = p_e / sum_{S(t)} p
  z  = y + sum_{e in S(t), e held here} w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e

computed as every held expert on every token times a weight that is 0 where
the expert was not chosen. What the absent experts would add is left out
(model-configs guide, section 4), here and in the program alike. The loss is
the mean over positions 0..N-2 of the cross-entropy against the next token,
over the slice of the vocabulary held; no auxiliary router loss.

ASSUMED (the configuration file lists them): the per-head RMS norm of q and
k, the absent auxiliary loss, seeded weights.

Memory: one row of the batch at a time (`lax.map` over rows, each row's loss
recomputed in the backward), query rows in blocks, experts one at a time,
each under `jax.checkpoint`, so that a batch of 4 x 8192 tokens at the
published widths fits beside float32 weights, gradient and Adam state.

`quant` is the CONTROL that sets the limits of `correct` (never a benchmark
run): the same model with every matmul operand, norm output and residual
sum rounded to fp8 (e4m3) or int8, per slice scaled to the format's range,
and their gradients rounded on the way back, as a model computed in that
precision rounds both. The router's product stays float32 on the rounded
h2, as the program's does on its bf16 h2. The fp8 rounding clips before it
casts: on the chip `x / max|x| * 448` comes out 448.00006 for one value in
twenty of a slice's own maximum, and a cast of anything above 448 is NaN
there (7 NaN in the embedding's gradient at 1,024 tokens, 0 with the clip:
PERF.md, PR 27, call G).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
Q_BLOCK = 1024  # query rows whose scores are whole at once


# ------------------------------------------------------------ the control


def _round(x, axis, kind):
    """Each slice along `axis` rounded to fp8 e4m3 (its largest at 448, the
    format's largest finite) or to int8 (127 levels of its largest)."""
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12)
    if kind == "fp8":
        scaled = jnp.clip(x / top * 448.0, -448.0, 448.0)
        return scaled.astype(jnp.float8_e4m3fn).astype(x.dtype) * (top / 448.0)
    if kind == "int8":
        return jnp.round(x / top * 127.0) * (top / 127.0)
    raise ValueError(f"unknown control precision {kind!r}")


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fake_low(x, axis, kind):
    """A tensor as the lower precision stores it, forward AND backward."""
    return _round(x, axis, kind)


_fake_low.defvjp(lambda x, axis, kind: (_round(x, axis, kind), None),
                 lambda axis, kind, _, g: (_round(g, axis, kind),))


def _mm(spec, a, b, quant, a_axis=-1, b_axis=0):
    """einsum, both operands rounded along their contracted axis in the control."""
    if quant:
        a, b = _fake_low(a, a_axis, quant), _fake_low(b, b_axis, quant)
    return jnp.einsum(spec, a, b)


def _act(x, quant):
    """An activation as the model's precision stores it."""
    return _fake_low(x, -1, quant) if quant else x


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree)))


# ------------------------------------------------------------ configuration


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file: the published keys, with the held
    experts and the vocabulary slice as the file states them."""
    depth = cfg["num_hidden_layers"]
    kinds = tuple(cfg["layer_types"][:depth])
    assert all(t == "sparse" for t in cfg["mlp_layer_types"][:depth])
    return dict(
        dim=cfg["hidden_size"], depth=depth, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], dim_head=cfg["head_dim"],
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        window=int(cfg["sliding_window"]), kinds=kinds,
        experts_total=cfg["published"]["num_experts"], experts_held=cfg["num_experts"],
        experts_first=cfg["deployment"]["experts_first"],
        per_token=cfg["num_experts_per_tok"], expert_dim=cfg["moe_intermediate_size"],
        rope=cfg["rope_parameters"],
    )


def param_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, dh = d["depth"], d["dim"], d["dim_head"]
    G, F = d["experts_held"], d["expert_dim"]
    return {
        "emb": (d["vocab"], D),
        "norm_attn_g": (L, D), "q_w": (L, D, d["heads"] * dh),
        "k_w": (L, D, d["kv_heads"] * dh), "v_w": (L, D, d["kv_heads"] * dh),
        "q_norm_g": (L, dh), "k_norm_g": (L, dh), "o_w": (L, d["heads"] * dh, D),
        "norm_ff_g": (L, D), "router_w": (L, D, d["experts_total"]),
        "gate_w": (L, G, D, F), "up_w": (L, G, D, F), "down_w": (L, G, F, D),
        "final_norm_g": (D,), "head_w": (D, d["vocab"]),
    }


LAYER_LEAVES = (
    "norm_attn_g", "q_w", "k_w", "v_w", "q_norm_g", "k_norm_g", "o_w",
    "norm_ff_g", "router_w", "gate_w", "up_w", "down_w",
)
EXPERT_LEAVES = ("gate_w", "up_w", "down_w")  # [L, G, ...]: a norm per expert


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded float32 weights, one jitted call on the device: matrices normal
    1/sqrt(fan_in) (the router's too), the embedding 1/sqrt(dim), norm gains
    1 +- 10%, so that no leaf is zero."""
    shapes = param_shapes(cfg)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if name.endswith("_w"):
                out[name] = z / math.sqrt(shape[-2])
            elif name == "emb":
                out[name] = z / math.sqrt(shape[-1])
            else:
                out[name] = 1.0 + 0.1 * z
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2**31 - 1)))


# ------------------------------------------------------------ rotary


def inv_freq(spec: dict, dim: int) -> np.ndarray:
    """[dim / 2] inverse frequencies: theta^(-2i/dim), and for `yarn` the
    slow channels divided by `factor`, the fast ones kept, a linear ramp
    between the correction dims of `beta_fast` and `beta_slow` rotations."""
    theta = float(spec["rope_theta"])
    base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if spec["rope_type"] == "default":
        return base.astype(np.float32)
    assert spec["rope_type"] == "yarn", spec["rope_type"]
    orig = float(spec["original_max_position_embeddings"])
    corr = lambda r: dim * math.log(orig / (r * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(float(spec["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(spec["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (base / float(spec["factor"]) * ramp + base * (1.0 - ramp)).astype(np.float32)


def cos_sin(spec: dict, dim: int, n: int):
    """(cos, sin) float32 [n, dim], halves paired; YaRN's attention_factor
    on both."""
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq(spec, dim))
    angles = jnp.concatenate([angles, angles], -1)
    scale = np.float32(spec.get("attention_factor", 1.0))
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(t, cos, sin):
    """t [n, heads, dim] turned by [n, dim] tables."""
    a, b = jnp.split(t, 2, -1)
    return t * cos[:, None] + jnp.concatenate([-b, a], -1) * sin[:, None]


# ------------------------------------------------------------ the forward


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def _attend(q, k, v, is_window, d, quant):
    """q [n, H, dh], k, v [n, Hkv, dh] -> [n, H * dh], query rows in blocks."""
    n, h, dh = q.shape
    group = h // k.shape[1]
    kk, vv = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    block = min(Q_BLOCK, n)
    pad = (-n) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, dh)
    t0 = jnp.arange(qb.shape[0]) * block

    @jax.checkpoint
    def rows(args):
        qi, start = args
        t = start + jnp.arange(block)[:, None]
        p = jnp.arange(n)[None, :]
        live = (p <= t) & (~is_window | (t - p < d["window"]))
        s = _mm("ihd,jhd->hij", qi * dh**-0.5, kk, quant, -1, -1)
        s = jnp.where(live[None], s, NEG)
        return _mm("hij,jhd->ihd", jax.nn.softmax(s, -1), vv, quant, -1, 0)

    out = jax.lax.map(rows, (qb, t0)).reshape(-1, h * dh)
    return out[:n]


def route(h2, router_w, d):
    """(weights [n, E] float32, 0 where not chosen and renormalised over the
    chosen; choices [n, per_token], largest first)."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(h2 @ router_w, -1)
    top, idx = jax.lax.top_k(p, d["per_token"])
    top = top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, d["experts_total"], dtype=p.dtype)  # [n, k, E]
    return jnp.einsum("nk,nke->ne", top, chosen), idx


def _experts(h2, weights, lp, d, quant, held=None):
    """Sum over the held experts of weight x SwiGLU, one expert at a time."""
    first, count = (d["experts_first"], d["experts_held"]) if held is None else held

    @jax.checkpoint
    def one(acc, e):
        wg, wu, wd, w = e
        a = jax.nn.silu(_mm("nd,df->nf", h2, wg, quant)) * _mm("nd,df->nf", h2, wu, quant)
        return acc + w[:, None] * _mm("nf,fd->nd", _act(a, quant), wd, quant), None

    w_held = jax.lax.dynamic_slice_in_dim(weights, first, count, 1).T  # [G, n]
    acc, _ = jax.lax.scan(one, jnp.zeros_like(h2),
                          (lp["gate_w"], lp["up_w"], lp["down_w"], w_held))
    return acc


def _attention_half(x, lp, is_window, tables, d, quant):
    """x [n, dim] -> x + attention(rms(x)) on one sequence."""
    n = x.shape[0]
    h, hkv, dh = d["heads"], d["kv_heads"], d["dim_head"]
    y = _rms(x, lp["norm_attn_g"], d["eps"], quant)
    q = _mm("nd,de->ne", y, lp["q_w"], quant).reshape(n, h, dh)
    k = _mm("nd,de->ne", y, lp["k_w"], quant).reshape(n, hkv, dh)
    v = _mm("nd,de->ne", y, lp["v_w"], quant).reshape(n, hkv, dh)
    q = _rms(q, lp["q_norm_g"], d["eps"], quant)
    k = _rms(k, lp["k_norm_g"], d["eps"], quant)
    (cw, sw), (cf, sf) = tables
    cos, sin = jnp.where(is_window, cw, cf)[:n], jnp.where(is_window, sw, sf)[:n]
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    o = _attend(q, k, v, is_window, d, quant)
    return _act(x + _mm("ne,ed->nd", o, lp["o_w"], quant), quant)


def _layer(x, lp, is_window, tables, d, quant, held=None):
    """One layer on one sequence x [n, dim]."""
    x = _attention_half(x, lp, is_window, tables, d, quant)
    h2 = _rms(x, lp["norm_ff_g"], d["eps"], quant)
    weights, _ = route(h2, lp["router_w"], d)
    return _act(x + _experts(h2, weights, lp, d, quant, held), quant)


def _tables(d, n):
    dh = d["dim_head"]
    return (cos_sin(d["rope"]["sliding_attention"], dh, n),
            cos_sin(d["rope"]["full_attention"], dh, n))


def _is_window(d):
    return jnp.asarray([k == "sliding_attention" for k in d["kinds"]])


def hidden_row(params, cfg, tokens, quant=None):
    """[n, dim]: one sequence through the layers and the final norm."""
    d = dims(cfg)
    x = params["emb"][tokens]
    tables = _tables(d, tokens.shape[0])

    @jax.checkpoint
    def body(x, scanned):
        lp, win = scanned
        return _layer(x, lp, win, tables, d, quant), None

    x, _ = jax.lax.scan(body, x, ({k: params[k] for k in LAYER_LEAVES}, _is_window(d)))
    return _rms(x, params["final_norm_g"], d["eps"], quant)


def logits_fn(params, cfg, tokens, quant=None):
    """Float32 logits [B, n, vocab] of tokens [B, n], a row at a time."""
    row = lambda t: _mm("nd,dv->nv", hidden_row(params, cfg, t, quant), params["head_w"], quant)
    return jax.lax.map(row, tokens)


def loss_fn(params, cfg, tokens, quant=None):
    """Mean over rows and positions 0..n-2 of the next token's cross-entropy."""

    @jax.checkpoint
    def row(t):
        logits = _mm("nd,dv->nv", hidden_row(params, cfg, t, quant)[:-1],
                     params["head_w"], quant)
        gold = jnp.take_along_axis(logits, t[1:, None], -1)[:, 0]
        return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - gold)

    return jnp.mean(jax.lax.map(row, tokens))


def route_choices(params, cfg, tokens, quant=None):
    """[B, n, per_token]: what the FIRST layer's router chooses."""
    d = dims(cfg)
    lp = {k: params[k][0] for k in LAYER_LEAVES}

    def row(t):
        x = _attention_half(params["emb"][t], lp, _is_window(d)[0],
                            _tables(d, t.shape[0]), d, quant)
        return route(_rms(x, lp["norm_ff_g"], d["eps"], quant), lp["router_w"], d)[1]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, t: jax.lax.map(row, t))(params, jnp.asarray(tokens)))


# -------------------------------------------------------------- training


def small_leaves(tree: dict) -> dict:
    """The vector leaves (gains), whole: compared by their difference too."""
    return {k: v for k, v in tree.items() if v.ndim <= (2 if k in LAYER_LEAVES else 1)}


def leaf_norms(tree: dict) -> dict:
    """Norm of each leaf; of each layer's slice for the stacked leaves, and
    of each expert's for the expert leaves."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        keep = 2 if name in EXPERT_LEAVES else 1 if name in LAYER_LEAVES else 0
        norms = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(keep, x.ndim))))
        out[name] = norms.reshape(-1) if keep == 2 else norms
    return out


def train_steps(cfg, seed, batches, opt, quant=None):
    """Follow the trainer through `len(batches)` optimizer steps (clip by
    global norm, then Adam at `learning_rate`, or at `learning_rate * k /
    warmup_steps` in step k of a linear warm-up) from `init_params(cfg, seed)`.

    `batches`: list of [B, n] int arrays. Returns the loss of each step, the
    per-leaf norms of the FIRST step's gradient as Adam gets it (after the
    clip) with its vector leaves whole, and the per-leaf norms of the
    parameters' change over all steps (against the weights made again from
    the seed: a kept copy would not fit beside gradient and Adam state).
    """
    lr, clip = float(opt["learning_rate"]), float(opt["clip_grad_norm"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    warmup = int(opt.get("warmup_steps", 0))
    grad = jax.jit(lambda p, t: jax.value_and_grad(loss_fn)(p, cfg, t, quant=quant))

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(p, mu, nu, g, step):
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / _global_norm(g)), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1**step, 1 - b2**step
        rate = lr * jnp.minimum(1.0, step / warmup) if warmup else lr
        p = jax.tree.map(
            lambda w, m, v: w - rate * (m / c1) / (jnp.sqrt(v / c2) + eps), p, mu, nu
        )
        return p, mu, nu, leaf_norms(g), small_leaves(g)

    p = init_params(cfg, seed)
    # Adam's moments wait on the host while a gradient is taken: weights,
    # gradient, the row loop's accumulators and a row's activations fill the
    # chip without them
    mu = nu = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), p)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            loss, g = grad(p, jnp.asarray(tokens))
            p, mu, nu, gn, gs = apply(p, mu, nu, g, float(i + 1))
            losses.append(float(loss))
            if i == 0:
                first = jax.device_get((gn, gs))
            if i + 1 < len(batches):
                mu, nu = jax.device_get((mu, nu))
    del mu, nu
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(lambda x, y: x - y, a, b)))(
        p, init_params(cfg, seed))
    return {"losses": losses, "grad_norms": first[0], "grad_small": first[1],
            "change_norms": jax.device_get(change)}
