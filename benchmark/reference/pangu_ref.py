"""Plain float32 reference of the openPangu-Ultra-MoE causal language model,
one chip's share of it: latent attention (MLA) in every layer, sandwich
norms, a leading dense SwiGLU layer, then routed layers with a shared expert
beside sigmoid-routed ones of which this chip holds some, an untied head
over a slice of the vocabulary.

Straightforward `jax.numpy`: no kernels, no cache, nothing imported from the
program. The layer, in its EXPANDED form (the keys are read from the
configuration file as they are named there; T tokens, x [T, hidden]):

  rms(u; g) = u / sqrt(mean(u^2) + eps) * g
  a   = rms(x; g_in)
  c_q = rms(a W_dq; g_q);  q = c_q W_uq, per head q_n | q_r
  c | k_r = a W_dkv;  c = rms(c; g_kv);  k_r is ONE head shared by all
  q_r, k_r rotated: rotate-half over qk_rope_head_dim, inv_freq_i =
      rope_theta^(-2i/dim), float32 angles, absolute positions, no scaling
  k_n | v = c W_ukv, per head
  s_j(t, p) = (q_n,j(t) . k_n,j(p) + q_r,j(t) . k_r(p)) / sqrt(nope + rope), p <= t
  u = concat_j(softmax(s_j) v_j) W_o
  y = x + rms(u; g_pa)                    the sandwich: a norm on the OUTPUT
  b = rms(y; g_pm);  z = y + rms(F(b); g_pf), F one of
    dense:  F(b) = (silu(b W_g) * (b W_u)) W_d
    routed: s = sigmoid(b W_r) over ALL the published experts; S(t) the
            num_experts_per_tok largest; w_e = routed_scaling_factor * s_e /
            sum_{S(t)} s;  F(b) = shared(b) + sum_{e in S(t), e held here}
            w_e (silu(b W_g,e) * (b W_u,e)) W_d,e
  after the last layer: logits = rms(x; g_f) W_head

computed as every held expert on every token times a weight that is 0 where
the expert was not chosen. What the absent experts would add is left out
(model-configs guide, section 4), here and in the program alike.

ASSUMED (the configuration file lists them): sigmoid scores, no group-limited
choice and no score-correction bias, the sandwich's placement, RMS norms on
both latents, the rotate-half layout, the softmax scale, seeded weights.

A DEPARTURE from "float32 weights": the model IS its stored weights. Where
the configuration stores them in bfloat16 (`program.weights_dtype`, as the
published checkpoint does), each seeded matrix is rounded to bfloat16 once
and the reference computes with that in float32; gains and the router are
float32 in both.

Memory: the share's 4.9 B parameters are 19.7 GB in float32, so the weights
are made ONE LAYER AT A TIME from per-leaf keys (`init_layer`: 4.0 GB for a
routed layer's share), the layer is applied to every checked row (query rows
in blocks, the held experts one at a time), and freed.

`quant` is the CONTROL that sets the limits of `correct` (never a benchmark
run): the same model with every matmul operand, norm output and residual sum
rounded to fp8 (e4m3), per slice scaled to the format's range; the router's
product stays float32 on the rounded input, as the program's does on its
bf16 one. The rounding clips before it casts (`mellum_ref.py` says why).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
Q_BLOCK = 256  # query rows whose scores are whole at once: [heads, 256, n] float32


# ------------------------------------------------------------ the control


def _round(x, axis, kind):
    """Each slice along `axis` rounded to fp8 e4m3 (its largest at 448)."""
    if kind != "fp8":
        raise ValueError(f"unknown control precision {kind!r}")
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12)
    scaled = jnp.clip(x / top * 448.0, -448.0, 448.0)
    return scaled.astype(jnp.float8_e4m3fn).astype(x.dtype) * (top / 448.0)


def _mm(spec, a, b, quant, a_axis=-1, b_axis=0):
    """einsum, both operands rounded along their contracted axis in the control."""
    if quant:
        a, b = _round(a, a_axis, quant), _round(b, b_axis, quant)
    return jnp.einsum(spec, a, b)


def _act(x, quant):
    """An activation as the model's precision stores it."""
    return _round(x, -1, quant) if quant else x


# ------------------------------------------------------------ configuration


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file: the published keys, with the held
    experts, the depth and the vocabulary slice as the file states them."""
    depth, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return dict(
        dim=cfg["hidden_size"], depth=depth, heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        theta=float(cfg["rope_theta"]), vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        kinds=tuple("dense" if i < dense else "routed" for i in range(depth)),
        dense_dim=cfg["intermediate_size"], expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts_total=cfg["published"]["n_routed_experts"], experts_held=cfg["n_routed_experts"],
        experts_first=cfg["deployment"]["experts_first"], per_token=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    D, H = d["dim"], d["heads"]
    shapes = {
        "norm_attn_g": (D,), "dq_w": (D, d["q_rank"]), "q_norm_g": (d["q_rank"],),
        "uq_w": (d["q_rank"], H * (d["nope"] + d["rope"])),
        "dkv_w": (D, d["kv_rank"] + d["rope"]), "kv_norm_g": (d["kv_rank"],),
        "ukv_w": (d["kv_rank"], H * (d["nope"] + d["v_dim"])), "o_w": (H * d["v_dim"], D),
        "post_attn_g": (D,), "norm_ff_g": (D,), "post_ff_g": (D,),
    }
    if kind == "dense":
        F = d["dense_dim"]
        shapes.update(gate_w=(D, F), up_w=(D, F), down_w=(F, D))
    else:
        G, F, Fs = d["experts_held"], d["expert_dim"], d["shared_dim"]
        shapes.update(router_w=(D, d["experts_total"]), gate_w=(G, D, F), up_w=(G, D, F),
                      down_w=(G, F, D), sh_gate_w=(D, Fs), sh_up_w=(D, Fs), sh_down_w=(Fs, D))
    return shapes


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"emb": (d["vocab"], d["dim"]), "final_norm_g": (d["dim"],),
            "head_w": (d["dim"], d["vocab"])}


def n_params(cfg: dict) -> int:
    """Parameters of the share: what the configuration file's `parameters_here` states."""
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return count(top_shapes(cfg)) + sum(count(layer_shapes(cfg, k)) for k in dims(cfg)["kinds"])


FLOAT32_LEAVES = ("router_w",)  # a matrix that is not stored rounded


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves: matrices normal / sqrt(fan_in) (the router's too), the
    embedding 1 / sqrt(dim), gains 1 +- 10%; one key a leaf, by its name's
    place in the sorted names; matrices rounded to what the model stores."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("_g"):
            out[name] = 1.0 + 0.1 * z
            continue
        w = z / math.sqrt(shape[-1] if name == "emb" else shape[-2])
        if stored == "bfloat16" and name not in FLOAT32_LEAVES:
            w = w.astype(jnp.bfloat16).astype(jnp.float32)
        out[name] = w
    return out


def _key(seed: int, part: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31 - 1)), part)


def init_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer i's weights alone, one jitted call on the device."""
    kind = dims(cfg)["kinds"][i]
    return jax.jit(lambda k: _make(k, layer_shapes(cfg, kind), dims(cfg)["stored"]))(
        _key(seed, i + 1))


def init_top(cfg: dict, seed: int) -> dict:
    """Embedding, final gain and head."""
    return jax.jit(lambda k: _make(k, top_shapes(cfg), dims(cfg)["stored"]))(_key(seed, 0))


# ------------------------------------------------------------ the forward


def cos_sin(d: dict, n: int):
    """(cos, sin) float32 [n, rope], halves paired."""
    inv_freq = d["theta"] ** (-np.arange(0, d["rope"], 2, dtype=np.float64) / d["rope"])
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.concatenate([angles, angles], -1)
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(t, cos, sin):
    """t [n, ..., dim] turned by [n, dim] tables."""
    a, b = jnp.split(t, 2, -1)
    shape = (t.shape[0],) + (1,) * (t.ndim - 2) + (t.shape[-1],)
    return t * cos.reshape(shape) + jnp.concatenate([-b, a], -1) * sin.reshape(shape)


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def _attend(q, k, v, quant):
    """q, k [n, H, dqk], v [n, H, dv] -> [n, H * dv], causal, query rows in blocks."""
    n, h, dqk = q.shape
    block = min(Q_BLOCK, n)
    pad = (-n) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, dqk)
    t0 = jnp.arange(qb.shape[0]) * block

    def rows(args):
        qi, start = args
        live = jnp.arange(n)[None, :] <= start + jnp.arange(block)[:, None]
        s = _mm("ihd,jhd->hij", qi * dqk**-0.5, k, quant, -1, -1)
        s = jnp.where(live[None], s, NEG)
        return _mm("hij,jhd->ihd", jax.nn.softmax(s, -1), v, quant, -1, 0)

    return jax.lax.map(rows, (qb, t0)).reshape(-1, h * v.shape[-1])[:n]


def attention_half(x, lp, d, quant=None):
    """x [n, dim] -> x + rms(latent attention(rms(x))) on one sequence."""
    n, h = x.shape[0], d["heads"]
    a = _rms(x, lp["norm_attn_g"], d["eps"], quant)
    c_q = _rms(_mm("nd,dr->nr", a, lp["dq_w"], quant), lp["q_norm_g"], d["eps"], quant)
    q = _mm("nr,re->ne", c_q, lp["uq_w"], quant).reshape(n, h, d["nope"] + d["rope"])
    ckr = _mm("nd,dr->nr", a, lp["dkv_w"], quant)
    c = _rms(ckr[:, :d["kv_rank"]], lp["kv_norm_g"], d["eps"], quant)
    cos, sin = cos_sin(d, n)
    q = jnp.concatenate([q[..., :d["nope"]], _rotate(q[..., d["nope"]:], cos, sin)], -1)
    k_r = _rotate(ckr[:, d["kv_rank"]:], cos, sin)
    kv = _mm("nr,re->ne", c, lp["ukv_w"], quant).reshape(n, h, d["nope"] + d["v_dim"])
    k = jnp.concatenate(
        [kv[..., :d["nope"]], jnp.broadcast_to(k_r[:, None], (n, h, d["rope"]))], -1)
    u = _mm("ne,ed->nd", _act(_attend(q, k, kv[..., d["nope"]:], quant), quant), lp["o_w"], quant)
    return _act(x + _rms(u, lp["post_attn_g"], d["eps"], quant), quant)


def _swiglu(b, wg, wu, wd, quant):
    a = jax.nn.silu(_mm("nd,df->nf", b, wg, quant)) * _mm("nd,df->nf", b, wu, quant)
    return _mm("nf,fd->nd", _act(a, quant), wd, quant)


def route(b, router_w, d):
    """(weights [n, E] float32: 0 where not chosen, renormalised over the
    chosen and scaled; choices [n, per_token], largest first)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(b @ router_w)
    top, idx = jax.lax.top_k(s, d["per_token"])
    top = d["routed_scale"] * top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, d["experts_total"], dtype=s.dtype)  # [n, k, E]
    return jnp.einsum("nk,nke->ne", top, chosen), idx


def routed_experts(b, weights, lp, d, quant=None, held=None):
    """Sum over the held experts of weight x SwiGLU, one expert at a time."""
    first, count = (d["experts_first"], d["experts_held"]) if held is None else held

    def one(acc, e):
        wg, wu, wd, w = e
        return acc + w[:, None] * _swiglu(b, wg, wu, wd, quant), None

    w_held = jax.lax.dynamic_slice_in_dim(weights, first, count, 1).T  # [G, n]
    return jax.lax.scan(one, jnp.zeros_like(b),
                        (lp["gate_w"], lp["up_w"], lp["down_w"], w_held))[0]


def shared_expert(b, lp, quant=None):
    return _swiglu(b, lp["sh_gate_w"], lp["sh_up_w"], lp["sh_down_w"], quant)


def layer(x, lp, kind, d, quant=None):
    """One layer on one sequence x [n, dim]: (x, the router's choices [n, k]
    or None for a dense layer)."""
    y = attention_half(x, lp, d, quant)
    b = _rms(y, lp["norm_ff_g"], d["eps"], quant)
    if kind == "dense":
        f, choices = _swiglu(b, lp["gate_w"], lp["up_w"], lp["down_w"], quant), None
    else:
        weights, choices = route(b, lp["router_w"], d)
        f = shared_expert(b, lp, quant) + routed_experts(b, weights, lp, d, quant)
    return _act(y + _rms(_act(f, quant), lp["post_ff_g"], d["eps"], quant), quant), choices


@partial(jax.jit, static_argnames=("kind", "quant", "d"))
def _layer_rows(x, lp, *, kind, d, quant):
    d = dict(d)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: layer(row, lp, kind, d, quant), x)


def forward(cfg: dict, seed: int, tokens, start: int = 0, quant=None, params=None) -> dict:
    """The uncached forward over `tokens` [R, n], a layer at a time.

    Returns `logits` [R, n - start, vocab] float32 (of positions `start`
    on) and `choices` [R, n - start, per_token]: what the FIRST routed
    layer's router chose there. `params`: `{"top": ..., "layers": [...]}` made
    already (the CPU tests); left out, each layer's weights are made from
    `seed` when it is reached and freed after."""
    d = dims(cfg)
    static = tuple(sorted((k, v) for k, v in d.items() if k != "kinds")) + (("kinds", d["kinds"]),)
    tokens = jnp.asarray(tokens)
    top = params["top"] if params else init_top(cfg, seed)
    x = top["emb"][tokens]
    first_choices = None
    for i, kind in enumerate(d["kinds"]):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        x, choices = _layer_rows(x, lp, kind=kind, d=static, quant=quant)
        if choices is not None and first_choices is None:
            first_choices = np.asarray(choices[:, start:])
        del lp
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, start:], top["final_norm_g"], d["eps"], quant)
        logits = _mm("rnd,dv->rnv", h, top["head_w"], quant, -1, 0)
    return {"logits": np.asarray(logits), "choices": first_choices}


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight at once (small configurations: the CPU tests)."""
    return {"top": init_top(cfg, seed),
            "layers": [init_layer(cfg, seed, i) for i in range(dims(cfg)["depth"])]}
