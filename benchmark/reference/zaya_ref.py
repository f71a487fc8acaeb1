"""Plain float32 reference of the ZAYA1-8B causal language model (`model_type:
zaya`), one pipeline stage's share of it: every layer an attention sublayer in
a compressed latent whose q and k pass two causal convolutions (arXiv:
2510.04476), then a routed sublayer of top-1 SwiGLU experts chosen by a router
that is a small MLP carrying a state from layer to layer (arXiv:2511.17127), a
residual stream that each sublayer scales and shifts, a final norm and a head
that is the embedding.

Straightforward `jax.numpy`: no kernels, no cache, full sequences, nothing
imported from the program. The keys are read from the configuration file as
they are named there (x [n, D], D = hidden_size; d = head_dim; H =
num_attention_heads query heads over K = num_key_value_heads K/V heads, P = H /
K query heads a K/V head; anything at position t - 1 < 0 is zero):

  rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g
  a sublayer f with four vectors (a, b, c, e) of D:  x <- (a x + b) + (c f(x) + e)

  attention sublayer, u = rms(x; g):
    q~ = u W_q  [H d],  k~ = u W_k  [K d],  v' = u W_v  [K d]            no bias
    v_t = [v'_t heads 0 .. K/2 - 1 ; v'_(t-1) heads K/2 .. K - 1]    the value shift
    c_t = [q~_t ; k~_t]                            C = (H + K) d channels, H + K heads
    c'_t = b0 + w0[1] c_t + w0[0] c_(t-1)          depthwise, cca_time0 = 2 taps
    c''_t[g] = b1[g] + c'_t[g] W1[1, g] + c'_(t-1)[g] W1[0, g]
                                   one group a head, W1[j, g] [d, d], cca_time1 = 2 taps
    m_q[h] = (q~[h] + k~[h // P]) / 2,  m_k[j] = mean of m_q[h] over the h of group j
    q[h] = c''[h] + m_q[h],  k[j] = c''[H + j] + m_k[j]        the q-k mean, on the
                                                      latents BEFORE the convolutions
    q^ = q / sqrt(mean(q^2) + eps),  k^ = tau[j] k / sqrt(mean(k^2) + eps)    a head:
                                      sqrt(d) q / |q|; tau a learned scalar a K/V head
    rotate-half over the first partial_rotary_factor x d columns of each head of
    q^ and k^ (theta = rope_parameters.hybrid.rope_theta)
    f = concat_h(softmax_causal(q^[h] . k^[h // P] / sqrt(d)) v[h // P]) W_o

  routed sublayer of layer l, u = rms(x; g):
    r = u W_d + b_d  [R = router_hidden_size]
    s_l = r + gamma_l s_(l-1),  s_(-1) = 0      the state that runs down the depth,
                                                a position at a time
    z = gelu(gelu(rms(s_l; g_r) W_1 + b_1) W_2 + b_2) W_3  [E];  p = softmax(z)
    e = argmax_j (p_j + beta_j)                 beta: the balancing bias, choice only
    f = p_e (silu(u W_gate[e]) * (u W_up[e])) W_down[e]         the gate is p_e itself

  after the last layer: logits = rms(x; g_final) Emb^T

computed as every expert on every token times a gate that is 0 where the
expert was not chosen. The router's products are float32 at `highest`
precision in every variant, as the program's are.

ASSUMED (the configuration file lists each with its ground): everything above
that the published keys do not fix, and seeded weights: matrices normal /
sqrt(fan_in) (the second convolution's over its taps x d inputs), the embedding
1 / sqrt(D), gains and the residual's a and c 1 +- 10%, tau 3 +- 10%, the first
convolution's taps 0.5 + 0.3 x normal, biases 0.1 x normal, the residual's b
and e 0.01 x normal, gamma 0.5 x normal, beta 0.02 x normal: every learned
vector OFF its neutral value, so that leaving one out moves the result.

A DEPARTURE from "float32 weights": the model IS its stored weights. Where the
configuration stores them in bfloat16 (`program.weights_dtype`), each seeded
matrix (both convolutions' taps among them) is rounded to bfloat16 once and the
reference computes with that in float32; gains, tau, gamma, beta, the biases,
the residual's vectors and the whole router are float32 in both.

Memory: weights are made ONE LAYER AT A TIME from per-leaf keys (`init_layer`),
the layer is applied to every checked row (query rows in blocks, the experts one
at a time), and freed.

The CONTROL sets the limits of `correct` (never a benchmark run). `quant`: the
same model with every matmul operand, norm output, activation and residual sum
rounded to fp8 (e4m3), per slice scaled to the format's range; the router's
products stay float32 on their rounded input.
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
    """Each slice along `axis` rounded to fp8 e4m3 (its largest at 448); the
    rounding clips before it casts."""
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
    """Sizes of a configuration file: the published keys, with the depth as
    the file states it."""
    if cfg["cca_time0"] != 2 or cfg["cca_time1"] != 2 or cfg["num_experts_per_tok"] != 1:
        raise ValueError("two convolutions of 2 taps each and ONE expert a token")
    heads, kv_heads, head_dim = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                                 cfg["head_dim"])
    if heads % kv_heads or kv_heads % 2:
        raise ValueError("query heads in whole groups, K/V heads in two halves")
    return dict(
        dim=cfg["hidden_size"], depth=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        rot_dim=int(head_dim * float(cfg["partial_rotary_factor"])),
        theta=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
        expert_dim=cfg["moe_intermediate_size"], experts=cfg["num_experts"],
        router_dim=cfg["router_hidden_size"],
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict) -> dict:
    """One layer's leaves: the attention sublayer's, then the routed one's."""
    d = dims(cfg)
    D, H, K, dh = d["dim"], d["heads"], d["kv_heads"], d["head_dim"]
    C, R, E, F = (H + K) * dh, d["router_dim"], d["experts"], d["expert_dim"]
    return {
        "attn_norm_g": (D,), "q_w": (D, H * dh), "k_w": (D, K * dh), "v_w": (D, K * dh),
        "conv0_w": (2, C), "conv0_b": (C,), "conv1_w": (2, H + K, dh, dh), "conv1_b": (C,),
        "tau_g": (K,), "o_w": (H * dh, D), "attn_res": (4, D),
        "ff_norm_g": (D,), "rd_w": (D, R), "rd_b": (R,), "gamma": (R,), "rnorm_g": (R,),
        "r1_w": (R, R), "r1_b": (R,), "r2_w": (R, R), "r2_b": (R,), "r3_w": (R, E),
        "beta": (E,), "gate_w": (E, D, F), "up_w": (E, D, F), "down_w": (E, F, D),
        "ff_res": (4, D),
    }


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"emb": (d["vocab"], d["dim"]), "final_norm_g": (d["dim"],)}


def n_params(cfg: dict, depth: int = None, embedding: bool = True) -> int:
    """Parameters of `depth` layers (the file's own where left out; the
    published 40 for the uncut model), with or without the embedding, which is
    the head."""
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    depth = dims(cfg)["depth"] if depth is None else depth
    return depth * count(layer_shapes(cfg)) + embedding * count(top_shapes(cfg))


# what is not stored rounded: vectors, and the whole router
FLOAT32_LEAVES = ("conv0_b", "conv1_b", "attn_res", "ff_res", "rd_w", "rd_b", "gamma",
                  "r1_w", "r1_b", "r2_w", "r2_b", "r3_w", "beta")
BIAS_SCALE = 0.1  # the seeded biases: leaving one out moves what it feeds
# the residual's shifts b and e, small beside a stream whose elements are about
# 0.1: forty sublayers' shifts of 0.1 an element add up to a vector that every
# position shares and that outweighs what the tokens put there, so that every
# normed input, every router's choice and the logits hardly depend on the token
# (the chip's first reading, PR 47: 1.8 of 16 experts touched a layer a step)
SHIFT_SCALE = 0.01
# tau about 3: q^ and k^ are unit vectors times sqrt(d), so a score between
# random directions has spread tau. At 1 a softmax over 8,192 positions is nearly
# uniform: every position's attention is the document's mean, the same vector for
# all, and the routers see one input. At 8 it picks single positions and a layer
# multiplies a rounding's size several times over: bfloat16 and float32 part ways
# for good (the chip, PR 47: `logit_gap_median` 1.05 for the program and 1.26 for
# its fp8 control). A simulation at a quarter of the widths (CPU, PR 47: the share
# of the stream's norm that all positions share, the experts 24 rows touch of 16,
# the median gap of a bfloat16 and of an fp8 model): tau 1: 0.74, 4.8, 0.011,
# 0.17; tau 3: 0.64, 5.9, 0.037, 0.32; tau 4: 0.58, 6.5, 0.21, 0.62; tau 8: 0.28,
# 7.5, 1.05, 1.22
TAU = 3.0
BETA_SCALE = 0.02  # beside probabilities of about 1 / experts: it flips near choices alone


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves, one key a leaf by its name's place in the sorted names."""
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.float32)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_res"):  # a, b, c, e: scales about 1, shifts about 0
            x = normal(k, shape)
            out[name] = jnp.stack([1.0 + 0.1 * x[0], SHIFT_SCALE * x[1],
                                   1.0 + 0.1 * x[2], SHIFT_SCALE * x[3]])
        elif name.endswith("_g"):
            out[name] = (TAU if name == "tau_g" else 1.0) * (1.0 + 0.1 * normal(k, shape))
        elif name.endswith("_b"):
            out[name] = BIAS_SCALE * normal(k, shape)
        elif name == "beta":
            out[name] = BETA_SCALE * normal(k, shape)
        elif name == "gamma":
            out[name] = 0.5 * normal(k, shape)
        else:
            if name == "conv0_w":
                w = 0.5 + 0.3 * normal(k, shape)
            else:
                fan_in = {"emb": shape[-1], "conv1_w": 2 * shape[-2]}.get(name, shape[-2])
                w = normal(k, shape) / math.sqrt(fan_in)
            rounded = stored == "bfloat16" and name not in FLOAT32_LEAVES
            out[name] = _to_bf16(w) if rounded else w
    return out


def _to_bf16(x):
    """x rounded to bfloat16's 8 bits of mantissa, still float32. Not a cast
    there and back: the compiler may drop such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _key(seed: int, part: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31 - 1)), part)


def init_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer i's weights alone, one jitted call on the device."""
    return jax.jit(lambda k: _make(k, layer_shapes(cfg), dims(cfg)["stored"]))(_key(seed, i + 1))


def init_top(cfg: dict, seed: int) -> dict:
    """Embedding (which is the head) and the final gain."""
    return jax.jit(lambda k: _make(k, top_shapes(cfg), dims(cfg)["stored"]))(_key(seed, 0))


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight at once (small configurations: the CPU tests)."""
    return {"top": init_top(cfg, seed),
            "layers": [init_layer(cfg, seed, i) for i in range(dims(cfg)["depth"])]}


# ------------------------------------------------------------ the forward


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def before(u):
    """u [n, ...] a position later: row t holds u_(t-1), row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(u[:1]), u[:-1]], axis=0)


def rotate(x, d):
    """Rotate-half over the first `rot_dim` columns of each head of x [n,
    heads, d], position t by t theta^(-2i / rot_dim); the rest passes."""
    rot = d["rot_dim"]
    inv_freq = d["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (jnp.concatenate([f(angles)] * 2, -1)[:, None] for f in (jnp.cos, jnp.sin))
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    turned = jnp.concatenate([-x2, x1], axis=-1)
    return jnp.concatenate([x[..., :rot] * cos + turned * sin, x[..., rot:]], axis=-1)


def latents(u, lp, d, quant=None):
    """(q^ [n, H, d], k^ [n, K, d], v [n, K, d]) of the attention sublayer on
    one normed sequence u [n, D]: q and k normed and scaled but not yet
    rotated, v with its shifted half."""
    n, H, K, dh = u.shape[0], d["heads"], d["kv_heads"], d["head_dim"]
    proj = lambda w: _act(_mm("nd,dc->nc", u, lp[w], quant), quant)
    qt, kt, vf = proj("q_w"), proj("k_w"), proj("v_w").reshape(n, K, dh)
    v = jnp.concatenate([vf[:, :K // 2], before(vf[:, K // 2:])], axis=1)
    c = jnp.concatenate([qt, kt], axis=-1)
    c1 = _act(lp["conv0_b"] + lp["conv0_w"][1] * c + lp["conv0_w"][0] * before(c), quant)
    c1 = c1.reshape(n, H + K, dh)
    c2 = (lp["conv1_b"].reshape(H + K, dh) + _mm("ngi,gio->ngo", c1, lp["conv1_w"][1], quant, -1, 1)
          + _mm("ngi,gio->ngo", before(c1), lp["conv1_w"][0], quant, -1, 1))
    qh, kh = qt.reshape(n, H, dh), kt.reshape(n, K, dh)
    m_q = (qh + jnp.repeat(kh, H // K, axis=1)) / 2
    m_k = m_q.reshape(n, K, H // K, dh).mean(2)
    unit = lambda t: t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + d["eps"])
    q = unit(c2[:, :H] + m_q)
    k = unit(c2[:, H:] + m_k) * lp["tau_g"][:, None]
    return _act(q, quant), _act(k, quant), v


def _attend(q, k, v, quant):
    """q [n, H, dh], k, v [n, K, dh] -> [n, H * dh], causal, query rows in
    blocks; query head j reads K/V head j // (H / K)."""
    n, h, dh = q.shape
    per = h // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    block = min(Q_BLOCK, n)
    pad = (-n) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, dh)
    t0 = jnp.arange(qb.shape[0]) * block

    def rows(args):
        qi, start = args
        live = jnp.arange(n)[None, :] <= start + jnp.arange(block)[:, None]
        s = _mm("ihd,jhd->hij", qi * dh**-0.5, k, quant, -1, -1)
        s = jnp.where(live[None], s, NEG)
        return _mm("hij,jhd->ihd", jax.nn.softmax(s, -1), v, quant, -1, 0)

    return jax.lax.map(rows, (qb, t0)).reshape(-1, h * dh)[:n]


def attention(u, lp, d, quant=None):
    q, k, v = latents(u, lp, d, quant)
    out = _attend(_act(rotate(q, d), quant), _act(rotate(k, d), quant), v, quant)
    return _mm("nc,cd->nd", _act(out, quant), lp["o_w"], quant)


def router(u, carried, lp, d):
    """(gate [n, E] float32: p_e at the chosen expert and 0 elsewhere, choice
    [n, 1], the state s_l [n, R] for the next layer's router)."""
    gelu = partial(jax.nn.gelu, approximate=False)
    with jax.default_matmul_precision("highest"):
        s = u @ lp["rd_w"] + lp["rd_b"] + lp["gamma"] * carried
        z = gelu(_rms(s, lp["rnorm_g"], d["eps"]) @ lp["r1_w"] + lp["r1_b"])
        z = gelu(z @ lp["r2_w"] + lp["r2_b"]) @ lp["r3_w"]
    p = jax.nn.softmax(z, axis=-1)
    choice = jnp.argmax(p + lp["beta"], axis=-1)
    chosen = jax.nn.one_hot(choice, d["experts"], dtype=p.dtype)
    return p * chosen, choice[:, None], s


def experts(u, gate, lp, quant=None):
    """Sum over the experts of gate x expert, one expert at a time."""

    def one(acc, e):
        w_gate, w_up, w_down, g = e
        a = jax.nn.silu(_mm("nd,df->nf", u, w_gate, quant)) * _mm("nd,df->nf", u, w_up, quant)
        return acc + g[:, None] * _mm("nf,fd->nd", _act(a, quant), w_down, quant), None

    return jax.lax.scan(one, jnp.zeros_like(u), (lp["gate_w"], lp["up_w"], lp["down_w"], gate.T))[0]


def _residual(x, f, vectors, quant):
    a, b, c, e = vectors
    return _act((a * x + b) + (c * _act(f, quant) + e), quant)


def layer(x, carried, lp, d, quant=None):
    """One layer on one sequence x [n, D] with the router's state of the layer
    before, carried [n, R]: (out, the state, the router's choices [n, 1])."""
    u = _rms(x, lp["attn_norm_g"], d["eps"], quant)
    x = _residual(x, attention(u, lp, d, quant), lp["attn_res"], quant)
    u = _rms(x, lp["ff_norm_g"], d["eps"], quant)
    gate, choices, carried = router(u, carried, lp, d)
    return _residual(x, experts(u, gate, lp, quant), lp["ff_res"], quant), carried, choices


@partial(jax.jit, static_argnames=("quant", "d"))
def _layer_rows(x, carried, lp, *, d, quant):
    d = dict(d)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: layer(*row, lp, d, quant), (x, carried))


@partial(jax.jit, static_argnames=("quant", "eps"))
def _head(x, g, emb, *, eps, quant):
    with jax.default_matmul_precision("highest"):
        return _mm("rnd,vd->rnv", _rms(x, g, eps, quant), emb, quant, -1, -1)


def forward(cfg: dict, seed: int, tokens, start=0, quant=None, params=None):
    """The uncached forward over `tokens` [R, n], a layer at a time.

    Returns `logits` [R, n - start, vocab] float32 (of positions `start` on)
    and `choices` [R, n - start, 1]: what the FIRST layer's router chose from
    `start` on. `params`: `{"top": ..., "layers": [...]}` made already (the CPU
    tests); left out, each layer's weights are made from `seed` when it is
    reached and freed after."""
    tokens = jnp.asarray(tokens)
    d = dims(cfg)
    static = tuple(sorted(d.items()))
    top = params["top"] if params else init_top(cfg, seed)
    x = top["emb"][tokens]
    carried = jnp.zeros((*tokens.shape, d["router_dim"]), jnp.float32)
    first = None
    for i in range(d["depth"]):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        x, carried, choices = _layer_rows(x, carried, lp, d=static, quant=quant)
        if first is None:
            first = np.asarray(choices[:, start:])
        del lp
    logits = _head(x[:, start:], top["final_norm_g"], top["emb"], eps=d["eps"], quant=quant)
    return {"logits": np.asarray(logits), "choices": first}
