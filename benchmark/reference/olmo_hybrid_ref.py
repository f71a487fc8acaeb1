"""Plain float32 reference of the Olmo-Hybrid causal language model: gated
delta-rule LINEAR attention layers 3:1 with FULL attention layers, a dense
SwiGLU in every layer, a norm on each sublayer's output alone, an untied head.

Straightforward `jax.numpy`: no kernels, no cache, no chunking, nothing
imported from the program; the linear layer is a `lax.scan` over tokens. The
keys are read from the configuration file as they are named there (T tokens,
x [T, D], D = hidden_size, H = num_attention_heads):

  rms(u; g) = u / sqrt(mean(u^2) + rms_norm_eps) * g

  LINEAR layer (d_k = linear_key_head_dim, d_v = linear_value_head_dim,
  linear_num_key_heads = linear_num_value_heads heads: no head is shared):
    q~ | k~ | v~ = x W_qkv        columns: H x d_k, H x d_k, H x d_v
    q', k', v' = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
        conv: causal, depthwise, over time, linear_conv_kernel_dim taps, no
        bias: conv(z)_t = sum_j c[j] z_{t - taps + 1 + j}, z = 0 before t = 0
    q = l2norm(q') / sqrt(d_k),  k = l2norm(k'),  v = v'            per head
        l2norm(z) = z / sqrt(sum(z^2) + 1e-6)
    b | a = x W_ab                columns: H, H
    beta_t  = 2 sigmoid(b_t)                        in (0, 2): linear_allow_neg_eigval
    alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))       in (0, 1), one a head
    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T       S: [d_k, d_v]
        = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,   S_{-1} = 0
    o_t = S_t^T q_t                                                         [d_v]
    y_t = rmsnorm(o_t; gain [d_v]) * silu(x_t W_g)                W_g: D -> H x d_v
    mixer(x) = concat_h(y) W_o                                    H x d_v -> D

  FULL layer (head size D / H; the config gives no head_dim):
    q | k | v = x W_qkv;  q = rms(q; g_q [D]),  k = rms(k; g_k [D]): one gain
    over ALL the columns, before the heads are split; no rotary embedding
    (rope_parameters.rope_theta is null); causal softmax attention at
    1 / sqrt(D / H); mixer(x) = concat_h(attend) W_o

  BLOCK, both kinds:  h = x + rms(mixer(x); g_pa),  out = h + rms(mlp(h); g_pf),
    mlp(h) = (silu(h W_g) * (h W_u)) W_d; a norm on each sublayer's OUTPUT and
    none on its input. After the last layer: logits = rms(x; g_f) W_head.

ASSUMED (the configuration file lists each with its ground): SiLU after the
convolution, no convolution bias, the L2 norm and the 1 / sqrt(d_k) on q,
A_log / dt_bias and the softplus, the gated output norm, the state in
float32, the norm placement, the whole-width q/k norm, no rotary for a null
theta, head size D / H, seeded weights (matrices normal / sqrt(fan_in), the
embedding 1 / sqrt(D), gains 1 +- 10%, A_log = log U(1, 16), dt_bias the
inverse softplus of U(0.001, 0.1)).

A DEPARTURE from "float32 weights": the model IS its stored weights. Where
the configuration stores them in bfloat16 (`program.weights_dtype`), each
seeded matrix (the convolution's taps among them) is rounded to bfloat16 once
and the reference computes with that in float32; gains, A_log and dt_bias are
float32 in both.

Memory: weights are made ONE LAYER AT A TIME from per-leaf keys
(`init_layer`), the layer is applied to every checked row, and freed.

Two CONTROLS set the limits of `correct` (never a benchmark run). `quant`:
the same model with every matmul operand, norm output, activation and
residual sum rounded to fp8 (e4m3), per slice scaled to the format's range;
the recurrence itself stays float32 on its rounded inputs. `state_round`:
the float32 model with the linear layers' state rounded to that dtype after
every token, which is what a state held in the cache's bf16 would be.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
Q_BLOCK = 256  # query rows whose scores are whole at once


# ------------------------------------------------------------ the controls


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

KINDS = {"linear_attention": "linear", "full_attention": "full"}


def dims(cfg: dict) -> dict:
    depth = cfg["num_hidden_layers"]
    return dict(
        dim=cfg["hidden_size"], depth=depth, heads=cfg["num_attention_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        lin_heads=cfg["linear_num_key_heads"], dk=cfg["linear_key_head_dim"],
        dv=cfg["linear_value_head_dim"], taps=cfg["linear_conv_kernel_dim"],
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        kinds=tuple(KINDS[k] for k in cfg["layer_types"][:depth]),
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    D, F = d["dim"], d["ff"]
    shapes = {"post_attn_g": (D,), "post_ff_g": (D,),
              "gate_w": (D, F), "up_w": (D, F), "down_w": (F, D)}
    if kind == "full":
        shapes.update(qkv_w=(D, 3 * D), q_norm_g=(D,), k_norm_g=(D,), o_w=(D, D))
    else:
        H, dk, dv = d["lin_heads"], d["dk"], d["dv"]
        shapes.update(qkv_w=(D, H * (2 * dk + dv)), ab_w=(D, 2 * H), g_w=(D, H * dv),
                      o_w=(H * dv, D), conv_w=(d["taps"], H * (2 * dk + dv)),
                      a_log=(H,), dt_bias=(H,), o_norm_g=(dv,))
    return shapes


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"emb": (d["vocab"], d["dim"]), "final_norm_g": (d["dim"],),
            "head_w": (d["dim"], d["vocab"])}


def n_params(cfg: dict) -> int:
    """Parameters held: what the configuration file's `parameters_here` states."""
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return count(top_shapes(cfg)) + sum(count(layer_shapes(cfg, k)) for k in dims(cfg)["kinds"])


FLOAT32_LEAVES = ("a_log", "dt_bias")  # vectors that are not stored rounded


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves, one key a leaf by its name's place in the sorted names:
    matrices normal / sqrt(fan_in) (the convolution's over its taps), the
    embedding 1 / sqrt(dim), gains 1 +- 10%, A_log = log U(1, 16), dt_bias
    the inverse softplus of U(0.001, 0.1); matrices rounded to what the model
    stores."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jax.random.uniform(k, shape, jnp.float32, 0.001, 0.1)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("_g"):
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                shape[-1] if name == "emb" else shape[-2])
            out[name] = _to_bf16(w) if stored == "bfloat16" else w
    return out


def _to_bf16(x):
    """x rounded to bfloat16's 8 bits of mantissa, still float32. Not a cast
    there and back: the compiler may drop such a pair as excess precision."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


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


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight at once (small configurations: the CPU tests)."""
    return {"top": init_top(cfg, seed),
            "layers": [init_layer(cfg, seed, i) for i in range(dims(cfg)["depth"])]}


# ------------------------------------------------------------ the forward


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def _l2norm(z):
    return z / jnp.sqrt(jnp.sum(z * z, -1, keepdims=True) + 1e-6)


def conv(z, taps_w):
    """z [n, C] -> [n, C]: causal depthwise convolution, zeros before t = 0."""
    taps = taps_w.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    return sum(taps_w[j] * padded[j:j + z.shape[0]] for j in range(taps))


def recurrence(q, k, v, alpha, beta, state_round=None, state=None):
    """The gated delta rule a token at a time: q, k [n, H, d_k], v [n, H,
    d_v], alpha, beta [n, H] -> (o [n, H, d_v], S [H, d_k, d_v] after the
    last token)."""
    def step(s, t):
        q_t, k_t, v_t, a_t, b_t = t
        s = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        if state_round:
            s = _to_bf16(s)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    if state_round not in (None, "bfloat16"):
        raise ValueError(f"unknown state precision {state_round!r}")
    if state is None:
        state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    state, o = jax.lax.scan(step, state, (q, k, v, alpha, beta))
    return o, state


def linear_inputs(x, lp, d, quant=None):
    """(q, k, v, alpha, beta) of a linear layer on one sequence x [n, D]."""
    n, H, dk, dv = x.shape[0], d["lin_heads"], d["dk"], d["dv"]
    mixed = jax.nn.silu(conv(_mm("nd,dc->nc", x, lp["qkv_w"], quant), lp["conv_w"]))
    q, k, v = jnp.split(mixed, [H * dk, 2 * H * dk], axis=-1)
    q = _act(_l2norm(q.reshape(n, H, dk)) * dk**-0.5, quant)
    k = _act(_l2norm(k.reshape(n, H, dk)), quant)
    v = _act(v.reshape(n, H, dv), quant)
    ab = _mm("nd,dc->nc", x, lp["ab_w"], quant)
    beta = 2.0 * jax.nn.sigmoid(ab[:, :H])
    alpha = jnp.exp(-jnp.exp(lp["a_log"]) * jax.nn.softplus(ab[:, H:] + lp["dt_bias"]))
    return q, k, v, alpha, beta


def linear_mixer(x, lp, d, quant=None, state_round=None):
    """x [n, D] -> (mixer(x) [n, D], the state after the last token)."""
    n, H, dv = x.shape[0], d["lin_heads"], d["dv"]
    o, state = recurrence(*linear_inputs(x, lp, d, quant), state_round=state_round)
    gate = jax.nn.silu(_mm("nd,dc->nc", x, lp["g_w"], quant)).reshape(n, H, dv)
    y = _act(_rms(o, lp["o_norm_g"], d["eps"]) * gate, quant).reshape(n, H * dv)
    return _mm("nc,cd->nd", y, lp["o_w"], quant), state


def _attend(q, k, v, quant):
    """q, k, v [n, H, dh] -> [n, H * dh], causal, query rows in blocks."""
    n, h, dh = q.shape
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


def full_mixer(x, lp, d, quant=None):
    n, D, H = x.shape[0], d["dim"], d["heads"]
    q, k, v = jnp.split(_mm("nd,dc->nc", x, lp["qkv_w"], quant), 3, axis=-1)
    q = _rms(q, lp["q_norm_g"], d["eps"], quant).reshape(n, H, D // H)
    k = _rms(k, lp["k_norm_g"], d["eps"], quant).reshape(n, H, D // H)
    o = _attend(q, k, _act(v, quant).reshape(n, H, D // H), quant)
    return _mm("nc,cd->nd", _act(o, quant), lp["o_w"], quant)


def _swiglu(b, wg, wu, wd, quant):
    a = jax.nn.silu(_mm("nd,df->nf", b, wg, quant)) * _mm("nd,df->nf", b, wu, quant)
    return _mm("nf,fd->nd", _act(a, quant), wd, quant)


def layer(x, lp, kind, d, quant=None, state_round=None):
    """One layer on one sequence x [n, D]: (out, the linear layer's last
    state [H, d_k, d_v], or zeros [1] of a full layer)."""
    if kind == "linear":
        m, state = linear_mixer(x, lp, d, quant, state_round)
    else:
        m, state = full_mixer(x, lp, d, quant), jnp.zeros((1,), jnp.float32)
    h = _act(x + _rms(_act(m, quant), lp["post_attn_g"], d["eps"], quant), quant)
    f = _swiglu(h, lp["gate_w"], lp["up_w"], lp["down_w"], quant)
    return _act(h + _rms(_act(f, quant), lp["post_ff_g"], d["eps"], quant), quant), state


@partial(jax.jit, static_argnames=("kind", "quant", "state_round", "d"))
def _layer_rows(x, lp, *, kind, d, quant, state_round):
    d = dict(d)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: layer(row, lp, kind, d, quant, state_round), x)


def forward(cfg: dict, seed: int, tokens, start: int = 0, quant=None, params=None,
            state_round=None) -> dict:
    """The uncached forward over `tokens` [R, n], a layer at a time.

    Returns `logits` [R, n - start, vocab] float32 (of positions `start` on)
    and `state` [R, H, d_k, d_v]: the FIRST linear layer's state after the
    last token. `params`: `{"top": ..., "layers": [...]}` made already (the
    CPU tests); left out, each layer's weights are made from `seed` when it is
    reached and freed after."""
    d = dims(cfg)
    static = tuple(sorted((k, v) for k, v in d.items()))
    tokens = jnp.asarray(tokens)
    top = params["top"] if params else init_top(cfg, seed)
    x = top["emb"][tokens]
    first_state = None
    for i, kind in enumerate(d["kinds"]):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        x, state = _layer_rows(x, lp, kind=kind, d=static, quant=quant,
                               state_round=state_round)
        if kind == "linear" and first_state is None:
            first_state = np.asarray(state)
        del lp
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, start:], top["final_norm_g"], d["eps"], quant)
        logits = _mm("rnd,dv->rnv", h, top["head_w"], quant, -1, 0)
    return {"logits": np.asarray(logits), "state": first_state}
