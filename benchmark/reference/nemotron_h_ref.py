"""Plain float32 reference of the NVIDIA-Nemotron-3-Nano-30B-A3B causal
language model (`model_type: nemotron_h`), one chip's share of it: every
layer ONE sublayer under one pre-norm and one residual, a Mamba-2 mixer (`M`),
attention over grouped K/V heads (`*`) or routed experts without a gate (`E`)
by `hybrid_override_pattern`, a final norm, an untied head over a slice of
the vocabulary.

Straightforward `jax.numpy`: no kernels, no cache, no chunking, nothing
imported from the program; the state-space layer is a `lax.scan` over tokens.
The keys are read from the configuration file as they are named there (T
tokens, x [T, D], D = hidden_size):

  rms(u; g) = u / sqrt(mean(u^2) + layer_norm_epsilon) * g
  every layer l:  x = x + mixer_l(rms(x; g_l))

  `M`, Mamba-2 (H = mamba_num_heads heads of P = mamba_head_dim, d_inner = H P,
  which is NOT expand x hidden_size; G = n_groups, N = ssm_state_size):
    z | xBC | dt = a W_in        columns: H P | H P + 2 G N | H, no bias
    xBC = silu(conv(xBC) + b_conv)
        conv: causal, depthwise, over time, conv_kernel taps:
        conv(u)_t = sum_j c[j] u_{t - taps + 1 + j}, u = 0 before t = 0
    x | B | C = xBC              x: H heads of P; B, C: G groups of N, head i
                                 reads group i // (H / G)
    dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)           one a head
    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T        S: [N, P] a head, S_{-1} = 0
    y_t = S_t^T C_t + D x_t                              [P]
    y = rms_group(y silu(z)) * gain      the norm over each of G groups of H P / G
    mixer = y W_out                      H P -> D
    (the issue writes S as [P, N] = dt x B^T; this file keeps its transpose,
    N first, as the program's cache does: the numbers are the same)

  `*`, attention: q = a W_q as num_attention_heads heads of head_dim, k = a
    W_k, v = a W_v as num_key_value_heads heads; query head j reads K/V head
    j // (heads / kv_heads); causal softmax at 1 / sqrt(head_dim); no bias, NO
    position embedding; mixer = concat_j(attend) W_o

  `E`, routed: s = sigmoid(a W_r) in float32 over ALL the published experts;
    S(t) the num_experts_per_tok largest of s + bias (n_group = topk_group = 1:
    no group limit); w_e = routed_scaling_factor * s_e / sum_{S(t)} s
    (norm_topk_prob); expert e(a) = relu(a W_up)^2 W_down (mlp_hidden_act
    relu2, width moe_intermediate_size, no gate); the shared expert the same
    form at moe_shared_expert_intermediate_size;
    mixer = shared(a) + sum_{e in S(t), e held here} w_e e(a)

  after the last layer: logits = rms(x; g_final) W_head

computed as every held expert on every token times a weight that is 0 where
the expert was not chosen. What the absent experts would add is left out
(model-configs guide, section 4), here and in the program alike.

ASSUMED (the configuration file lists each with its ground): one pre-norm and
one residual a layer, no position embedding in the attention layers, the
gated group norm, the convolution's SiLU and bias, the score-correction
bias, the state in float32, seeded weights (matrices normal / sqrt(fan_in),
the convolution's over its taps, the embedding 1 / sqrt(D), gains and D 1 +-
10%, the two biases 0.1 x normal, A_log = log U(1, 16), dt_bias the inverse
softplus of exp U(log 0.001, log 0.1)).

A DEPARTURE from "float32 weights": the model IS its stored weights. Where
the configuration stores them in bfloat16 (`program.weights_dtype`), each
seeded matrix (the convolution's taps among them) is rounded to bfloat16 once
and the reference computes with that in float32; gains, the router and its
bias, the convolution's bias, A_log, D and dt_bias are float32 in both.

Memory: weights are made ONE LAYER AT A TIME from per-leaf keys
(`init_layer`), the layer is applied to every checked row (query rows in
blocks, the held experts one at a time), and freed. Rows of different lengths
go in as a LIST of [R, n] arrays, a layer's weights made once for all of them.

Two CONTROLS set the limits of `correct` (never a benchmark run). `quant`:
the same model with every matmul operand, norm output, activation and
residual sum rounded to fp8 (e4m3), per slice scaled to the format's range;
the recurrence itself stays float32 on its rounded inputs, and the router's
product float32 on its rounded input. `state_round`: the float32 model with
the state-space layers' state rounded to that dtype after every token, which
is what a state held in the cache's bf16 would be.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
Q_BLOCK = 256  # query rows whose scores are whole at once: [heads, 256, n] float32


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

KINDS = {"M": "ssm", "*": "attention", "E": "routed"}


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file: the published keys, with the held
    experts, the depth and the vocabulary slice as the file states them."""
    depth = cfg["num_hidden_layers"]
    return dict(
        dim=cfg["hidden_size"], depth=depth, vocab=cfg["vocab_size"],
        eps=float(cfg["layer_norm_epsilon"]),
        kinds=tuple(KINDS[c] for c in cfg["hybrid_override_pattern"][:depth]),
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        groups=cfg["n_groups"], state=cfg["ssm_state_size"], taps=cfg["conv_kernel"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["moe_shared_expert_intermediate_size"],
        experts_total=cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]),
        experts_held=cfg["n_routed_experts"],
        experts_first=cfg.get("deployment", {}).get("experts_first", 0),
        per_token=cfg["num_experts_per_tok"], routed_scale=float(cfg["routed_scaling_factor"]),
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict, kind: str) -> dict:
    """One layer's leaves: its norm's gain and its one sublayer's."""
    d = dims(cfg)
    D = d["dim"]
    if kind == "ssm":
        H, P, G, N = d["ssm_heads"], d["ssm_head_dim"], d["groups"], d["state"]
        width = H * P + 2 * G * N
        return {"norm_g": (D,), "in_w": (D, H * P + width + H), "conv_w": (d["taps"], width),
                "conv_b": (width,), "a_log": (H,), "dt_bias": (H,), "skip_g": (H,),
                "gate_norm_g": (H * P,), "out_w": (H * P, D)}
    if kind == "attention":
        H, K, dh = d["heads"], d["kv_heads"], d["head_dim"]
        return {"norm_g": (D,), "q_w": (D, H * dh), "k_w": (D, K * dh), "v_w": (D, K * dh),
                "o_w": (H * dh, D)}
    G, F, Fs = d["experts_held"], d["expert_dim"], d["shared_dim"]
    return {"norm_g": (D,), "router_w": (D, d["experts_total"]), "router_b": (d["experts_total"],),
            "up_w": (G, D, F), "down_w": (G, F, D), "sh_up_w": (D, Fs), "sh_down_w": (Fs, D)}


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"emb": (d["vocab"], d["dim"]), "final_norm_g": (d["dim"],),
            "head_w": (d["dim"], d["vocab"])}


def n_params(cfg: dict) -> int:
    """Parameters held: what the configuration file's `parameters_here` states."""
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return count(top_shapes(cfg)) + sum(count(layer_shapes(cfg, k)) for k in dims(cfg)["kinds"])


# vectors and the router, which are not stored rounded
FLOAT32_LEAVES = ("router_w", "router_b", "conv_b", "a_log", "dt_bias")
BIAS_SCALE = 0.1  # the seeded biases: leaving one out moves what it feeds


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves, one key a leaf by its name's place in the sorted names:
    matrices normal / sqrt(fan_in) (the convolution's over its taps, the
    router's too), the embedding 1 / sqrt(dim), gains and D 1 +- 10%, biases
    BIAS_SCALE x normal, A_log = log U(1, 16), dt_bias the inverse softplus of
    exp U(log 0.001, log 0.1); matrices rounded to what the model stores."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(0.001),
                                            math.log(0.1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("_g"):
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("_b"):
            out[name] = BIAS_SCALE * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                shape[-1] if name == "emb" else shape[-2])
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


def conv(u, taps_w):
    """u [n, C] -> [n, C]: causal depthwise convolution, zeros before t = 0."""
    taps = taps_w.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(taps_w[j] * padded[j:j + u.shape[0]] for j in range(taps))


def recurrence(x, dt, a, b, c, skip, state_round=None, state=None):
    """The state-space recurrence a token at a time: x [n, H, P], dt [n, H]
    (after its softplus), a, skip [H], b, c [n, G, N] -> (y [n, H, P], S [H,
    N, P] after the last token)."""
    per_head = x.shape[1] // b.shape[1]

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        b_t, c_t = (jnp.repeat(u, per_head, axis=0) for u in (b_t, c_t))  # [H, N]
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        if state_round:
            s = _to_bf16(s)
        return s, jnp.einsum("hnp,hn->hp", s, c_t) + skip[:, None] * x_t

    if state_round not in (None, "bfloat16"):
        raise ValueError(f"unknown state precision {state_round!r}")
    if state is None:
        state = jnp.zeros((x.shape[1], b.shape[2], x.shape[2]), jnp.float32)
    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


def ssm_inputs(a_in, lp, d, quant=None):
    """(z, x, dt, b, c) of a state-space layer on one normed sequence [n, D]."""
    n, H, P, G, N = a_in.shape[0], d["ssm_heads"], d["ssm_head_dim"], d["groups"], d["state"]
    inner = H * P
    z, xbc, dt = jnp.split(_mm("nd,dc->nc", a_in, lp["in_w"], quant),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    mixed = _act(jax.nn.silu(conv(xbc, lp["conv_w"]) + lp["conv_b"]), quant)
    x, b, c = jnp.split(mixed, [inner, inner + G * N], axis=-1)
    return (z, x.reshape(n, H, P), jax.nn.softplus(dt + lp["dt_bias"]),
            b.reshape(n, G, N), c.reshape(n, G, N))


def ssm_mixer(a_in, lp, d, quant=None, state_round=None):
    """a_in [n, D] -> (mixer [n, D], the state after the last token [H, N, P])."""
    n, G = a_in.shape[0], d["groups"]
    z, x, dt, b, c = ssm_inputs(a_in, lp, d, quant)
    y, state = recurrence(x, dt, -jnp.exp(lp["a_log"]), b, c, lp["skip_g"], state_round)
    y = (y.reshape(n, -1) * jax.nn.silu(z)).reshape(n, G, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + d["eps"])
    y = _act(y.reshape(n, -1) * lp["gate_norm_g"], quant)
    return _mm("nc,cd->nd", y, lp["out_w"], quant), state


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


def attention_mixer(a_in, lp, d, quant=None):
    n, H, K, dh = a_in.shape[0], d["heads"], d["kv_heads"], d["head_dim"]
    q = _act(_mm("nd,dc->nc", a_in, lp["q_w"], quant), quant).reshape(n, H, dh)
    k = _act(_mm("nd,dc->nc", a_in, lp["k_w"], quant), quant).reshape(n, K, dh)
    v = _act(_mm("nd,dc->nc", a_in, lp["v_w"], quant), quant).reshape(n, K, dh)
    return _mm("nc,cd->nd", _act(_attend(q, k, v, quant), quant), lp["o_w"], quant)


def _relu2(b, w_up, w_down, quant):
    a = jnp.square(jax.nn.relu(_mm("nd,df->nf", b, w_up, quant)))
    return _mm("nf,fd->nd", _act(a, quant), w_down, quant)


def route(b, router_w, router_b, d):
    """(weights [n, E] float32: 0 where not chosen, renormalised over the
    chosen and scaled; choices [n, per_token], by score + bias, largest first)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(b @ router_w)
    idx = jax.lax.top_k(s + router_b, d["per_token"])[1]
    top = jnp.take_along_axis(s, idx, -1)
    top = d["routed_scale"] * top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, d["experts_total"], dtype=s.dtype)  # [n, k, E]
    return jnp.einsum("nk,nke->ne", top, chosen), idx


def routed_experts(b, weights, lp, d, quant=None, held=None):
    """Sum over the held experts of weight x expert, one expert at a time.
    `held = (first, count)`: others than the configuration's (the experts'
    leaves are then `count` of them from the first leaf on)."""
    first, count = (d["experts_first"], d["experts_held"]) if held is None else held

    def one(acc, e):
        w_up, w_down, w = e
        return acc + w[:, None] * _relu2(b, w_up, w_down, quant), None

    w_held = jax.lax.dynamic_slice_in_dim(weights, first, count, 1).T  # [G, n]
    return jax.lax.scan(one, jnp.zeros_like(b), (lp["up_w"], lp["down_w"], w_held))[0]


def routed_mixer(a_in, lp, d, quant=None):
    """a_in [n, D] -> (mixer [n, D], the router's choices [n, k])."""
    weights, choices = route(a_in, lp["router_w"], lp["router_b"], d)
    shared = _relu2(a_in, lp["sh_up_w"], lp["sh_down_w"], quant)
    return shared + routed_experts(a_in, weights, lp, d, quant), choices


def layer(x, lp, kind, d, quant=None, state_round=None):
    """One layer on one sequence x [n, D]: (out, the state-space layer's last
    state [H, N, P] or zeros [1], the router's choices [n, k] or zeros [1])."""
    a_in = _rms(x, lp["norm_g"], d["eps"], quant)
    state = choices = jnp.zeros((1,), jnp.float32)
    if kind == "ssm":
        m, state = ssm_mixer(a_in, lp, d, quant, state_round)
    elif kind == "attention":
        m = attention_mixer(a_in, lp, d, quant)
    else:
        m, choices = routed_mixer(a_in, lp, d, quant)
    return _act(x + _act(m, quant), quant), state, choices


@partial(jax.jit, static_argnames=("kind", "quant", "state_round", "d"))
def _layer_rows(x, lp, *, kind, d, quant, state_round):
    d = dict(d)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: layer(row, lp, kind, d, quant, state_round), x)


def forward(cfg: dict, seed: int, tokens, start=0, quant=None, params=None,
            state_round=None):
    """The uncached forward over `tokens` [R, n], a layer at a time.

    Returns `logits` [R, n - start, vocab] float32 (of positions `start` on),
    `state` [R, H, N, P]: the FIRST state-space layer's state after the last
    token, and `choices` [R, n - start, per_token]: what the FIRST routed
    layer's router chose from `start` on. `tokens` may be a LIST of such
    arrays, rows of another length each, with `start` a list beside it: a
    layer's weights are made once for all of them, and every entry of the
    result is a list. `params`: `{"top": ..., "layers": [...]}` made already
    (the CPU tests); left out, each layer's weights are made from `seed` when
    it is reached and freed after."""
    many = isinstance(tokens, (list, tuple))
    groups = [jnp.asarray(t) for t in (tokens if many else [tokens])]
    starts = list(start) if many else [start]
    d = dims(cfg)
    static = tuple(sorted((k, v) for k, v in d.items()))
    top = params["top"] if params else init_top(cfg, seed)
    xs = [top["emb"][t] for t in groups]
    first_state, first_choices = None, None
    for i, kind in enumerate(d["kinds"]):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        outs = [_layer_rows(x, lp, kind=kind, d=static, quant=quant, state_round=state_round)
                for x in xs]
        xs = [o[0] for o in outs]
        if kind == "ssm" and first_state is None:
            first_state = [np.asarray(o[1]) for o in outs]
        if kind == "routed" and first_choices is None:
            first_choices = [np.asarray(o[2][:, s:]) for o, s in zip(outs, starts)]
        del lp, outs
    logits = []
    with jax.default_matmul_precision("highest"):
        for x, s in zip(xs, starts):
            h = _rms(x[:, s:], top["final_norm_g"], d["eps"], quant)
            logits.append(np.asarray(_mm("rnd,dv->rnv", h, top["head_w"], quant, -1, 0)))
    out = {"logits": logits, "state": first_state, "choices": first_choices}
    return out if many else {k: v[0] for k, v in out.items()}
