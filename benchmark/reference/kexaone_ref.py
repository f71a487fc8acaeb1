"""Plain float32 reference of the K-EXAONE-236B-A23B causal language model,
one chip's share of it, with its multi-token module: grouped K/V heads under
a per-head q/k norm, window and full layers `L L L G`, a leading dense SwiGLU
layer, then routed layers with a shared expert beside sigmoid-routed ones
chosen under a score-correction bias, of which this chip holds some, an
untied head over a slice of the vocabulary, and after the trunk one more
block that drafts the token after the next.

Straightforward `jax.numpy`: no kernels, no cache, nothing imported from the
program. The keys are read from the configuration file as they are named
there. T tokens, x [T, hidden]:

  rms(u; g) = u / sqrt(mean(u^2) + eps) * g,  eps = rms_norm_eps
  Block:  y = x + Attn(rms(x; g_a));  z = y + F(rms(y; g_f))          pre-norm
  Attn(a): q = a W_q as num_attention_heads heads of head_dim; k = a W_k, v =
      a W_v as num_key_value_heads heads; no biases
      q_j = rms(q_j; g_q), k_j = rms(k_j; g_k)     per head, gains [head_dim]
      a WINDOW layer (`sliding_attention`) turns q and k: rotate-half over
      head_dim, inv_freq_i = rope_theta^(-2i/head_dim), float32 angles,
      absolute positions, no scaling; a FULL layer takes no rotary
      query head j reads K/V head j // (heads / kv_heads)
      s_j(t, p) = q_j(t) . k(p) / sqrt(head_dim);  a window layer's query at
      t sees p iff 0 <= t - p < sliding_window, a full layer's p <= t
      Attn = concat_j(softmax(s_j) v) W_o
  F, a `dense` layer (mlp_layer_types):  (silu(b W_g) * (b W_u)) W_d, width
      intermediate_size
  F, a `sparse` layer:  s = sigmoid(b W_r) in float32 over ALL the published
      experts; S(t) the num_experts_per_tok largest of s + bias (bias a
      float32 vector; n_group = topk_group = 1: no group limit); w_e =
      routed_scaling_factor * s_e / sum_{S(t)} s (norm_topk_prob);
      F(b) = shared(b) + sum_{e in S(t), e held here} w_e E_e(b), each a
      SwiGLU of width moe_intermediate_size
  after the last layer: logits = rms(x; g_final) W_head
  the multi-token module, for position i with the trunk's output h_i BEFORE
      the final norm and the NEXT token t_{i+1}:
      u_i = [rms(Emb(t_{i+1}); g_e); rms(h_i; g_h)] W_eh      (2 hidden -> hidden)
      g_i = Block_mtp(u_0 .. u_i)      a FULL layer (mtp_layer_types), routed F
      logits drafting position i + 2 = rms(g_i; g_m) W_head   the trunk's own
      embedding and head

  a verify step's SECOND position (`forward(..., drafts=)`): the draft d put
      at position p + 1 in place of the sequence's own token, which sees the
      sequence's positions up to p and itself: the logits there are those of
      the sequence tokens[0..p] + [d] at its last position, for every p of
      the turn at once (a second stream beside the sequence's own: same
      weights, its keys and values seen by itself alone)

computed as every held expert on every token times a weight that is 0 where
the expert was not chosen. What the absent experts would add is left out
(model-configs guide, section 4), here and in the program alike.

ASSUMED (the configuration file lists them with their grounds): pre-norm
blocks, the per-head q/k norm, the rotary on the window layers alone, the
score-correction bias, the module's form, seeded weights.

A DEPARTURE from "float32 weights": the model IS its stored weights. Where
the configuration stores them in bfloat16 (`program.weights_dtype`, as the
published checkpoint does), each seeded matrix is rounded to bfloat16 once
and the reference computes with that in float32; gains, the router and its
bias are float32 in both.

Memory: the weights are made ONE LAYER AT A TIME from per-leaf keys
(`init_layer`), the layer is applied to every checked row (query rows in
blocks, the held experts one at a time), and freed.

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
SIDE_BLOCK = 64  # the same for the second stream's queries


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

ATTN_KINDS = {"sliding_attention": "window", "full_attention": "full"}
FF_KINDS = {"dense": "dense", "sparse": "routed"}


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file: the published keys, with the held
    experts, the depth and the vocabulary slice as the file states them."""
    depth = cfg["num_hidden_layers"]
    return dict(
        dim=cfg["hidden_size"], depth=depth, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=int(cfg["sliding_window"]), theta=float(cfg["rope_parameters"]["rope_theta"]),
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        attn=tuple(ATTN_KINDS[k] for k in cfg["layer_types"][:depth]),
        kinds=tuple(FF_KINDS[k] for k in cfg["mlp_layer_types"][:depth]),
        dense_dim=cfg["intermediate_size"], expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        experts_total=cfg["published"]["num_experts"], experts_held=cfg["num_experts"],
        experts_first=cfg["deployment"]["experts_first"], per_token=cfg["num_experts_per_tok"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        drafts=int(cfg["num_nextn_predict_layers"]),
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict, kind: str) -> dict:
    """One block's leaves; `kind` its feed-forward's (`dense` or `routed`)."""
    d = dims(cfg)
    D, H, K, dh = d["dim"], d["heads"], d["kv_heads"], d["head_dim"]
    shapes = {
        "norm_attn_g": (D,), "q_w": (D, H * dh), "k_w": (D, K * dh), "v_w": (D, K * dh),
        "q_norm_g": (dh,), "k_norm_g": (dh,), "o_w": (H * dh, D), "norm_ff_g": (D,),
    }
    if kind == "dense":
        F = d["dense_dim"]
        shapes.update(gate_w=(D, F), up_w=(D, F), down_w=(F, D))
    else:
        G, F, Fs = d["experts_held"], d["expert_dim"], d["shared_dim"]
        shapes.update(router_w=(D, d["experts_total"]), router_b=(d["experts_total"],),
                      gate_w=(G, D, F), up_w=(G, D, F), down_w=(G, F, D),
                      sh_gate_w=(D, Fs), sh_up_w=(D, Fs), sh_down_w=(Fs, D))
    return shapes


def top_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    return {"emb": (d["vocab"], d["dim"]), "final_norm_g": (d["dim"],),
            "head_w": (d["dim"], d["vocab"])}


def module_shapes(cfg: dict) -> dict:
    """The multi-token module's own leaves, beside its block's."""
    D = dims(cfg)["dim"]
    return {"norm_e_g": (D,), "norm_h_g": (D,), "proj_w": (2 * D, D), "norm_m_g": (D,)}


def n_params(cfg: dict) -> int:
    """Parameters of the share: what the configuration file's `parameters_here` states."""
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    d = dims(cfg)
    module = (count(module_shapes(cfg)) + count(layer_shapes(cfg, "routed"))) * d["drafts"]
    return count(top_shapes(cfg)) + sum(count(layer_shapes(cfg, k)) for k in d["kinds"]) + module


FLOAT32_LEAVES = ("router_w", "router_b")  # not stored rounded
BIAS_SCALE = 0.1  # the seeded score-correction bias: leaving it out flips routes


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves: matrices normal / sqrt(fan_in) (the router's too), the
    embedding 1 / sqrt(dim), gains 1 +- 10%, the router's bias BIAS_SCALE x
    normal; one key a leaf, by its name's place in the sorted names; matrices
    rounded to what the model stores."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("_g"):
            out[name] = 1.0 + 0.1 * z
        elif name.endswith("_b"):
            out[name] = BIAS_SCALE * z
        else:
            w = z / math.sqrt(shape[-1] if name == "emb" else shape[-2])
            if stored == "bfloat16" and name not in FLOAT32_LEAVES:
                w = w.astype(jnp.bfloat16).astype(jnp.float32)
            out[name] = w
    return out


def _key(seed: int, part: int):
    return jax.random.fold_in(jax.random.PRNGKey(int(seed) % (2**31 - 1)), part)


def init_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer i's weights alone, one jitted call on the device; layer `depth`
    is the multi-token module's block."""
    d = dims(cfg)
    kind = d["kinds"][i] if i < d["depth"] else "routed"
    return jax.jit(lambda k: _make(k, layer_shapes(cfg, kind), d["stored"]))(_key(seed, i + 1))


def init_top(cfg: dict, seed: int) -> dict:
    """Embedding, final gain and head."""
    return jax.jit(lambda k: _make(k, top_shapes(cfg), dims(cfg)["stored"]))(_key(seed, 0))


def init_module(cfg: dict, seed: int) -> dict:
    """The module's two input gains, its projection and its final gain."""
    d = dims(cfg)
    return jax.jit(lambda k: _make(k, module_shapes(cfg), d["stored"]))(
        _key(seed, d["depth"] + 2))


# ------------------------------------------------------------ the forward


def cos_sin(d: dict, n: int):
    """(cos, sin) float32 [n, head_dim], halves paired."""
    dh = d["head_dim"]
    inv_freq = d["theta"] ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.concatenate([angles, angles], -1)
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(t, cos, sin):
    """t [n, heads, dim] turned by [n, dim] tables."""
    a, b = jnp.split(t, 2, -1)
    return t * cos[:, None] + jnp.concatenate([-b, a], -1) * sin[:, None]


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def _attend(q, k, v, window, quant, first=0):
    """q [m, H, dh] (the queries at positions first .. first + m - 1), k, v
    [n, H, dh] -> [m, H * dh], causal (and within `window`, where there is
    one), query rows in blocks. A window layer's block is given the keys it
    can see and no others (the block's positions and the window - 1 before
    them): the same sums, without the n - window products that the mask
    would set aside."""
    (m, h, dh), n = q.shape, k.shape[0]
    block = min(Q_BLOCK, m)
    pad = (-m) % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, dh)
    t0 = first + jnp.arange(qb.shape[0]) * block
    span = n if window is None else block + window - 1
    lead = 0 if window is None else window - 1
    k, v = (jnp.pad(t, ((lead, pad), (0, 0), (0, 0))) for t in (k, v))

    def rows(args):
        qi, start = args
        # the keys at positions start - lead .. (all of them without a window)
        begin = 0 if window is None else start
        ki, vi = (jax.lax.dynamic_slice_in_dim(t, begin, span, 0) for t in (k, v))
        at = begin - lead + jnp.arange(span)
        gap = (start + jnp.arange(block))[:, None] - at[None, :]
        live = (gap >= 0) & (at >= 0)[None, :] & (at < n)[None, :]
        if window is not None:
            live &= gap < window
        s = _mm("ihd,jhd->hij", qi * dh**-0.5, ki, quant, -1, -1)
        s = jnp.where(live[None], s, NEG)
        return _mm("hij,jhd->ihd", jax.nn.softmax(s, -1), vi, quant, -1, 0)

    return jax.lax.map(rows, (qb, t0)).reshape(-1, h * dh)[:m]


def _attend_side(q, ks, vs, k, v, window, quant, first):
    """The second stream's attention: q, ks, vs [m, H, dh], entry j standing
    at position first + j + 1 beside the sequence's k, v [n, H, dh]: it sees
    the sequence's positions up to first + j (within `window` of its own,
    where there is one) and itself. -> [m, H * dh], query rows in blocks."""
    (m, h, dh), n = q.shape, k.shape[0]
    block = min(SIDE_BLOCK, m)
    pad = (-m) % block
    qb, kb, vb = (jnp.pad(t, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, h, dh)
                  for t in (q, ks, vs))
    t0 = first + 1 + jnp.arange(qb.shape[0]) * block

    def rows(args):
        qi, ki, vi, start = args
        gap = (start + jnp.arange(block))[:, None] - jnp.arange(n)[None, :]
        live = gap >= 1
        if window is not None:
            live &= gap < window
        s = _mm("ihd,jhd->hij", qi * dh**-0.5, k, quant, -1, -1)
        s = jnp.where(live[None], s, NEG)
        own = _mm("ihd,ihd->hi", qi * dh**-0.5, ki, quant, -1, -1)
        w = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), -1)
        return (_mm("hij,jhd->ihd", w[..., :-1], v, quant, -1, 0)
                + w[..., -1].T[..., None] * _act(vi, quant))

    return jax.lax.map(rows, (qb, kb, vb, t0)).reshape(-1, h * dh)[:m]


def attention_half(x, lp, d, kind, quant=None, first=0, side=None):
    """x [n, dim] -> (x + Attn(rms(x)))[first:] on one sequence; `kind`
    window or full. Keys and values of every position, queries of the
    positions from `first` on. With `side` [m, dim] (the second stream: entry
    j at position n - m + j + 1, beside the sequence's last m positions) also
    its own `side + Attn(.)`, as a pair."""
    n, h, kv, dh = x.shape[0], d["heads"], d["kv_heads"], d["head_dim"]
    window = d["window"] if kind == "window" else None

    def qkv(rows, at, skip=0):
        """k, v [len, H, dh] of rows standing at the positions `at`, and q of
        all but the first `skip` of them."""
        a = _rms(rows, lp["norm_attn_g"], d["eps"], quant)
        q = _mm("nd,de->ne", a[skip:], lp["q_w"], quant).reshape(-1, h, dh)
        k = _mm("nd,de->ne", a, lp["k_w"], quant).reshape(-1, kv, dh)
        v = _mm("nd,de->ne", a, lp["v_w"], quant).reshape(-1, kv, dh)
        q, k = _rms(q, lp["q_norm_g"], d["eps"], quant), _rms(k, lp["k_norm_g"], d["eps"], quant)
        if kind == "window":
            cos, sin = (t[at] for t in cos_sin(d, n + 1))
            q, k = _rotate(q, cos[skip:], sin[skip:]), _rotate(k, cos, sin)
        k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))  # head j reads j // group
        return q, k, v

    out = lambda rows, o: _act(
        rows + _mm("ne,ed->nd", _act(o, quant), lp["o_w"], quant), quant)
    q, k, v = qkv(x, jnp.arange(n), first)
    y = out(x[first:], _attend(q, k, v, window, quant, first))
    if side is None:
        return y
    m = side.shape[0]
    qs, ks, vs = qkv(side, n - m + 1 + jnp.arange(m))
    return y, out(side, _attend_side(qs, ks, vs, k, v, window, quant, n - m))


def _swiglu(b, wg, wu, wd, quant):
    a = jax.nn.silu(_mm("nd,df->nf", b, wg, quant)) * _mm("nd,df->nf", b, wu, quant)
    return _mm("nf,fd->nd", _act(a, quant), wd, quant)


def route(b, router_w, router_b, d):
    """(weights [n, E] float32: 0 where not chosen, renormalised over the
    chosen and scaled; choices [n, per_token], by score + bias, largest first)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(b @ router_w)
    idx = jax.lax.top_k(s if router_b is None else s + router_b, d["per_token"])[1]
    top = jnp.take_along_axis(s, idx, -1)
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


def feed_forward_half(y, lp, kind, d, quant=None):
    """y [n, dim] -> (y + F(rms(y)), the router's choices [n, k] or None for a
    dense layer)."""
    b = _rms(y, lp["norm_ff_g"], d["eps"], quant)
    if kind == "dense":
        f, choices = _swiglu(b, lp["gate_w"], lp["up_w"], lp["down_w"], quant), None
    else:
        weights, choices = route(b, lp["router_w"], lp["router_b"], d)
        f = shared_expert(b, lp, quant) + routed_experts(b, weights, lp, d, quant)
    return _act(y + _act(f, quant), quant), choices


def layer(x, lp, attn, kind, d, quant=None, first=0, side=None):
    """One block on one sequence x [n, dim]: (its output at the positions
    from `first` on, the router's choices there [n - first, k] or None for a
    dense layer). Every position's keys and values are made; what is asked of
    the positions before `first` alone is not. With `side` (the second
    stream: `attention_half`) its output comes third."""
    if side is None:
        return feed_forward_half(attention_half(x, lp, d, attn, quant, first), lp, kind, d, quant)
    y, y_side = attention_half(x, lp, d, attn, quant, first, side)
    return (*feed_forward_half(y, lp, kind, d, quant),
            feed_forward_half(y_side, lp, kind, d, quant)[0])


@partial(jax.jit, static_argnames=("attn", "kind", "quant", "d", "first"))
def _layer_rows(x, lp, side=None, *, attn, kind, d, quant, first=0):
    d = dict(d)
    with jax.default_matmul_precision("highest"):
        if side is None:
            return jax.lax.map(lambda row: layer(row, lp, attn, kind, d, quant, first), x)
        return jax.lax.map(lambda rows: layer(rows[0], lp, attn, kind, d, quant, first, rows[1]),
                           (x, side))


@partial(jax.jit, static_argnames=("eps", "quant"))
def _module_rows(emb, next_tokens, hidden, mp, *, eps, quant):
    """The module's inputs u [R, n, dim], a row at a time: the next tokens'
    embeddings and the trunk's outputs, each under its norm, side by side
    through the projection."""
    def one(args):
        t, h = args
        both = jnp.concatenate([_rms(emb[t], mp["norm_e_g"], eps, quant),
                                _rms(h, mp["norm_h_g"], eps, quant)], -1)
        return _act(_mm("nd,de->ne", both, mp["proj_w"], quant), quant)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (next_tokens, hidden))


def _static(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def forward(cfg: dict, seed: int, tokens, start: int = 0, quant=None, params=None,
            drafts=None) -> dict:
    """The uncached forward of trunk and module over `tokens` [R, n], a layer
    at a time.

    Returns `logits` [R, n - start, vocab] float32 (of positions `start` on),
    `choices` [R, n - start, per_token] (what the FIRST routed layer's router
    chose there) and, where the model has a module, `draft` [R, n - start,
    vocab]: entry j the module's logits at position start - 1 + j (the last
    is n - 2: a position needs the token after it), which draft the token at
    start + 1 + j (`start` >= 1). With `drafts` [R, n - start] also `second`
    [R, n - start, vocab]: entry j the logits at position start + j + 1 of
    the sequence tokens[:start + j + 1] + [drafts[j]] (what a verify step's
    second position computes when it is fed that draft). `params`: `{"top",
    "layers", "module"}` made already (the CPU tests: `init_params`); left
    out, each layer's weights are made from `seed` when it is reached and
    freed after."""
    d = dims(cfg)
    static = _static(d)
    tokens = jnp.asarray(tokens)
    top = params["top"] if params else init_top(cfg, seed)
    x = top["emb"][tokens]
    side = None if drafts is None else top["emb"][jnp.asarray(drafts)]
    first_choices = None
    # what nothing reads is not computed: where the model has no module, the
    # last layer's output before `start`; the module's own before start - 1
    tail = start if not d["drafts"] else 0
    for i, (attn, kind) in enumerate(zip(d["attn"], d["kinds"])):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        first = tail if i == d["depth"] - 1 else 0
        x, choices, *rest = _layer_rows(x, lp, side, attn=attn, kind=kind, d=static,
                                        quant=quant, first=first)
        side = rest[0] if rest else None
        if choices is not None and first_choices is None:
            first_choices = np.asarray(choices[:, start - first:])
        del lp
    head = lambda h, g: _mm("rnd,dv->rnv", _rms(h, g, d["eps"], quant), top["head_w"],
                            quant, -1, 0)
    with jax.default_matmul_precision("highest"):
        out = {"logits": np.asarray(head(x[:, start - tail:], top["final_norm_g"])),
               "choices": first_choices}
        if side is not None:
            out["second"] = np.asarray(head(side, top["final_norm_g"]))
    if not d["drafts"]:
        return out
    assert start >= 1, "the module's first entry is position start - 1"
    mp = params["module"] if params else init_module(cfg, seed)
    u = _module_rows(top["emb"], tokens[:, 1:], x[:, :-1], mp, eps=d["eps"], quant=quant)
    del x
    lp = params["layers"][d["depth"]] if params else init_layer(cfg, seed, d["depth"])
    g, _ = _layer_rows(u, lp, attn="full", kind="routed", d=static, quant=quant,
                       first=start - 1)
    with jax.default_matmul_precision("highest"):
        out["draft"] = np.asarray(head(g, mp["norm_m_g"]))
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight at once (small configurations: the CPU tests)."""
    d = dims(cfg)
    layers = [init_layer(cfg, seed, i) for i in range(d["depth"] + d["drafts"])]
    return {"top": init_top(cfg, seed), "layers": layers,
            "module": init_module(cfg, seed) if d["drafts"] else None}
