"""Plain float32 reference of the DeepSeek-V3.2-Exp causal language model, one
chip's share of it: latent attention (MLA) under a YaRN rotary table, a
lightning indexer beside it that selects the positions each query attends
(learned sparse attention), a leading dense SwiGLU layer, then routed layers
with a shared expert beside sigmoid-routed ones chosen group-limited by a
bias-corrected score, of which this chip holds some; an untied head over a
slice of the vocabulary.

Straightforward `jax.numpy`: no kernels, no cache, nothing imported from the
program. The layer, in its EXPANDED form (the keys are read from the
configuration file as they are named there; T tokens, x [T, hidden]):

  rms(u; g) = u / sqrt(mean(u^2) + eps) * g
  a   = rms(x; g_in)
  c_q = rms(a W_dq; g_q);  q = c_q W_uq, per head q_n | q_r
  c | k_r = a W_dkv;  c = rms(c; g_kv);  k_r is ONE head shared by all
  q_r, k_r rotated: rotate-half over qk_rope_head_dim under YaRN's table
      (`yarn_inv_freq`: rope_scaling's factor, original_max_position_embeddings,
      beta_fast, beta_slow; rope_theta), float32 angles, absolute positions;
      cos and sin times m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln(factor) + 1
  k_n | v = c W_ukv, per head
  the indexer:
      q_I = c_q W_Iq -> index_n_heads of index_head_dim, the first qk_rope_head_dim
            of each rotated by the same table
      k_I = LayerNorm(a W_Ik; gain, bias) -> one key, rotated likewise
      w   = a W_Iw / sqrt(index_n_heads)
      I(t, p) = sum_j w_t,j relu(q_I,t,j . k_I,p) / sqrt(index_head_dim)
      S_t = the min(index_topk, t + 1) positions p <= t of largest I(t, p)
            (`lax.top_k`: of equal scores the lower position)
  s_j(t, p) = (q_n,j(t) . k_n,j(p) + q_r,j(t) . k_r(p)) / sqrt(nope + rope)
              * m(mscale_all_dim)^2,    p in S_t (an explicit mask)
  y = x + concat_j(softmax(s_j) v_j) W_o
  b = rms(y; g_ff);  z = y + F(b), F one of
    dense:  F(b) = (silu(b W_g) * (b W_u)) W_d
    routed: s = sigmoid(b W_r) over ALL the published experts; u = s + bias;
            the experts stand in n_group groups; a group's score is the sum
            of its two largest u; the topk_group best groups stay; S(t) the
            num_experts_per_tok largest u within them; w_e =
            routed_scaling_factor * s_e / sum_{S(t)} s;  F(b) = shared(b) +
            sum_{e in S(t), e held here} w_e (silu(b W_g,e) * (b W_u,e)) W_d,e
  after the last layer: logits = rms(x; g_f) W_head

computed as every held expert on every token times a weight that is 0 where
the expert was not chosen. What the absent experts would add is left out
(model-configs guide, section 4), here and in the program alike.

DEPARTURES, stated in the configuration file: the published indexer turns
q_I and k_I by a Hadamard matrix (orthogonal: every q . k unchanged) and
quantises both to fp8, which is here the CONTROL's precision and not the
model's; both rotaries are rotate-half (a fixed permutation of seeded
columns); the multi-token module lies beyond the depth held. The model IS
its stored weights: where the configuration stores them in bfloat16
(`program.weights_dtype`), each seeded matrix is rounded to bfloat16 once
and the reference computes with that in float32; gains, biases and the
router are float32 in both.

Memory: a document of 33 k positions has 4.3 GB of expanded keys and values
in float32, 3.2 GB of queries and a billion (query, position) pairs a layer,
so the attention runs over GROUPS of heads (queries, keys and values expanded
a group at a time, the groups' outputs summed through W_o) against the
selection's mask [n, n], made once a layer in blocks of queries; the weights
are made ONE LAYER AT A TIME from per-leaf keys (`init_layer`), the layer is
applied to the checked rows one after the other, and freed.

`quant` is the CONTROL that sets the limits of `correct` (never a benchmark
run): the same model with every matmul operand, norm output and residual sum
rounded to fp8 (e4m3), per slice scaled to the format's range, the indexer's
products too; the router's product stays float32 on the rounded input.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
PAIRS = 1 << 27  # (head or index head, query, position) scores whole at once: 0.5 GB float32
HEAD_GROUP = 16  # heads whose keys and values are expanded at once


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
    yarn = cfg["rope_scaling"]
    if yarn["type"] != "yarn":
        raise ValueError("the reference builds YaRN's rotary table")
    m = lambda scale: 0.1 * float(scale) * math.log(float(yarn["factor"])) + 1.0
    return dict(
        dim=cfg["hidden_size"], depth=depth, heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        theta=float(cfg["rope_theta"]), yarn_factor=float(yarn["factor"]),
        yarn_original=float(yarn["original_max_position_embeddings"]),
        yarn_fast=float(yarn["beta_fast"]), yarn_slow=float(yarn["beta_slow"]),
        rotary_scale=m(yarn["mscale"]) / m(yarn["mscale_all_dim"]),
        softmax_mult=m(yarn["mscale_all_dim"]) ** 2,
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        index_eps=1e-6,  # the index key's LayerNorm: assumed (the config has no key)
        kinds=tuple("dense" if i < dense else "routed" for i in range(depth)),
        dense_dim=cfg["intermediate_size"], expert_dim=cfg["moe_intermediate_size"],
        shared_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts_total=cfg["published"]["n_routed_experts"], experts_held=cfg["n_routed_experts"],
        experts_first=cfg["deployment"]["experts_first"], per_token=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        stored=cfg.get("program", {}).get("weights_dtype", "float32"),
    )


def layer_shapes(cfg: dict, kind: str) -> dict:
    d = dims(cfg)
    D, H, Hi, Di = d["dim"], d["heads"], d["index_heads"], d["index_dim"]
    shapes = {
        "norm_attn_g": (D,), "dq_w": (D, d["q_rank"]), "q_norm_g": (d["q_rank"],),
        "uq_w": (d["q_rank"], H * (d["nope"] + d["rope"])),
        "dkv_w": (D, d["kv_rank"] + d["rope"]), "kv_norm_g": (d["kv_rank"],),
        "ukv_w": (d["kv_rank"], H * (d["nope"] + d["v_dim"])), "o_w": (H * d["v_dim"], D),
        "iq_w": (d["q_rank"], Hi * Di), "ik_w": (D, Di), "ik_norm_g": (Di,),
        "ik_norm_b": (Di,), "iw_w": (D, Hi), "norm_ff_g": (D,),
    }
    if kind == "dense":
        F = d["dense_dim"]
        shapes.update(gate_w=(D, F), up_w=(D, F), down_w=(F, D))
    else:
        G, F, Fs, E = d["experts_held"], d["expert_dim"], d["shared_dim"], d["experts_total"]
        shapes.update(router_w=(D, E), router_b=(E,), gate_w=(G, D, F), up_w=(G, D, F),
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


FLOAT32_LEAVES = ("router_w", "router_b", "ik_norm_b")  # not stored rounded


def _make(key, shapes: dict, stored: str) -> dict:
    """Seeded leaves: matrices normal / sqrt(fan_in) (the router's too), the
    embedding 1 / sqrt(dim), gains 1 +- 10%, the two biases (the router's
    score correction, the index key's norm) 0.1 x normal: non-zero, so that
    leaving one out shows; one key a leaf, by its name's place in the sorted
    names; matrices rounded to what the model stores."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("_g"):
            out[name] = 1.0 + 0.1 * z
        elif name.endswith("_b"):
            out[name] = 0.1 * z
        else:
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


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight at once (small configurations: the CPU tests)."""
    return {"top": init_top(cfg, seed),
            "layers": [init_layer(cfg, seed, i) for i in range(dims(cfg)["depth"])]}


# ------------------------------------------------------------ the forward


def yarn_inv_freq(d: dict) -> np.ndarray:
    """[rope / 2] float64: YaRN's inverse frequencies (arXiv:2309.00071). A
    channel that turns more than `beta_fast` times over the original context
    keeps its frequency, one that turns less than `beta_slow` times is
    interpolated by `factor`, a linear ramp between the two."""
    dim, theta = d["rope"], d["theta"]
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def channel(turns: float) -> float:  # the channel that turns so often over the original
        return dim * math.log(d["yarn_original"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(channel(d["yarn_fast"])), 0)
    high = min(math.ceil(channel(d["yarn_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0, 1)
    return plain / d["yarn_factor"] * ramp + plain * (1 - ramp)


def cos_sin(d: dict, n: int):
    """(cos, sin) float32 [n, rope], halves paired."""
    inv_freq = jnp.asarray(yarn_inv_freq(d), jnp.float32)
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], -1)
    scale = np.float32(d["rotary_scale"])
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def _rotate(t, cos, sin):
    """t [n, ..., dim] turned by [n, dim] tables."""
    a, b = jnp.split(t, 2, -1)
    shape = (t.shape[0],) + (1,) * (t.ndim - 2) + (t.shape[-1],)
    return t * cos.reshape(shape) + jnp.concatenate([-b, a], -1) * sin.reshape(shape)


def _rotate_first(t, cos, sin):
    """The first `cos.shape[-1]` channels of t [n, ..., dim] turned, the rest kept."""
    r = cos.shape[-1]
    return jnp.concatenate([_rotate(t[..., :r], cos, sin), t[..., r:]], -1)


def _rms(u, g, eps, quant=None):
    return _act(u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + eps) * g, quant)


def _layer_norm(u, g, b, eps, quant=None):
    mean = jnp.mean(u, -1, keepdims=True)
    var = jnp.mean((u - mean) ** 2, -1, keepdims=True)
    return _act((u - mean) / jnp.sqrt(var + eps) * g + b, quant)


def _split(a, block: int):
    """a [n, ...] -> [blocks, block, ...], the last block padded with zeros."""
    pad = (-a.shape[0]) % block
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(-1, block, *a.shape[1:])


def _query_block(n: int, d: dict) -> int:
    """Queries a block, so that a block's index scores [block, Hi, n] (and
    with them a group of heads' scores) stay under PAIRS numbers."""
    return max(1, min(n, PAIRS // max(d["index_heads"], HEAD_GROUP) // n))


def index_projections(a, c_q, lp, d, cos, sin, quant=None):
    """The indexer's three projections of one sequence: (q_I [n, Hi, Di], k_I
    [n, Di], w [n, Hi]), rotated, every constant folded into w."""
    n, hi, di = a.shape[0], d["index_heads"], d["index_dim"]
    q_i = _mm("nr,re->ne", c_q, lp["iq_w"], quant).reshape(n, hi, di)
    k_i = _layer_norm(_mm("nd,de->ne", a, lp["ik_w"], quant), lp["ik_norm_g"], lp["ik_norm_b"],
                      d["index_eps"], quant)
    w = _mm("nd,dh->nh", a, lp["iw_w"], quant) * (hi ** -0.5 * di ** -0.5)
    return _rotate_first(q_i, cos, sin), _rotate_first(k_i, cos, sin), w


def selection(q_i, k_i, w, d, quant=None):
    """`(mask [blocks, block, n] bool: query t attends position p, selected
    [n, topk] int32: S_t, -1 in the slots a short S_t leaves empty)`, a block
    of queries at a time: I(t, .) whole, `lax.top_k` of it, and the mask set
    at its indices."""
    n = q_i.shape[0]
    k = min(d["index_topk"], n)
    block = _query_block(n, d)
    if quant:
        k_i = _round(k_i, -1, quant)

    def rows(args):
        q, w_q, t0 = args
        if quant:
            q = _round(q, -1, quant)
        at = t0 + jnp.arange(block)
        score = jnp.einsum("th,thp->tp", w_q,
                           jax.nn.relu(jnp.einsum("thd,pd->thp", q, k_i)))
        score = jnp.where(jnp.arange(n)[None, :] <= at[:, None], score, NEG)
        best, idx = jax.lax.top_k(score, k)
        live = best > NEG
        mask = jnp.zeros((block, n), bool).at[
            jnp.arange(block)[:, None], jnp.where(live, idx, n)].set(True, mode="drop")
        return mask, jnp.where(live, idx, -1).astype(jnp.int32)

    blocks = _split(q_i, block).shape[0]
    mask, selected = jax.lax.map(
        rows, (_split(q_i, block), _split(w, block), jnp.arange(blocks) * block))
    return mask, selected.reshape(blocks * block, k)[:n]


def _attend(c_q, c, k_r, mask, lp, d, cos, sin, quant):
    """The expanded attention of one sequence under `mask` [blocks, block, n],
    through W_o: [n, dim]. A GROUP of heads at a time: its queries from c_q
    [n, q_rank] (rotated here), its keys and values from c [n, kv_rank], the
    shared rotary key k_r [n, rope] beside them; queries in the mask's blocks."""
    n, h = c_q.shape[0], d["heads"]
    dn, dr, dv = d["nope"], d["rope"], d["v_dim"]
    group = min(HEAD_GROUP, h)
    assert h % group == 0, "heads in equal groups"
    scale = (dn + dr) ** -0.5 * d["softmax_mult"]
    by_group = lambda w, width: w.reshape(-1, h // group, group, width).transpose(1, 0, 2, 3)
    w_q, w_kv = by_group(lp["uq_w"], dn + dr), by_group(lp["ukv_w"], dn + dv)
    w_o = lp["o_w"].reshape(h // group, group * dv, -1)

    def heads(u, args):
        wq_g, wkv_g, wo_g = args
        q = _mm("nr,rhe->nhe", c_q, wq_g, quant, -1, 0)
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)], -1)
        kv = _mm("nr,rhe->nhe", c, wkv_g, quant, -1, 0)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (n, group, dr))], -1)
        v = kv[..., dn:]

        def rows(args):
            qi, mi = args
            s = _mm("ihd,jhd->hij", qi * scale, k, quant, -1, -1)
            s = jnp.where(mi[None], s, NEG)
            return _mm("hij,jhd->ihd", jax.nn.softmax(s, -1), v, quant, -1, 0)

        out = jax.lax.map(rows, (_split(q, mask.shape[1]), mask))  # [blocks, block, g, dv]
        out = _act(out.reshape(-1, group * dv)[:n], quant)
        return u + _mm("ne,ed->nd", out, wo_g, quant), None

    return jax.lax.scan(heads, jnp.zeros((n, lp["o_w"].shape[1]), c.dtype),
                        (w_q, w_kv, w_o))[0]


def attention_half(x, lp, d, quant=None):
    """x [n, dim] -> (x + sparse latent attention(rms(x)), S [n, topk]) on
    one sequence."""
    n = x.shape[0]
    a = _rms(x, lp["norm_attn_g"], d["eps"], quant)
    c_q = _rms(_mm("nd,dr->nr", a, lp["dq_w"], quant), lp["q_norm_g"], d["eps"], quant)
    ckr = _mm("nd,dr->nr", a, lp["dkv_w"], quant)
    c = _rms(ckr[:, :d["kv_rank"]], lp["kv_norm_g"], d["eps"], quant)
    cos, sin = cos_sin(d, n)
    k_r = _rotate(ckr[:, d["kv_rank"]:], cos, sin)
    mask, selected = selection(*index_projections(a, c_q, lp, d, cos, sin, quant), d, quant)
    u = _attend(c_q, c, k_r, mask, lp, d, cos, sin, quant)
    return _act(x + u, quant), selected


def _swiglu(b, wg, wu, wd, quant):
    a = jax.nn.silu(_mm("nd,df->nf", b, wg, quant)) * _mm("nd,df->nf", b, wu, quant)
    return _mm("nf,fd->nd", _act(a, quant), wd, quant)


def route(b, router_w, router_b, d):
    """(weights [n, E] float32: 0 where not chosen, renormalised over the
    chosen and scaled; choices [n, per_token], largest biased score first)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(b @ router_w)
    u = s + router_b
    groups = u.reshape(u.shape[0], d["n_group"], -1)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)  # [n, n_group]
    kept = jax.lax.top_k(group_score, d["topk_group"])[1]
    stays = jnp.zeros(group_score.shape, bool).at[
        jnp.arange(u.shape[0])[:, None], kept].set(True)
    limited = jnp.where(jnp.repeat(stays, groups.shape[-1], axis=1), u, -jnp.inf)
    idx = jax.lax.top_k(limited, d["per_token"])[1]
    top = jnp.take_along_axis(s, idx, -1)
    top = d["routed_scale"] * top / jnp.sum(top, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, d["experts_total"], dtype=s.dtype)  # [n, k, E]
    return jnp.einsum("nk,nke->ne", top, chosen), idx


def routed_experts(b, weights, lp, d, quant=None, held=None):
    """Sum over the held experts of weight x SwiGLU, one expert at a time.
    `held = (first, count)` with `lp`'s matrices those experts': another
    chip's share (the test that adds the shares up)."""
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
    or None for a dense layer, S [n, topk])."""
    y, selected = attention_half(x, lp, d, quant)
    b = _rms(y, lp["norm_ff_g"], d["eps"], quant)
    if kind == "dense":
        f, choices = _swiglu(b, lp["gate_w"], lp["up_w"], lp["down_w"], quant), None
    else:
        weights, choices = route(b, lp["router_w"], lp["router_b"], d)
        f = shared_expert(b, lp, quant) + routed_experts(b, weights, lp, d, quant)
    return _act(y + _act(f, quant), quant), choices, selected


def _static(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items() if k != "kinds")) + (("kinds", d["kinds"]),)


@partial(jax.jit, static_argnames=("kind", "quant", "d", "start"), donate_argnums=(0,))
def _layer_row(x, lp, *, kind, d, quant, start):
    """One layer on one row x [n, dim] (donated): (x, choices or None, S),
    the last two of positions `start` on."""
    with jax.default_matmul_precision("highest"):
        y, choices, selected = layer(x, lp, kind, dict(d), quant)
    return y, None if choices is None else choices[start:], selected[start:]


def forward(cfg: dict, seed: int, tokens, start: int = 0, quant=None, params=None) -> dict:
    """The uncached forward over `tokens` [R, n], a layer at a time.

    Returns, of positions `start` on: `logits` [R, n - start, vocab] float32,
    `choices` [R, n - start, per_token] (what the FIRST routed layer's router
    chose there) and `selected` [R, depth, n - start, min(index_topk, n)]
    int32 (each layer's S_t, -1 in empty slots). `params`: `{"top": ...,
    "layers": [...]}` made already (the CPU tests); left out, each layer's
    weights are made from `seed` when it is reached and freed after."""
    d = dims(cfg)
    tokens = jnp.asarray(tokens)
    top = params["top"] if params else init_top(cfg, seed)
    rows = [top["emb"][t] for t in tokens]  # a row at a time: a layer's temporaries are per row
    first_choices, selected = None, []
    for i, kind in enumerate(d["kinds"]):
        lp = params["layers"][i] if params else init_layer(cfg, seed, i)
        out = [_layer_row(x, lp, kind=kind, d=_static(d), quant=quant, start=int(start))
               for x in rows]
        rows = [o[0] for o in out]
        selected.append(np.stack([np.asarray(o[2]) for o in out]))
        if out[0][1] is not None and first_choices is None:
            first_choices = np.stack([np.asarray(o[1]) for o in out])
        del lp, out
    with jax.default_matmul_precision("highest"):
        h = _rms(jnp.stack([x[start:] for x in rows]), top["final_norm_g"], d["eps"], quant)
        logits = _mm("rnd,dv->rnv", h, top["head_w"], quant, -1, 0)
    return {"logits": np.asarray(logits), "choices": first_choices,
            "selected": np.stack(selected, axis=1)}
