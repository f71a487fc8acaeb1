"""Plain float32 reference of the DALL-E transformer the two configurations use.

Straightforward `jax.numpy`: no kernels, no cache, no batching tricks, and
nothing imported from the program. It follows lucidrains/DALLE-pytorch
(`dalle_pytorch.py`, `transformer.py`, `attention.py`) for exactly what the
benchmark's configurations switch on:

  * embeddings with the unique-pad text ids (pad id 0 at text position p
    becomes id `num_text_tokens + p`) and a <bos> of id 0 in front;
  * pre-norm layers with LayerScale, token shift before attention and
    before the feed-forward, GEGLU feed-forward;
  * causal attention with the dual rotary embedding (1-D text + 2-D axial
    pixel, applied to q, k AND v as upstream does) and the static patterns
    `full`, `axial_row`, `axial_col`, `conv_like`, re-derived here from
    their definition;
  * the final norm, the head, and the logits-range mask (text positions
    emit text ids only, image positions image ids only);
  * the forward objective (text CE + 7 x image CE) / 8, its gradient, and
    the optimizer the trainer builds: clip by global norm, then Adam.

Departures from upstream, all because the program states them and the
benchmark measures the program: LayerNorm epsilon 1e-6 and the tanh GELU
(flax defaults; torch has 1e-5 and erf), and `rotary_angle_dtype`: the
program stores its rotation ANGLES in the model's dtype before taking their
cosine and sine, so a bf16 model turns by angles rounded to 8 bits (position
201 x frequency 0.3 is 60.5 there, not 60.3). That is not rounding noise but
a different, self-consistent positional code, which a checkpoint trained in
bf16 has learned; the reference turns by the same stored angles (and takes
cosine and sine of them in float32). All three are named in the
configuration file. With exact angles the bf16 program and this reference
disagree as much as an fp8 model does (PERF.md, PR 23).

Weights are made here, from a seed, in this file's own naming (layer leaves
stacked over depth); `benchmark/build.py` lays the same arrays out in the
program's tree. Everything runs under `jax.default_matmul_precision
("highest")`: on a TPU a float32 matmul is otherwise done in bf16 passes.

`quant` switches on the CONTROL used to set the limits of `correct` (never
in a benchmark run): the same model computed in "int8" or "fp8" (e4m3), the
precisions below the configurations' bf16, as the program computes in bf16:
every matmul operand, every layer-norm output and the residual stream after
every addition are rounded (per row, scaled to the format's range), and so
are their gradients on the way back. Rounding the forward values alone is NOT
a control: on the chip it lands closer to float32 than the bf16 program does
(PERF.md, PR 23), because most of a bf16 step's error is made in its backward
pass.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -float(np.finfo(np.float32).max)
PATTERNS = ("full", "axial_row", "axial_col", "conv_like")


# ------------------------------------------------------------ configuration


def dims(cfg: dict) -> dict:
    """Derived sizes of a configuration file's `model` group."""
    m = cfg["model"]
    fmap = m["image_fmap_size"]
    t = m["text_seq_len"]
    return dict(
        dim=m["dim"], depth=m["depth"], heads=m["heads"], dim_head=m["dim_head"],
        inner=m["heads"] * m["dim_head"], ff_hidden=int(m["dim"] * m["ff_mult"]),
        text_seq=t, fmap=fmap, image_seq=fmap * fmap, seq=t + fmap * fmap,
        text_len=t + 1,  # with <bos>
        text_vocab=m["num_text_tokens"] + t, image_vocab=m["num_image_tokens"],
        vocab=m["num_text_tokens"] + t + m["num_image_tokens"],
        base_text_vocab=m["num_text_tokens"],
        attn_types=tuple(m.get("attn_types") or ("full",)),
        shift=bool(m["shift_tokens"]), rotary=bool(m["rotary_emb"]),
        loss_img_weight=float(m["loss_img_weight"]),
        ln_eps=float(m["layernorm_eps"]),
        angle_dtype=m["rotary_angle_dtype"],
    )


def param_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    L, D, I, F, V = d["depth"], d["dim"], d["inner"], d["ff_hidden"], d["vocab"]
    return {
        "text_emb": (d["text_vocab"], D), "image_emb": (d["image_vocab"], D),
        "norm_attn_g": (L, D), "norm_attn_b": (L, D),
        "qkv_w": (L, D, 3 * I), "out_w": (L, I, D), "out_b": (L, D),
        "attn_scale": (L, D),
        "norm_ff_g": (L, D), "norm_ff_b": (L, D),
        "ff1_w": (L, D, 2 * F), "ff1_b": (L, 2 * F),
        "ff2_w": (L, F, D), "ff2_b": (L, D), "ff_scale": (L, D),
        "final_norm_g": (D,), "final_norm_b": (D,),
        "head_w": (D, V), "head_b": (V,),
    }


LAYER_LEAVES = (
    "norm_attn_g", "norm_attn_b", "qkv_w", "out_w", "out_b", "attn_scale",
    "norm_ff_g", "norm_ff_b", "ff1_w", "ff1_b", "ff2_w", "ff2_b", "ff_scale",
)


def init_params(cfg: dict, seed: int) -> dict:
    """Seeded float32 weights, one jitted call, made on the device.

    Matrices are normal with std 1/sqrt(fan_in), embeddings 1/sqrt(dim)
    (what the program's own init draws); norm gains 1 +- 10%, biases normal
    0.02, LayerScale `weights.layerscale` +- 10% in every layer, so that no
    leaf is zero and every layer weighs on the logits.
    """
    shapes = param_shapes(cfg)
    layerscale = float(cfg["weights"]["layerscale"])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("_w"):
                out[name] = z / math.sqrt(shape[-2])
            elif name.endswith("_emb"):
                out[name] = z / math.sqrt(shape[-1])
            elif name.endswith("_g"):
                out[name] = 1.0 + 0.1 * z
            elif name.endswith("_scale"):
                out[name] = layerscale * (1.0 + 0.1 * z)
            else:
                out[name] = 0.02 * z
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2**31 - 1)))


# ------------------------------------------------------- static tables


def rotary_table(d: dict) -> np.ndarray:
    """[text_len + image_seq, 3 * 2 * (rot // 2)] rotation angles.

    Three blocks per head (upstream `transformer.py`, rotary section): a 1-D
    rotary over text positions with every image position at 8192; then row
    and column rotaries over the image grid with coordinates in
    linspace(-1, 1) and every text position at -10. rot = dim_head // 3;
    adjacent channel pairs share an angle.
    """
    rot = d["dim_head"] // 3
    half = rot // 2
    lang = 1.0 / (10000.0 ** (np.arange(0, rot, 2)[:half] / rot))
    pixel = np.linspace(1.0, 10.0 / 2.0, half) * np.pi
    fmap, tl = d["fmap"], d["text_len"]
    text_pos = np.concatenate([np.arange(tl), np.full(d["image_seq"], 8192.0)])
    grid = np.linspace(-1.0, 1.0, fmap)
    rows = np.concatenate([np.full(tl, -10.0), np.repeat(grid, fmap)])
    cols = np.concatenate([np.full(tl, -10.0), np.tile(grid, fmap)])
    blocks = [np.outer(text_pos, lang), np.outer(rows, pixel), np.outer(cols, pixel)]
    table = np.concatenate([np.repeat(b, 2, axis=-1) for b in blocks], axis=-1)
    if d["angle_dtype"] == "bfloat16":  # as the program stores them
        import ml_dtypes

        table = table.astype(np.float32).astype(ml_dtypes.bfloat16)
    elif d["angle_dtype"] != "float32":
        raise ValueError(f"unknown rotary_angle_dtype {d['angle_dtype']!r}")
    return table.astype(np.float32)


def pattern_mask(kind: str, d: dict) -> np.ndarray:
    """[seq, seq] bool, True = may attend, causal included.

    `axial_row` / `axial_col`: everything sees all text; an image position
    also sees its own grid row / column. `conv_like`: text sees text; an
    image position (r, c) sees all text and the 5 x 5 window whose lower
    right corner is (r, c). All under the causal triangle.
    """
    n, tl, fmap = d["text_len"] + d["image_seq"], d["text_len"], d["fmap"]
    causal = np.tril(np.ones((n, n), bool))
    if kind == "full":
        m = causal
    else:
        m = np.zeros((n, n), bool)
        r, c = np.divmod(np.arange(d["image_seq"]), fmap)
        if kind == "axial_row":
            m[:, :tl] = True
            m[tl:, tl:] = r[:, None] == r[None, :]
        elif kind == "axial_col":
            m[:, :tl] = True
            m[tl:, tl:] = c[:, None] == c[None, :]
        elif kind == "conv_like":
            m[:tl, :tl] = True
            m[tl:, :tl] = True
            dr, dc = r[:, None] - r[None, :], c[:, None] - c[None, :]
            m[tl:, tl:] = (dr >= 0) & (dr <= 4) & (dc >= 0) & (dc <= 4)
        else:
            raise ValueError(f"unknown attention pattern {kind!r}")
        m &= causal
    return m[: d["seq"], : d["seq"]]


def layer_patterns(d: dict):
    """(table [K, seq, seq] of the distinct masks, index [depth])."""
    kinds = [d["attn_types"][i % len(d["attn_types"])] for i in range(d["depth"])]
    uniq = sorted(set(kinds), key=kinds.index)
    table = np.stack([pattern_mask(k, d) for k in uniq])
    return table, np.array([uniq.index(k) for k in kinds], np.int32)


# ------------------------------------------------------------ the forward


def _round(x, axis, kind):
    """Each slice along `axis` rounded to int8 (127 levels of its largest), to
    fp8 e4m3 (its largest at 448) or to bf16."""
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12)
    if kind == "int8":
        return jnp.round(x / top * 127.0) * (top / 127.0)
    if kind == "fp8":
        return (x / top * 448.0).astype(jnp.float8_e4m3fn).astype(x.dtype) * (top / 448.0)
    if kind == "bf16":  # not a control: how far bf16 alone moves a number
        return x.astype(jnp.bfloat16).astype(x.dtype)
    raise ValueError(f"unknown control precision {kind!r}")


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _fake_low(x, axis, kind):
    """A tensor as the lower precision stores it, forward AND backward: the
    value is rounded on the way up and its gradient on the way down, as a
    model computed in that precision rounds both."""
    return _round(x, axis, kind)


def _fake_low_fwd(x, axis, kind):
    return _round(x, axis, kind), None


def _fake_low_bwd(axis, kind, _, g):
    return (_round(g, axis, kind),)


_fake_low.defvjp(_fake_low_fwd, _fake_low_bwd)


def _mm(spec, a, b, quant, a_axis=-1, b_axis=0):
    if quant:
        a, b = _fake_low(a, a_axis, quant), _fake_low(b, b_axis, quant)
    return jnp.einsum(spec, a, b)


def _act(x, quant):
    """An activation as the model's precision stores it."""
    return _fake_low(x, -1, quant) if quant else x


def _layer_norm(x, g, b, eps, quant=None):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return _act((x - mu) / jnp.sqrt(var + eps) * g + b, quant)


def _shift(x, d):
    """Token shift (upstream `PreShiftToken`): text positions take the
    first half of their channels from the token before; image positions the
    first quarter from the token one grid row up and the second quarter
    from the token one column left. Missing neighbours are zero."""
    b, n, dim = x.shape
    tl, fmap = d["text_len"], d["fmap"]
    half, q = dim // 2, dim // 4
    text, img = x[:, :tl], x[:, tl:]
    prev = jnp.pad(text[:, :-1, :half], ((0, 0), (1, 0), (0, 0)))
    text = jnp.concatenate([prev, text[..., half:]], -1)
    n_img = img.shape[1]
    img = jnp.pad(img, ((0, 0), (0, d["image_seq"] - n_img), (0, 0)))
    img = img.reshape(b, fmap, fmap, dim)
    up = jnp.pad(img[:, :-1, :, :q], ((0, 0), (1, 0), (0, 0), (0, 0)))
    left = jnp.pad(img[:, :, :-1, q : 2 * q], ((0, 0), (0, 0), (1, 0), (0, 0)))
    img = jnp.concatenate([up, left, img[..., 2 * q :]], -1)
    img = img.reshape(b, d["image_seq"], dim)[:, :n_img]
    return jnp.concatenate([text, img], 1)


def _rotate(t, angles):
    """Rotate the first `angles.shape[-1]` channels of t in adjacent pairs."""
    dr = angles.shape[-1]
    r, rest = t[..., :dr], t[..., dr:]
    pairs = r.reshape(*r.shape[:-1], -1, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(r.shape)
    return jnp.concatenate([r * jnp.cos(angles) + turned * jnp.sin(angles), rest], -1)


def _layer(x, lp, mask, angles, d, quant):
    b, n, _ = x.shape
    h, dh = d["heads"], d["dim_head"]
    y = _layer_norm(x, lp["norm_attn_g"], lp["norm_attn_b"], d["ln_eps"], quant)
    if d["shift"]:
        y = _shift(y, d)
    qkv = _mm("bnd,de->bne", y, lp["qkv_w"], quant)
    q, k, v = (
        t.reshape(b, n, h, dh).transpose(0, 2, 1, 3) for t in jnp.split(qkv, 3, -1)
    )
    if angles is not None:
        q, k, v = (_rotate(t, angles[:n]) for t in (q, k, v))
    s = _mm("bhid,bhjd->bhij", q * dh**-0.5, k, quant, -1, -1)
    s = jnp.where(mask[:n, :n], s, NEG)
    p = jax.nn.softmax(s, -1)
    o = _mm("bhij,bhjd->bhid", p, v, quant, -1, -2)
    o = o.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
    o = _mm("bne,ed->bnd", o, lp["out_w"], quant) + lp["out_b"]
    x = _act(x + o * lp["attn_scale"], quant)

    y = _layer_norm(x, lp["norm_ff_g"], lp["norm_ff_b"], d["ln_eps"], quant)
    if d["shift"]:
        y = _shift(y, d)
    y = _mm("bnd,df->bnf", y, lp["ff1_w"], quant) + lp["ff1_b"]
    a, gates = jnp.split(y, 2, -1)
    y = a * jax.nn.gelu(gates, approximate=True)
    y = _mm("bnf,fd->bnd", y, lp["ff2_w"], quant) + lp["ff2_b"]
    return _act(x + y * lp["ff_scale"], quant)


def text_ids(text, d):
    """Unique-pad remap and <bos>: [B, T] -> [B, T + 1]."""
    pad_ids = d["base_text_vocab"] + jnp.arange(d["text_seq"])
    return jnp.pad(jnp.where(text == 0, pad_ids, text), ((0, 0), (1, 0)))


def logits_fn(params, cfg, text, image, quant=False, remat=False):
    """Masked float32 logits [B, n, vocab] for text [B, T] and image ids
    [B, <= image_seq]; position i predicts token i + 1 of <bos>+text+image."""
    d = dims(cfg)
    ids = text_ids(text, d)
    x = params["text_emb"][ids]
    if image is not None and image.shape[1] > 0:
        x = jnp.concatenate([x, params["image_emb"][image]], 1)
    x = x[:, : d["seq"]]
    n = x.shape[1]
    table, index = layer_patterns(d)
    table = jnp.asarray(table)
    angles = jnp.asarray(rotary_table(d)) if d["rotary"] else None

    def body(x, scanned):
        lp, idx = scanned
        return _layer(x, lp, table[idx], angles, d, quant), None

    if remat:
        body = jax.checkpoint(body)
    stacked = {k: params[k] for k in LAYER_LEAVES}
    x, _ = jax.lax.scan(body, x, (stacked, jnp.asarray(index)))
    x = _layer_norm(x, params["final_norm_g"], params["final_norm_b"], d["ln_eps"], quant)
    logits = _mm("bnd,dv->bnv", x, params["head_w"], quant) + params["head_b"]
    text_row = (jnp.arange(n) < d["text_seq"])[:, None]
    text_col = (jnp.arange(d["vocab"]) < d["text_vocab"])[None, :]
    return jnp.where(text_row == text_col, logits, NEG)


def _ce(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def loss_fn(params, cfg, text, image, quant=False, remat=True):
    """The forward objective: (CE over text + w x CE over image) / (1 + w)."""
    d = dims(cfg)
    logits = logits_fn(params, cfg, text, image, quant=quant, remat=remat)
    labels = jnp.concatenate([text_ids(text, d)[:, 1:], image + d["text_vocab"]], 1)
    t = d["text_seq"]
    w = d["loss_img_weight"]
    return (_ce(logits[:, :t], labels[:, :t]) + w * _ce(logits[:, t:], labels[:, t:])) / (
        1.0 + w
    )


# -------------------------------------------------------------- training


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree)))


def small_leaves(tree: dict) -> dict:
    """The vector leaves (gains, biases, LayerScale): small enough to keep
    whole, so that a gradient can also be compared by its difference."""
    return {k: v for k, v in tree.items() if v.ndim <= (2 if k in LAYER_LEAVES else 1)}


def leaf_norms(tree: dict) -> dict:
    """Norm of each leaf; of each layer's slice for the stacked leaves."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name in LAYER_LEAVES:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def train_steps(cfg, params, batches, opt, rows_per_block, quant=False):
    """Follow the trainer through `len(batches)` optimizer steps.

    `batches`: list of (text [B, T], image [B, N]) int arrays. Gradients
    are taken in blocks of `rows_per_block` rows (every row weighs the same
    in both CE means, so the mean of the blocks' gradients is the batch's).
    Returns the loss of each step, the per-leaf norms of the FIRST step's
    gradient as Adam gets it (after the clip) with its vector leaves whole,
    and the per-leaf norms of the parameters' change over all steps.
    """
    lr, clip = float(opt["learning_rate"]), float(opt["clip_grad_norm"])
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])

    @jax.jit
    def block_grad(p, text, image):
        return jax.value_and_grad(loss_fn)(p, cfg, text, image, quant=quant)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(p, mu, nu, g, step):
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / _global_norm(g)), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1**step, 1 - b2**step
        p = jax.tree.map(
            lambda w, m, v: w - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), p, mu, nu
        )
        return p, mu, nu, leaf_norms(g), small_leaves(g)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    start = params
    p = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for i, (text, image) in enumerate(batches):
            n_blocks = text.shape[0] // rows_per_block
            total, loss = None, 0.0
            for j in range(n_blocks):
                rows = slice(j * rows_per_block, (j + 1) * rows_per_block)
                l, g = block_grad(p, jnp.asarray(text[rows]), jnp.asarray(image[rows]))
                loss += float(l) / n_blocks
                total = g if total is None else add(total, g)
            total = jax.tree.map(lambda x: x / n_blocks, total)
            p, mu, nu, gn, gs = apply(p, mu, nu, total, float(i + 1))
            losses.append(loss)
            if i == 0:
                first_grad, first_small = jax.device_get((gn, gs))
    change = jax.device_get(
        leaf_norms(jax.tree.map(lambda a, b: a - b, p, start))
    )
    return {"losses": losses, "grad_norms": first_grad, "grad_small": first_small,
            "change_norms": change}


# --------------------------------------------------------------- serving


def greedy_gaps(cfg, params, text, image, quant=False):
    """For each image position of each row: how far the served token's
    reference logit lies below the reference's best (0 = the reference
    would have served the same token).

    text [B, T], image [B, N]: prompts with the tokens that were served.
    With `quant` (the control: "int8" or "fp8") the "served" tokens are those the int8
    forward puts first at each position of the same inputs, and the gap is
    still read from the float32 logits. Returns gaps [B, N] float32.
    """
    d = dims(cfg)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(partial(logits_fn, cfg=cfg))(params, text=text, image=image)
        ref = ref[:, d["text_seq"] :, d["text_vocab"] :]
        if quant:
            low = jax.jit(partial(logits_fn, cfg=cfg, quant=quant))(
                params, text=text, image=image
            )
            served = jnp.argmax(low[:, d["text_seq"] :, d["text_vocab"] :], -1)
        else:
            served = image
        got = jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
        return np.asarray(jnp.max(ref, -1) - got)
