"""From a configuration file to the program's model and its seeded weights.

The only file of the benchmark that knows how the program lays out its
parameter tree. Weights are made by the reference's seeded init
(`reference/dalle_ref.py:init_params`, one jitted call on the device) and
laid out here in the program's flax tree, for either layer executor; the
same table maps a program tree (parameters, Adam moments) back to the
reference's names so that the two can be compared leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import dalle_ref

# keys of a configuration's "model" group that the program's DALLE takes as
# they are; the others are read by the reference and pinned below
PROGRAM_KEYS = (
    "dim", "depth", "heads", "dim_head", "text_seq_len", "num_text_tokens",
    "num_image_tokens", "image_fmap_size", "shift_tokens", "rotary_emb",
    "loss_img_weight", "attn_impl", "executor",
)
# what the program hard-wires and a configuration file therefore has to state
PINNED = {"ff_mult": 4, "layernorm_eps": 1e-6}
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# reference leaf -> (unrolled path with {i}, scan path); "/"-separated
TOP = {
    "text_emb": "text_emb/embedding",
    "image_emb": "image_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "final_norm_b": "logits_norm/bias",
    "head_w": "logits_dense/kernel",
    "head_b": "logits_dense/bias",
}
LAYERS = {
    "norm_attn_g": ("attn_norms_{i}/scale", "scan_stack/layers/norm_attn/scale"),
    "norm_attn_b": ("attn_norms_{i}/bias", "scan_stack/layers/norm_attn/bias"),
    "qkv_w": ("attn_{i}/to_qkv/kernel", "scan_stack/layers/attn/to_qkv/kernel"),
    "out_w": ("attn_{i}/to_out/kernel", "scan_stack/layers/attn/to_out/kernel"),
    "out_b": ("attn_{i}/to_out/bias", "scan_stack/layers/attn/to_out/bias"),
    "attn_scale": ("attn_scale_{i}", "attn_scale_stack"),
    "norm_ff_g": ("ff_norms_{i}/scale", "scan_stack/layers/norm_ff/scale"),
    "norm_ff_b": ("ff_norms_{i}/bias", "scan_stack/layers/norm_ff/bias"),
    "ff1_w": ("ff_{i}/Dense_0/kernel", "scan_stack/layers/ff/Dense_0/kernel"),
    "ff1_b": ("ff_{i}/Dense_0/bias", "scan_stack/layers/ff/Dense_0/bias"),
    "ff2_w": ("ff_{i}/Dense_1/kernel", "scan_stack/layers/ff/Dense_1/kernel"),
    "ff2_b": ("ff_{i}/Dense_1/bias", "scan_stack/layers/ff/Dense_1/bias"),
    "ff_scale": ("ff_scale_{i}", "ff_scale_stack"),
}
SCALES = ("attn_scale", "ff_scale")  # [D] here, [1, 1, D] in the program


def model(cfg: dict, **overrides):
    """The program's DALLE for a configuration file (+ a job's overrides)."""
    from dalle_pytorch_tpu.models.dalle import DALLE

    m = dict(cfg["model"], **overrides)
    for key, value in dict(PINNED, rotary_angle_dtype=m["dtype"]).items():
        if m[key] != value:
            raise ValueError(f"the program hard-wires {key}={value}, file says {m[key]}")
    kwargs = {k: m[k] for k in PROGRAM_KEYS}
    for k in ("reversible", "reversible_impl", "fused_ce", "remat_policy"):
        if k in m:
            kwargs[k] = m[k]
    types = tuple(m.get("attn_types") or ("full",))
    kwargs["attn_types"] = None if types == ("full",) else types
    return DALLE(dtype=DTYPES[m["dtype"]], **kwargs)


def _set(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[leaf] = value


def _get(tree, path: str):
    for p in path.split("/"):
        tree = tree[p]
    return tree


def to_program(ref: dict, executor: str, depth: int) -> dict:
    """Reference-named weights -> the program's `params` tree."""
    params: dict = {}
    for name, path in TOP.items():
        _set(params, path, ref[name])
    t = params.setdefault("transformer", {})
    for name, (unrolled, scan) in LAYERS.items():
        x = ref[name]
        if name in SCALES:
            x = x[:, None, None, :]
        if executor == "scan":
            _set(t, scan, x)
        else:
            for i in range(depth):
                _set(t, unrolled.format(i=i), x[i])
    return params


def from_program(params: dict, executor: str, depth: int) -> dict:
    """The program's `params`-shaped tree -> reference names, layers stacked."""
    out = {name: _get(params, path) for name, path in TOP.items()}
    t = params["transformer"]
    for name, (unrolled, scan) in LAYERS.items():
        if executor == "scan":
            x = _get(t, scan)
        else:
            x = jnp.stack([_get(t, unrolled.format(i=i)) for i in range(depth)])
        out[name] = x.reshape(x.shape[0], -1) if name in SCALES else x
    return out


def seeded_variables(cfg: dict, mdl, seed: int, check: bool = True) -> dict:
    """{"params": ...} for `mdl`, made on the device from `seed`, and (unless
    the caller has had the same tree checked already) checked against the
    shapes the program's own init would produce."""
    ref = dalle_ref.init_params(cfg, seed)
    params = jax.jit(lambda r: to_program(r, mdl.executor, mdl.depth))(ref)
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0),
        jnp.zeros((1, mdl.text_seq_len), jnp.int32),
        jnp.zeros((1, mdl.image_seq_len), jnp.int32),
    )["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}


def seeded_vae(cfg: dict, seed: int):
    """(the dVAE that decodes pixels, its parameters from the program's own
    init): the reference never sees pixels, only tokens."""
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE

    vae = DiscreteVAE(**cfg["vae"])
    size = cfg["vae"]["image_size"]
    params = jax.jit(vae.init)(
        jax.random.PRNGKey((seed + 1) % (2**31 - 1)), jnp.zeros((1, size, size, 3))
    )["params"]
    return vae, params


def leaf_norms_of(tree: dict, executor: str, depth: int) -> dict:
    """Per-leaf (per-layer) norms of a `params`-shaped tree, by reference name."""
    return dalle_ref.leaf_norms(from_program(tree, executor, depth))
