"""Seeded weights for the program's `CausalLM`: `build.py`'s part for
configurations whose file holds the published `config.json` keys at its top
level. The program reads those keys itself (`CausalLM.from_config`); what is
here is the layout table between the reference's weights and the program's
tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree. Weights are made by the reference's seeded init
(`reference/mellum_ref.py:init_params`) and laid out here in the program's
flax tree (q, k and v fused into `to_qkv`); the
same table maps a program tree (parameters, Adam moments) back to the
reference's names, layers stacked, so that the two compare leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import mellum_ref

TOP = {
    "emb": "token_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "head_w": "logits_dense/kernel",
}
# reference leaf -> path under "transformer", {i} the layer
LAYERS = {
    "norm_attn_g": "attn_norms_{i}/scale",
    "q_norm_g": "attn_{i}/q_norm/scale",
    "k_norm_g": "attn_{i}/k_norm/scale",
    "o_w": "attn_{i}/to_out/kernel",
    "norm_ff_g": "ff_norms_{i}/scale",
    "router_w": "ff_{i}/router",
    "gate_w": "ff_{i}/w_gate",
    "up_w": "ff_{i}/w_up",
    "down_w": "ff_{i}/w_out",
}
QKV = "attn_{i}/to_qkv/kernel"  # q | k | v side by side, k and v of one width


def _set(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[leaf] = value


def _get(tree, path: str):
    for p in path.split("/"):
        tree = tree[p]
    return tree


def to_program(ref: dict, depth: int) -> dict:
    """Reference-named weights -> the program's `params` tree."""
    params: dict = {}
    for name, path in TOP.items():
        _set(params, path, ref[name])
    t = params.setdefault("transformer", {})
    for i in range(depth):
        for name, path in LAYERS.items():
            _set(t, path.format(i=i), ref[name][i])
        _set(t, QKV.format(i=i),
             jnp.concatenate([ref[n][i] for n in ("q_w", "k_w", "v_w")], -1))
    return params


def from_program(params: dict, depth: int) -> dict:
    """The program's `params`-shaped tree -> reference names, layers stacked."""
    out = {name: _get(params, path) for name, path in TOP.items()}
    t = params["transformer"]
    for name, path in LAYERS.items():
        out[name] = jnp.stack([_get(t, path.format(i=i)) for i in range(depth)])
    fused = jnp.stack([_get(t, QKV.format(i=i)) for i in range(depth)])
    q_width = _get(t, "attn_0/to_out/kernel").shape[0]
    kv_width = (fused.shape[-1] - q_width) // 2
    out.update(zip(("q_w", "k_w", "v_w"),
                   jnp.split(fused, [q_width, q_width + kv_width], -1)))
    return out


def seeded_variables(cfg: dict, mdl, seed: int, check: bool = True) -> dict:
    """{"params": ...} for `mdl`, made on the device from `seed`, and (unless
    the caller has had the same tree checked already) checked against the
    shapes the program's own init would produce."""
    ref = mellum_ref.init_params(cfg, seed)
    params = jax.jit(lambda r: to_program(r, mdl.depth))(ref)
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, mdl.seq_len), jnp.int32)
    )["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}


def leaf_norms_of(tree: dict, depth: int) -> dict:
    """Per-leaf (per-layer, per-expert) norms of a `params`-shaped tree, by
    reference name."""
    return mellum_ref.leaf_norms(from_program(tree, depth))
