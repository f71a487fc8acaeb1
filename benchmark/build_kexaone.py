"""Seeded weights for the program's `CausalLM` in the window-and-full family
with a multi-token module: `build_lm.py`'s part for `k-exaone-236b-ep8`. The
program reads the published keys itself (`CausalLM.from_config`); what is
here is the layout table between the reference's weights
(`reference/kexaone_ref.py`) and the program's flax tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree. The weights are made as the reference makes them, ONE
LAYER AT A TIME (`kexaone_ref.init_layer`), and each layer is laid out in the
program's tree (the reference's three projections side by side as the fused
`to_qkv`) and cast leaf by leaf to what the program stores
(`program.weights_dtype`: matrices bfloat16, gains, the router and its bias
float32) before the next is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import kexaone_ref

TOP = {
    "emb": "token_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "head_w": "logits_dense/kernel",
}
MODULE = {
    "norm_e_g": "mtp_norm_e/scale",
    "norm_h_g": "mtp_norm_h/scale",
    "proj_w": "mtp_proj/kernel",
    "norm_m_g": "mtp_norm_f/scale",
}
# reference leaf -> path under the trunk ("transformer") or the module's
# block ("mtp_block"), {i} the layer there
LAYER = {
    "norm_attn_g": "attn_norms_{i}/scale",
    "norm_ff_g": "ff_norms_{i}/scale",
    "q_norm_g": "attn_{i}/q_norm/scale",
    "k_norm_g": "attn_{i}/k_norm/scale",
    "o_w": "attn_{i}/to_out/kernel",
}
FF = {
    "dense": {"gate_w": "ff_{i}/w_gate/kernel", "up_w": "ff_{i}/w_up/kernel",
              "down_w": "ff_{i}/w_out/kernel"},
    "routed": {"router_w": "ff_{i}/router", "router_b": "ff_{i}/router_bias",
               "gate_w": "ff_{i}/w_gate", "up_w": "ff_{i}/w_up", "down_w": "ff_{i}/w_out",
               "sh_gate_w": "ff_{i}/shared_gate", "sh_up_w": "ff_{i}/shared_up",
               "sh_down_w": "ff_{i}/shared_out"},
}


def _set(tree: dict, path: str, value) -> None:
    *parents, leaf = path.split("/")
    for p in parents:
        tree = tree.setdefault(p, {})
    tree[leaf] = value


def _stored(name: str, x, dtype):
    """A leaf as the program stores it: matrices in `dtype`, gains, the
    router and its bias in float32."""
    return x if name.endswith("_g") or name in kexaone_ref.FLOAT32_LEAVES else x.astype(dtype)


def layer_to_program(lp: dict, i: int, kind: str, dtype) -> dict:
    """Reference-named weights of one block -> its part of the tree that
    holds it, as layer `i` there."""
    out: dict = {}
    for name, path in {**LAYER, **FF[kind]}.items():
        _set(out, path.format(i=i), _stored(name, lp[name], dtype))
    fused = jnp.concatenate([lp["q_w"], lp["k_w"], lp["v_w"]], axis=1)
    _set(out, f"attn_{i}/to_qkv/kernel", fused.astype(dtype))
    return out


def _parts(cfg: dict):
    """(tree the block lies in, its layer there, the reference's layer, kind)."""
    d = kexaone_ref.dims(cfg)
    parts = [("transformer", i, i, kind) for i, kind in enumerate(d["kinds"])]
    return parts + [("mtp_block", 0, d["depth"], "routed")] * d["drafts"]


def to_program(ref: dict, cfg: dict, dtype) -> dict:
    """`kexaone_ref.init_params`' weights -> the program's `params` tree."""
    params: dict = {}
    tops = [(TOP, ref["top"])] + ([(MODULE, ref["module"])] if ref.get("module") else [])
    for table, leaves in tops:
        for name, path in table.items():
            _set(params, path, _stored(name, leaves[name], dtype))
    for tree, at, i, kind in _parts(cfg):
        params.setdefault(tree, {}).update(layer_to_program(ref["layers"][i], at, kind, dtype))
    return params


def seeded_variables(cfg: dict, mdl, seed: int, check: bool = True) -> dict:
    """{"params": ...} for `mdl`, made on the device from `seed` a layer at a
    time, and (unless told not to) checked against the shapes and dtypes the
    program's own init would produce."""
    dtype = mdl.param_dtype
    params: dict = {}
    cast = jax.jit(lambda t: {k: _stored(k, v, dtype) for k, v in t.items()})
    tops = [(TOP, kexaone_ref.init_top(cfg, seed))]
    if kexaone_ref.dims(cfg)["drafts"]:
        tops.append((MODULE, kexaone_ref.init_module(cfg, seed)))
    for table, leaves in tops:
        for name, leaf in cast(leaves).items():
            _set(params, table[name], leaf)
    lay = jax.jit(layer_to_program, static_argnums=(1, 2, 3))
    for tree, at, i, kind in _parts(cfg):
        params.setdefault(tree, {}).update(
            lay(kexaone_ref.init_layer(cfg, seed, i), at, kind, dtype))
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}
