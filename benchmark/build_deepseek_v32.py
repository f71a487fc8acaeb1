"""Seeded weights for the program's `CausalLM` in the latent-attention family
with a lightning indexer: `build_lm.py`'s part for configurations whose file
has `index_topk`. The program reads the published keys itself
(`CausalLM.from_config`); what is here is the layout table between the
reference's weights (`reference/deepseek_v32_ref.py`) and the program's flax
tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree. The share's 4.6 B parameters do not fit the chip in
float32, so the weights are made as the reference makes them, ONE LAYER AT A
TIME (`deepseek_v32_ref.init_layer`), and each layer is laid out in the
program's tree and cast leaf by leaf to what the program stores
(`program.weights_dtype`: matrices bfloat16; gains, the two biases and the
router float32) before the next is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v32_ref as ref_model

TOP = {
    "emb": "token_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "head_w": "logits_dense/kernel",
}
# reference leaf -> path under "transformer", {i} the layer
LAYER = {
    "norm_attn_g": "attn_norms_{i}/scale",
    "norm_ff_g": "ff_norms_{i}/scale",
    "dq_w": "attn_{i}/to_q_latent/kernel",
    "q_norm_g": "attn_{i}/q_norm/scale",
    "uq_w": "attn_{i}/to_q/kernel",
    "dkv_w": "attn_{i}/to_kv_latent/kernel",
    "kv_norm_g": "attn_{i}/kv_norm/scale",
    "ukv_w": "attn_{i}/to_kv",
    "o_w": "attn_{i}/to_out/kernel",
    "iq_w": "attn_{i}/index_q/kernel",
    "ik_w": "attn_{i}/index_k/kernel",
    "ik_norm_g": "attn_{i}/index_k_norm/scale",
    "ik_norm_b": "attn_{i}/index_k_norm/bias",
    "iw_w": "attn_{i}/index_w/kernel",
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
    """A leaf as the program stores it: matrices in `dtype`; gains, the two
    biases and the router in float32."""
    return x if name.endswith("_g") or name in ref_model.FLOAT32_LEAVES else x.astype(dtype)


def layer_to_program(lp: dict, i: int, kind: str, dtype) -> dict:
    """Reference-named weights of layer i -> their part of `params["transformer"]`."""
    out: dict = {}
    for name, path in {**LAYER, **FF[kind]}.items():
        _set(out, path.format(i=i), _stored(name, lp[name], dtype))
    return out


def to_program(ref: dict, cfg: dict, dtype) -> dict:
    """`deepseek_v32_ref.init_params`' weights -> the program's `params` tree."""
    params: dict = {"transformer": {}}
    for name, path in TOP.items():
        _set(params, path, _stored(name, ref["top"][name], dtype))
    for i, kind in enumerate(ref_model.dims(cfg)["kinds"]):
        params["transformer"].update(layer_to_program(ref["layers"][i], i, kind, dtype))
    return params


def seeded_variables(cfg: dict, mdl, seed: int, check: bool = True) -> dict:
    """{"params": ...} for `mdl`, made on the device from `seed` a layer at a
    time, and (unless told not to) checked against the shapes and dtypes the
    program's own init would produce."""
    dtype = mdl.param_dtype
    params: dict = {"transformer": {}}
    top = jax.jit(lambda t: {k: _stored(k, v, dtype) for k, v in t.items()})(
        ref_model.init_top(cfg, seed))
    for name, path in TOP.items():
        _set(params, path, top[name])
    lay = jax.jit(layer_to_program, static_argnums=(1, 2, 3))
    for i, kind in enumerate(ref_model.dims(cfg)["kinds"]):
        params["transformer"].update(lay(ref_model.init_layer(cfg, seed, i), i, kind, dtype))
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}
