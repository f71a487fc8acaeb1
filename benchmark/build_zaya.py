"""Seeded weights for the program's `CausalLM` in the convolved-latent family
(`model_type: zaya`): `build_pangu.py`'s part for configurations whose file has
`cca_time0`. The program reads the published keys itself
(`CausalLM.from_config`); what is here is the layout table between the
reference's weights (`reference/zaya_ref.py`) and the program's flax tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree: the attention's three projections side by side as the
fused `to_qkv`, the router's MLP under the routed layer's `router_*` names, the
residual's four vectors a sublayer as `attn_res_{i}/vectors` and
`ff_res_{i}/vectors` of the trunk, and NO head (it is the embedding). The weights are made as the reference
makes them, ONE LAYER AT A TIME (`zaya_ref.init_layer`), and each layer is laid
out in the program's tree and cast leaf by leaf to what the program stores
(`program.weights_dtype`: matrices and both convolutions' taps bfloat16; gains,
tau, the biases, the residual's vectors and the whole router float32) before
the next is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.build_pangu import _set
from benchmark.reference import zaya_ref as ref

TOP = {"emb": "token_emb/embedding", "final_norm_g": "logits_norm/scale"}
# reference leaf -> path under "transformer", {i} the layer
LAYER = {
    "attn_norm_g": "attn_norms_{i}/scale", "conv0_w": "attn_{i}/conv0",
    "conv0_b": "attn_{i}/conv0_bias", "conv1_w": "attn_{i}/conv1",
    "conv1_b": "attn_{i}/conv1_bias", "tau_g": "attn_{i}/tau", "o_w": "attn_{i}/to_out/kernel",
    "attn_res": "attn_res_{i}/vectors", "ff_norm_g": "ff_norms_{i}/scale",
    "rd_w": "ff_{i}/router_down", "rd_b": "ff_{i}/router_down_bias",
    "gamma": "ff_{i}/router_gamma", "rnorm_g": "ff_{i}/router_norm",
    "r1_w": "ff_{i}/router_1", "r1_b": "ff_{i}/router_1_bias", "r2_w": "ff_{i}/router_2",
    "r2_b": "ff_{i}/router_2_bias", "r3_w": "ff_{i}/router_out", "beta": "ff_{i}/router_bias",
    "gate_w": "ff_{i}/w_gate", "up_w": "ff_{i}/w_up", "down_w": "ff_{i}/w_out",
    "ff_res": "ff_res_{i}/vectors",
}


def _stored(name: str, x, dtype):
    """A leaf as the program stores it: matrices in `dtype`; gains, tau, the
    biases, the residual's vectors and the router in float32."""
    return x if name.endswith("_g") or name in ref.FLOAT32_LEAVES else x.astype(dtype)


def layer_to_program(lp: dict, i: int, dtype) -> dict:
    """Reference-named weights of layer i -> their part of `params["transformer"]`."""
    out: dict = {}
    for name, path in LAYER.items():
        _set(out, path.format(i=i), _stored(name, lp[name], dtype))
    fused = jnp.concatenate([lp["q_w"], lp["k_w"], lp["v_w"]], axis=1)
    _set(out, f"attn_{i}/to_qkv/kernel", fused.astype(dtype))
    return out


def to_program(weights: dict, cfg: dict, dtype) -> dict:
    """`zaya_ref.init_params`' weights -> the program's `params` tree."""
    params: dict = {"transformer": {}}
    for name, path in TOP.items():
        _set(params, path, _stored(name, weights["top"][name], dtype))
    for i, lp in enumerate(weights["layers"]):
        params["transformer"].update(layer_to_program(lp, i, dtype))
    return params


def seeded_variables(cfg: dict, mdl, seed: int, check: bool = True) -> dict:
    """{"params": ...} for `mdl`, made on the device from `seed` a layer at a
    time, and (unless told not to) checked against the shapes and dtypes the
    program's own init would produce."""
    dtype = mdl.param_dtype
    params: dict = {"transformer": {}}
    top = jax.jit(lambda t: {k: _stored(k, v, dtype) for k, v in t.items()})(
        ref.init_top(cfg, seed))
    for name, path in TOP.items():
        _set(params, path, top[name])
    lay = jax.jit(layer_to_program, static_argnums=(1, 2))
    for i in range(ref.dims(cfg)["depth"]):
        params["transformer"].update(lay(ref.init_layer(cfg, seed, i), i, dtype))
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}
