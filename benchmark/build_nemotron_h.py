"""Seeded weights for the program's `CausalLM` in the state-space hybrid
family (`model_type: nemotron_h`): `build_pangu.py`'s part for configurations
whose file has `hybrid_override_pattern`. The program reads the published
keys itself (`CausalLM.from_config`); what is here is the layout table
between the reference's weights (`reference/nemotron_h_ref.py`) and the
program's flax tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree: a layer of one sublayer has its norm and its module
under the mixer's names (`attn_norms_{i}`, `attn_{i}`) or the feed-forward's
(`ff_norms_{i}`, `ff_{i}`), and nothing under the other's. The weights are
made as the reference makes them, ONE LAYER AT A TIME
(`nemotron_h_ref.init_layer`), and each layer is laid out in the program's
tree (the attention's three projections side by side as the fused `to_qkv`)
and cast leaf by leaf to what the program stores (`program.weights_dtype`:
matrices and the convolution's taps bfloat16; gains, the router and its bias,
the convolution's bias, A_log, D and dt_bias float32) before the next is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.build_pangu import _set
from benchmark.reference import nemotron_h_ref as ref

TOP = {
    "emb": "token_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "head_w": "logits_dense/kernel",
}
# reference leaf -> path under "transformer", {i} the layer
LAYER = {
    "ssm": {"norm_g": "attn_norms_{i}/scale", "in_w": "attn_{i}/to_in",
            "conv_w": "attn_{i}/conv", "conv_b": "attn_{i}/conv_bias",
            "a_log": "attn_{i}/A_log", "dt_bias": "attn_{i}/dt_bias", "skip_g": "attn_{i}/D",
            "gate_norm_g": "attn_{i}/norm", "out_w": "attn_{i}/to_out/kernel"},
    "attention": {"norm_g": "attn_norms_{i}/scale", "o_w": "attn_{i}/to_out/kernel"},
    "routed": {"norm_g": "ff_norms_{i}/scale", "router_w": "ff_{i}/router",
               "router_b": "ff_{i}/router_bias", "up_w": "ff_{i}/w_up", "down_w": "ff_{i}/w_out",
               "sh_up_w": "ff_{i}/shared_up", "sh_down_w": "ff_{i}/shared_out"},
}


def _stored(name: str, x, dtype):
    """A leaf as the program stores it: matrices in `dtype`; gains, the
    router, the biases, A_log, D and dt_bias in float32."""
    return x if name.endswith("_g") or name in ref.FLOAT32_LEAVES else x.astype(dtype)


def layer_to_program(lp: dict, i: int, kind: str, dtype) -> dict:
    """Reference-named weights of layer i -> their part of `params["transformer"]`."""
    out: dict = {}
    for name, path in LAYER[kind].items():
        _set(out, path.format(i=i), _stored(name, lp[name], dtype))
    if kind == "attention":
        fused = jnp.concatenate([lp["q_w"], lp["k_w"], lp["v_w"]], axis=1)
        _set(out, f"attn_{i}/to_qkv/kernel", fused.astype(dtype))
    return out


def to_program(weights: dict, cfg: dict, dtype) -> dict:
    """`nemotron_h_ref.init_params`' weights -> the program's `params` tree."""
    params: dict = {"transformer": {}}
    for name, path in TOP.items():
        _set(params, path, _stored(name, weights["top"][name], dtype))
    for i, kind in enumerate(ref.dims(cfg)["kinds"]):
        params["transformer"].update(layer_to_program(weights["layers"][i], i, kind, dtype))
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
    lay = jax.jit(layer_to_program, static_argnums=(1, 2, 3))
    for i, kind in enumerate(ref.dims(cfg)["kinds"]):
        params["transformer"].update(lay(ref.init_layer(cfg, seed, i), i, kind, dtype))
    if not check:
        return {"params": params}
    want = jax.eval_shape(
        mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if have != want:
        raise ValueError("seeded weights do not match the program's parameter tree")
    return {"params": params}
