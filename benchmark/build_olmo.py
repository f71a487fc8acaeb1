"""Seeded weights for the program's `CausalLM` in the linear-and-full family:
`build_pangu.py`'s part for configurations whose file has
`linear_key_head_dim`. The program reads the published keys itself
(`CausalLM.from_config`); what is here is the layout table between the
reference's weights (`reference/olmo_hybrid_ref.py`) and the program's flax
tree.

The only file of the benchmark that knows how the program lays out this
model's parameter tree. The weights are made as the reference makes them, ONE
LAYER AT A TIME (`olmo_hybrid_ref.init_layer`), and each layer is laid out in
the program's tree and cast leaf by leaf to what the program stores
(`program.weights_dtype`: matrices and the convolution's taps bfloat16, gains,
A_log and dt_bias float32) before the next is made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.build_pangu import _set
from benchmark.reference import olmo_hybrid_ref as ref

TOP = {
    "emb": "token_emb/embedding",
    "final_norm_g": "logits_norm/scale",
    "head_w": "logits_dense/kernel",
}
# reference leaf -> path under "transformer", {i} the layer
BLOCK = {
    "post_attn_g": "attn_norms_out_{i}/scale",
    "post_ff_g": "ff_norms_out_{i}/scale",
    "gate_w": "ff_{i}/w_gate/kernel",
    "up_w": "ff_{i}/w_up/kernel",
    "down_w": "ff_{i}/w_out/kernel",
    "o_w": "attn_{i}/to_out/kernel",
}
MIXER = {
    "linear": {"qkv_w": "attn_{i}/to_qkv", "ab_w": "attn_{i}/to_ab",
               "g_w": "attn_{i}/to_gate/kernel", "conv_w": "attn_{i}/conv",
               "a_log": "attn_{i}/A_log", "dt_bias": "attn_{i}/dt_bias",
               "o_norm_g": "attn_{i}/o_norm"},
    "full": {"qkv_w": "attn_{i}/to_qkv/kernel", "q_norm_g": "attn_{i}/q_norm/scale",
             "k_norm_g": "attn_{i}/k_norm/scale"},
}


def _stored(name: str, x, dtype):
    """A leaf as the program stores it: matrices in `dtype`, gains, A_log and
    dt_bias in float32."""
    return x if name.endswith("_g") or name in ref.FLOAT32_LEAVES else x.astype(dtype)


def layer_to_program(lp: dict, i: int, kind: str, dtype) -> dict:
    """Reference-named weights of layer i -> their part of `params["transformer"]`."""
    out: dict = {}
    for name, path in {**BLOCK, **MIXER[kind]}.items():
        _set(out, path.format(i=i), _stored(name, lp[name], dtype))
    return out


def to_program(weights: dict, cfg: dict, dtype) -> dict:
    """`olmo_hybrid_ref.init_params`' weights -> the program's `params` tree."""
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
