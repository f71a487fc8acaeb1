"""GPipe pipeline-parallel engine: numerical parity with sequential
execution on the virtual 8-device CPU mesh (conftest forces
--xla_force_host_platform_device_count=8).

The oracle is the same depth-stacked lax.scan the scan executor runs;
the engine must reproduce it bitwise-close through the full
M + P - 1-tick schedule, forward AND gradients (autodiff through
ppermute runs the backward pipeline in reverse automatically).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from dalle_pytorch_tpu.parallel.gpipe import (
    gpipe_apply,
    make_pp_mesh,
    stage_params_sharding,
)

DEPTH, DIM, BATCH, SEQ = 8, 16, 8, 4


def _params(key):
    k1, k2 = jax.random.split(key)
    scale = 1.0 / np.sqrt(DIM)
    return {
        "w1": jax.random.normal(k1, (DEPTH, DIM, 2 * DIM)) * scale,
        "w2": jax.random.normal(k2, (DEPTH, 2 * DIM, DIM)) * scale,
    }


def _layer(lp, x):
    # residual MLP block: order-sensitive (non-commuting layers), so any
    # schedule mistake that reorders or drops a stage shows up
    return x + jnp.tanh(x @ lp["w1"]) @ lp["w2"]


def _sequential(params, x):
    def body(h, lp):
        return _layer(lp, h), None

    out, _ = lax.scan(body, x, params)
    return out


@pytest.mark.parametrize("pp,n_micro", [(2, 4), (4, 2), (8, 4), (4, 8)])
def test_forward_matches_sequential(pp, n_micro):
    params = _params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, DIM))
    want = _sequential(params, x)
    mesh = make_pp_mesh(pp)
    got = jax.jit(
        lambda p, x: gpipe_apply(mesh, p, _layer, x, n_micro)
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_grads_match_sequential():
    params = _params(jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (BATCH, SEQ, DIM))
    mesh = make_pp_mesh(4)

    def loss_seq(p, x):
        return (_sequential(p, x) ** 2).mean()

    def loss_pp(p, x):
        return (gpipe_apply(mesh, p, _layer, x, 4) ** 2).mean()

    g_seq = jax.grad(loss_seq)(params, x)
    g_pp = jax.jit(jax.grad(loss_pp))(params, x)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g_pp, g_seq,
    )


@pytest.mark.parametrize(
    "rotary,attn_types",
    [(False, None), (True, None),
     (True, ("full", "axial_row", "axial_col", "conv_like"))],
)
def test_pipelines_real_transformer_trunk(rotary, attn_types):
    """pipeline_trunk_apply runs the PRODUCTION trunk: a scan-executor
    Transformer's own param tree (the checkpoint layout) pipelined over
    4 stages must reproduce transformer.apply — with token-shift,
    dual-rotary embeddings, and the reference's sparse attn-type cycle
    (per-layer pattern indices ride with each stage's layer slice)."""
    from dalle_pytorch_tpu.models.transformer import (
        Transformer,
        pipeline_trunk_apply,
    )

    dim, depth, heads, dim_head, fmap = 32, 4, 2, 16, 4
    seq_len = 24  # text 9 + image 16, minus the shifted-in bos slot
    tr = Transformer(
        dim=dim, depth=depth, heads=heads, dim_head=dim_head,
        seq_len=seq_len, causal=True, image_fmap_size=fmap,
        shift_tokens=True, rotary_emb=rotary, attn_impl="dense",
        attn_types=attn_types, executor="scan",
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, seq_len, dim))
    params = tr.init(jax.random.PRNGKey(1), x)["params"]
    want = tr.apply({"params": params}, x)

    got = jax.jit(
        lambda p, x: pipeline_trunk_apply(tr, p, make_pp_mesh(4), x, 2)
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    # per-example key-padding mask rides the microbatch schedule (aux)
    mask = jnp.arange(seq_len)[None, :] < jnp.arange(
        seq_len - BATCH, seq_len
    )[:, None]
    want_m = tr.apply({"params": params}, x, key_mask=mask)
    got_m = jax.jit(
        lambda p, x, m: pipeline_trunk_apply(
            tr, p, make_pp_mesh(4), x, 2, key_mask=m
        )
    )(params, x, mask)
    np.testing.assert_allclose(
        np.asarray(got_m), np.asarray(want_m), atol=1e-5
    )


@pytest.mark.slow  # ~21 s: remat + bf16 variants re-compile the pipelined
# trunk twice (tier-1 budget)
def test_trunk_remat_and_bf16():
    """Deployment settings: (a) reversible=True + remat policy — the
    pipelined trunk wraps layers in jax.checkpoint, values and grads
    unchanged; (b) bf16 compute dtype — pipelined forward matches the
    module at bf16 tolerance."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.transformer import (
        Transformer,
        pipeline_trunk_apply,
    )

    kw = dict(
        dim=32, depth=4, heads=2, dim_head=16, seq_len=24, causal=True,
        image_fmap_size=4, shift_tokens=True, rotary_emb=True,
        attn_impl="dense", executor="scan",
    )
    mesh = make_pp_mesh(4)

    # (a) remat parity incl. grads
    tr = Transformer(
        reversible=True,
        remat_policy="dots_with_no_batch_dims_saveable", **kw,
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, 24, 32))
    params = tr.init(jax.random.PRNGKey(1), x)["params"]

    def loss_mod(p):
        return (tr.apply({"params": p}, x) ** 2).mean()

    def loss_pp(p):
        return (pipeline_trunk_apply(tr, p, mesh, x, 2) ** 2).mean()

    l_mod, g_mod = jax.value_and_grad(loss_mod)(params)
    l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(params)
    np.testing.assert_allclose(float(l_pp), float(l_mod), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        g_pp, g_mod,
    )

    # (b) bf16 forward parity
    tr16 = Transformer(dtype=jnp.bfloat16, **kw)
    p16 = tr16.init(jax.random.PRNGKey(2), x)["params"]
    want = tr16.apply({"params": p16}, x)
    got = jax.jit(
        lambda p, x: pipeline_trunk_apply(tr16, p, mesh, x, 2)
    )(p16, x)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2,
    )


def test_composes_with_data_parallel_axis():
    """pipeline_layers is axis-parameterized (ring.py pattern), so it
    runs inside a 2-axis ('dp', 'pp') mesh: batch sharded over dp, each
    dp row driving its own 4-stage pipeline — the composition
    gpipe_apply's standalone mesh cannot express."""
    from jax.sharding import Mesh, PartitionSpec as P

    from dalle_pytorch_tpu.parallel.gpipe import pipeline_layers

    params = _params(jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, DIM))
    want = _sequential(params, x)

    dp, pp, n_micro = 2, 4, 2
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(dp, pp), ("dp", "pp"))
    staged = jax.tree.map(
        lambda a: a.reshape(pp, DEPTH // pp, *a.shape[1:]), params
    )
    mb = x.reshape(n_micro, BATCH // n_micro, SEQ, DIM)

    def stage_fn(params_local, mb_local):
        my_layers = jax.tree.map(lambda a: a[0], params_local)
        outs = pipeline_layers(
            _layer, my_layers, mb_local, axis_name="pp", n_micro=n_micro
        )
        return outs[None]

    from jax import shard_map

    outs = jax.jit(
        shard_map(
            stage_fn,
            mesh=mesh,
            in_specs=(P("pp"), P(None, "dp")),  # batch rows over dp
            out_specs=P("pp", None, "dp"),
            check_vma=False,
        )
    )(staged, mb)
    got = outs[-1].reshape(BATCH, SEQ, DIM)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pipelines_unrolled_checkpoint_via_converter():
    """A trunk trained/checkpointed under the UNROLLED executor pipelines
    after unrolled_params_to_scan: legacy layout -> scan layout ->
    4-stage pipeline == the unrolled module's own forward."""
    from dalle_pytorch_tpu.models.transformer import (
        Transformer,
        pipeline_trunk_apply,
        unrolled_params_to_scan,
    )

    kw = dict(
        dim=32, depth=4, heads=2, dim_head=16, seq_len=24, causal=True,
        image_fmap_size=4, shift_tokens=True, rotary_emb=True,
        attn_impl="dense",
    )
    unrolled = Transformer(**kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (BATCH, 24, 32))
    uparams = unrolled.init(jax.random.PRNGKey(1), x)["params"]
    want = unrolled.apply({"params": uparams}, x)

    sparams = unrolled_params_to_scan(uparams, depth=4)
    got = jax.jit(
        lambda p, x: pipeline_trunk_apply(
            Transformer(executor="scan", **kw), p, make_pp_mesh(4), x, 2
        )
    )(sparams, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.slow  # ~32 s: full DALLE loss + grads through the pipelined
# trunk (tier-1 budget); test_pipelines_real_transformer_trunk keeps the
# fast-tier pipeline-parity signal
def test_dalle_loss_with_pipelined_trunk():
    """End-to-end DALLE training loss with the trunk run pipeline-
    parallel (trunk_fn override): loss AND grads match the plain
    scan-executor forward — pipeline parallelism composes with the full
    model (embeddings, logits masks, CE) without touching its code."""
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.dalle import DALLE
    from dalle_pytorch_tpu.models.transformer import (
        Transformer,
        make_pipeline_trunk,
    )

    model = DALLE(
        dim=32, depth=4, num_image_tokens=16, image_fmap_size=4,
        num_text_tokens=26, text_seq_len=8, heads=2, dim_head=16,
        shift_tokens=True, rotary_emb=True, executor="scan",
    )
    text = jax.random.randint(jax.random.PRNGKey(0), (4, 8), 1, 26)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 16)
    params = model.init(jax.random.PRNGKey(2), text, toks)["params"]
    mesh = make_pp_mesh(4)
    # built OUTSIDE model.apply (flax intercepts module construction
    # inside a parent scope)
    pipelined = make_pipeline_trunk(
        Transformer(**model.transformer_kwargs()), mesh, n_micro=2
    )

    def loss_plain(p):
        loss, _ = model.apply({"params": p}, text, toks, return_loss=True)
        return loss

    def loss_pp(p):
        trunk = lambda h: pipelined(p["transformer"], h)
        loss, _ = model.apply(
            {"params": p}, text, toks, return_loss=True, trunk_fn=trunk
        )
        return loss

    l_plain, g_plain = jax.value_and_grad(loss_plain)(params)
    l_pp, g_pp = jax.jit(jax.value_and_grad(loss_pp))(params)
    np.testing.assert_allclose(float(l_pp), float(l_plain), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5
        ),
        g_pp, g_plain,
    )


def test_trains_with_sharded_params():
    """One optimizer-style update with params device_put under the pp
    sharding: the jitted grad runs with stage-resident parameters (the
    deployment layout), and pp=1 degenerates to the plain scan."""
    params = _params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, SEQ, DIM))
    mesh = make_pp_mesh(4)
    sharded = jax.device_put(params, stage_params_sharding(mesh, params))

    def loss(p, x):
        return (gpipe_apply(mesh, p, _layer, x, 2) ** 2).mean()

    l0, g = jax.jit(jax.value_and_grad(loss))(sharded, x)
    stepped = jax.tree.map(lambda p, g: p - 0.1 * g, sharded, g)
    l1 = jax.jit(loss)(stepped, x)
    assert np.isfinite(l0) and l1 < l0

    got1 = gpipe_apply(make_pp_mesh(1), params, _layer, x, 2)
    np.testing.assert_allclose(
        np.asarray(got1), np.asarray(_sequential(params, x)), atol=1e-6
    )
