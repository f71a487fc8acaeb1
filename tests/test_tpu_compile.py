"""Ask the TPU compiler about every main-path `pallas_call` at flagship
shapes — without a chip.

The rest of the suite runs the Pallas kernels in interpret mode on the
CPU, which cannot see what Mosaic refuses (block shapes off the (8, 128)
tiling, too much VMEM) or what does not fit 16 GB of HBM. libtpu compiles
for a DESCRIBED v5e here. A compile that passes is not a chip run: it says
nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import: one process at a time may load libtpu, and every xdist worker
imports every test file), everything compiles in the test's own process,
and all of it lives in this ONE file so one worker owns the library.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

# flagship attention geometry: batch 4, 16 heads x 64, text 256 + 32x32 image
B, H, D = 4, 16, 64
TEXT, FMAP = 256, 32
SEQ = TEXT + FMAP * FMAP  # 1280
CACHE = SEQ + 1  # decode cache length (bos + sequence)
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent cache
    # but not read back without a chip; keep these compiles out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Code that picks interpret mode from `jax.default_backend()` sees the
    CPU here; these compiles are for the described chip."""
    from dalle_pytorch_tpu.ops import pallas_attention, pallas_decode

    for module in (pallas_attention, pallas_decode):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, n, s=None, dtype=jnp.bfloat16):
    s = n if s is None else s
    q = jax.ShapeDtypeStruct((B, H, n, D), dtype, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, H, s, D), dtype, sharding=sharding)
    return q, kv, kv


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _axial_mask():
    from dalle_pytorch_tpu.ops.masks import axial_static_mask

    return np.tril(np.ones((SEQ, SEQ), bool)) & np.asarray(
        axial_static_mask(SEQ, FMAP, 0)
    )[:SEQ, :SEQ]


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "axial"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, masked, grad):
    from dalle_pytorch_tpu.ops.pallas_attention import flash_attention

    mask = _axial_mask() if masked else None
    attn = functools.partial(flash_attention, mask=mask, interpret=False)
    if grad:
        fn = jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    else:
        fn = attn
    _compile(fn, *_qkv(one_chip, SEQ))


@pytest.mark.parametrize("n", [1, 4, 256])
@pytest.mark.parametrize("quant", [False, True], ids=["model", "int8"])
def test_flash_decode_compiles(one_chip, n, quant):
    from dalle_pytorch_tpu.ops.pallas_decode import flash_decode_attention

    q, k, v = _qkv(one_chip, n, CACHE, jnp.int8 if quant else jnp.bfloat16)
    q = jax.ShapeDtypeStruct(q.shape, jnp.bfloat16, sharding=one_chip)
    lens = _i32(one_chip, B)
    if quant:
        sc = _f32(one_chip, B, H, CACHE)
        fn = lambda q, k, v, l, ks, vs: flash_decode_attention(
            q, k, v, l, k_scale=ks, v_scale=vs, interpret=False
        )
        _compile(fn, q, k, v, lens, sc, sc)
    else:
        fn = functools.partial(flash_decode_attention, interpret=False)
        _compile(fn, q, k, v, lens)


@pytest.mark.parametrize("quant", [False, True], ids=["model", "int8"])
def test_block_sparse_flash_decode_compiles(one_chip, quant):
    from dalle_pytorch_tpu.ops.pallas_decode import (
        block_sparse_flash_decode_attention,
    )

    q, k, v = _qkv(one_chip, 4, CACHE, jnp.int8 if quant else jnp.bfloat16)
    q = jax.ShapeDtypeStruct(q.shape, jnp.bfloat16, sharding=one_chip)
    lens = _i32(one_chip, B)
    bitmap = _i32(one_chip, B, -(-CACHE // 128))
    if quant:
        sc = _f32(one_chip, B, H, CACHE)
        fn = lambda q, k, v, l, bm, ks, vs: block_sparse_flash_decode_attention(
            q, k, v, l, bm, k_scale=ks, v_scale=vs, interpret=False
        )
        _compile(fn, q, k, v, lens, bitmap, sc, sc)
    else:
        fn = functools.partial(block_sparse_flash_decode_attention, interpret=False)
        _compile(fn, q, k, v, lens, bitmap)


@pytest.mark.parametrize("sparse", [False, True], ids=["paged", "sparse_paged"])
@pytest.mark.parametrize("quant", [False, True], ids=["model", "int8"])
def test_paged_flash_decode_compiles(one_chip, sparse, quant):
    from dalle_pytorch_tpu.ops.pallas_decode import (
        block_sparse_paged_flash_decode_attention,
        paged_flash_decode_attention,
    )

    page, n_pages = 32, -(-CACHE // 32)
    pool = B * n_pages + 1
    q = jax.ShapeDtypeStruct((B, H, 4, D), jnp.bfloat16, sharding=one_chip)
    pages = jax.ShapeDtypeStruct(
        (pool, H, page, D), jnp.int8 if quant else jnp.bfloat16, sharding=one_chip
    )
    args = [q, pages, pages, _i32(one_chip, B), _i32(one_chip, B, n_pages)]
    kernel = paged_flash_decode_attention
    if sparse:
        kernel = block_sparse_paged_flash_decode_attention
        args.append(_i32(one_chip, B, n_pages))
    n_plain = len(args)
    if quant:
        sc = _f32(one_chip, pool, H, page)
        args += [sc, sc]

    def fn(*a):
        kw = {"k_scale": a[-2], "v_scale": a[-1]} if quant else {}
        return kernel(*a[:n_plain], interpret=False, **kw)

    _compile(fn, *args)


def test_sharded_flash_decode_compiles_on_four_chips(topo, compiled_kernels):
    """`serve.py --mesh dp=1,tp=4`'s kernel: shard_map over heads on a Mesh
    of the four described devices."""
    from dalle_pytorch_tpu.ops.pallas_decode import sharded_flash_decode_attention
    from dalle_pytorch_tpu.parallel.mesh import MESH_AXES

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4, 1), MESH_AXES)
    heads = NamedSharding(mesh, P(None, "tp", None, None))
    q, k, v = _qkv(heads, 4, CACHE)
    lens = _i32(NamedSharding(mesh, P()), B)
    compiled = _compile(
        functools.partial(sharded_flash_decode_attention, mesh), q, k, v, lens
    )
    # each device holds a quarter of the heads of k and v, not all of them
    kv_bytes = 2 * B * H * CACHE * D * 2
    assert compiled.memory_analysis().argument_size_in_bytes < kv_bytes / 2


# ------------------------------------------------------ whole train step


def _flagship_step(devices, mesh_axes, batch, executor="scan", reversible=True):
    """The step `train_dalle.py` jits — flagship widths, a frozen 256 px dVAE
    encoding in the step, bf16 — lowered for DESCRIBED devices: state and
    batch are shapes with shardings, never arrays."""
    from dalle_pytorch_tpu.models.dvae import DiscreteVAE
    from dalle_pytorch_tpu.parallel import (
        batch_sharding, make_mesh, partition_params, state_shardings,
    )
    from dalle_pytorch_tpu.training import (
        TrainState, make_dalle_train_step, make_optimizer,
    )
    from dalle_pytorch_tpu.training.config import TrainConfig
    from dalle_pytorch_tpu.training.pipeline import dalle_from_config, vae_from_config

    cfg = TrainConfig()
    m = cfg.model
    m.dim, m.depth, m.heads, m.dim_head, m.text_seq_len = 1024, 12, H, D, TEXT
    m.shift_tokens = m.rotary_emb = True
    m.reversible, m.executor = reversible, executor
    cfg.vae.image_size = 8 * FMAP  # 3 layers: 256 px -> 32x32 tokens
    vae = vae_from_config(cfg.vae)
    mesh = make_mesh(devices=devices, **mesh_axes)
    model = dalle_from_config(
        cfg, num_image_tokens=vae.num_tokens, image_fmap_size=FMAP,
        vocab_size=32768, sp_mesh=mesh,  # as train_dalle.py builds it
    )
    text = jnp.zeros((1, TEXT), jnp.int32)
    tokens = jnp.zeros((1, FMAP * FMAP), jnp.int32)
    image = jnp.zeros((1, 8 * FMAP, 8 * FMAP, 3), jnp.float32)

    def init_state():
        params = model.init(jax.random.PRNGKey(0), text, tokens)["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params,
            tx=make_optimizer(3e-4, clip_grad_norm=0.5),
        )

    state = jax.eval_shape(init_state)
    vae_params = jax.eval_shape(
        lambda: vae.init(
            {"params": jax.random.PRNGKey(1), "gumbel": jax.random.PRNGKey(2)}, image
        )["params"]
    )
    state_sh = state_shardings(state, mesh)
    vae_sh = partition_params(vae_params, mesh)
    batch_sh = {
        "text": batch_sharding(mesh, extra_dims=1),
        "images": batch_sharding(mesh, extra_dims=3),
    }
    step = jax.jit(
        make_dalle_train_step(model, vae=vae),
        in_shardings=(state_sh, batch_sh, None, vae_sh),
        out_shardings=(state_sh, None),
        donate_argnums=0,
    )

    def shaped(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings,
        )

    replicated = NamedSharding(mesh, P())
    batch_shapes = {
        "text": jax.ShapeDtypeStruct((batch, TEXT), jnp.int32, sharding=batch_sh["text"]),
        "images": jax.ShapeDtypeStruct(
            (batch, 8 * FMAP, 8 * FMAP, 3), jnp.float32, sharding=batch_sh["images"]
        ),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    return step.lower(
        shaped(state, state_sh), batch_shapes, rng, shaped(vae_params, vae_sh)
    )


def _device_bytes(compiled):
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )


def test_flagship_train_step_fits_one_chip(topo, compiled_kernels):
    """Batch 16 with remat on the scan executor: what chip_smoke.py's
    trainer phase runs (there on the default unrolled executor, which the
    compiler also fits, 70 s of compile instead of 20)."""
    compiled = _flagship_step(
        [topo.devices[0]], dict(dp=1), batch=16
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernel is in
    assert _device_bytes(compiled) < HBM_BYTES, compiled.memory_analysis()


@pytest.mark.slow
def test_flagship_train_step_without_remat_does_not_fit(topo, compiled_kernels):
    """Why the smoke sets model.reversible=true at batch 16: the shipped
    default (no remat) is refused by the compiler, a few MB over HBM."""
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|hbm"):
        _flagship_step(
            [topo.devices[0]], dict(dp=1), batch=16, executor="unrolled",
            reversible=False,
        ).compile()


@pytest.mark.slow
def test_flagship_train_step_shards_over_four_chips(topo, compiled_kernels):
    """`mesh.fsdp=2 mesh.tp=2` (chip_smoke.py --chips 4): compiles for a
    Mesh of the four described devices, each holding a share of the state."""
    compiled = _flagship_step(
        list(topo.devices), dict(dp=1, fsdp=2, tp=2), batch=16
    ).compile()
    one = _flagship_step([topo.devices[0]], dict(dp=1), batch=16).compile()
    sharded_args = compiled.memory_analysis().argument_size_in_bytes
    assert sharded_args < 0.5 * one.memory_analysis().argument_size_in_bytes
    assert _device_bytes(compiled) < HBM_BYTES
