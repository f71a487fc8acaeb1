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
import re

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


TRAIN_BATCH = 16  # the train cell's: the kernels see 16 x 1280 x (16 x 64)
# what `choose_tiles` gives the cell's token-major call, a pair of heads a
# block of 128 lanes: the plan of a 128-wide head, 11.88 MiB of the 12
TOKEN_TILES = (640, 640)


@pytest.mark.parametrize("layout", ["head_major", "token_major"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("masked", [False, True], ids=["causal", "axial"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(one_chip, masked, grad, dtype, layout):
    """fwd, dq and dkv at the train cell's shape and the tiles the chooser
    gives it: Mosaic takes the tiles, the in-kernel loops over them, the
    bf16 MXU operands and the VMEM they ask for; token-major, a pair of
    64-wide heads as one 128-lane column block of `[B, N, H x D]`, its
    lane masks and selects and the `[rows, 2]` statistics."""
    from dalle_pytorch_tpu.ops import pallas_attention as pa

    tokens = layout == pa.TOKEN_MAJOR
    mask = _axial_mask() if masked else None
    attn = functools.partial(pa.flash_attention, mask=mask, interpret=False, layout=layout)
    if grad:
        fn = jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), (0, 1, 2))
    else:
        fn = attn
    shape = (TRAIN_BATCH, SEQ, H, D) if tokens else (TRAIN_BATCH, H, SEQ, D)
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)] * 3
    pa.forget()
    text = _compile(fn, *shapes).as_text()
    assert text.count("tpu_custom_call") == (3 if grad else 1)
    assert pa.kernel_bodies == (3 if grad else 1)
    assert pa.layouts_built == {layout: 3 if grad else 1}
    # float32 operands, a mask's block and a block twice a head's width each
    # take VMEM from the score tile: any two of them leave 256 x 640
    narrow = (dtype != jnp.bfloat16) + masked + tokens
    want = (640, 640) if narrow < 2 else (256, 640)
    assert set(pa.tiles_chosen.values()) == {want}
    if tokens:  # the kernels' operands and results are the projection's columns
        kernels = re.findall(r"%(\w+_flash)[.\d]* = \(?(\w+\[[\d,]*\])", text)
        assert len(kernels) == (3 if grad else 1)
        assert {shape for _, shape in kernels} == {
            f"{'bf16' if dtype == jnp.bfloat16 else 'f32'}[{TRAIN_BATCH},{SEQ},{H * D}]"}


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


def _flagship_step(devices, mesh_axes, batch, executor="scan", reversible=True,
                   remat_policy="layer_residuals"):
    """The step `train_dalle.py` jits — flagship widths, a frozen 256 px dVAE
    encoding in the step, bf16 — lowered for DESCRIBED devices: state and
    batch are shapes with shardings, never arrays. `remat_policy` is the
    trainer's default unless a test asks for another."""
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
    assert m.remat_policy == "layer_residuals"  # what a trainer gets unasked
    m.reversible, m.executor, m.remat_policy = reversible, executor, remat_policy
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


# This file's unrolled flagship step lowers to 8,948,581 characters with 4
# flash bodies and 3 of the rotary's pass in it; it did to 9,159,532 with the
# rotary as 36 groups of slices, stacks and concatenates on `[.., 16, 64]`
# and the head transposes around 4 head-major bodies (PR 33), and to
# 9,529,737 with 48 bodies (one per call site, before PR 26). The bound is
# the parent's length: paths and line numbers, which the serialized kernels
# embed, move it by a few thousand; a body built per call site breaks it.
LOWERED_TEXT_BYTES = 9_159_532


@pytest.mark.parametrize("policy, forward", [
    ("layer_residuals", (12, 1)), ("flash_residuals", (12, 1)), ("nothing_saveable", (24, 2))],
    ids=["layer_residuals", "flash_residuals", "nothing_saveable"])
def test_flagship_step_holds_each_kernel_body_once(topo, compiled_kernels, policy, forward):
    """The unrolled 12-layer step with remat calls the flash kernels 36
    times (fwd, dq, dkv per layer) when it keeps the forward rule's
    residuals (the trainer's default `layer_residuals`, which keeps the
    feed-forward's two products beside them, and `flash_residuals`: each
    ONE policy object for all layers) and 48 when it saves nothing (fwd
    again under remat), and holds 4 bodies either way: the emitters are
    jitted, so each is traced once and lowered to
    Mosaic once per program. All four index the projection's columns,
    `[B, N, H x D]`, and so does the rotary's pass, which takes `to_qkv`'s
    result whole and hands q, k and v over (forward, and again under remat
    where q, k and v are not kept; joined again backward: 24 or 36 calls of
    3 bodies); between `to_qkv` and `to_out` no
    array is laid out `[B, H, N, D]`, none is transposed and the fused
    projection is never sliced.
    Lowering only; nothing is compiled."""
    from dalle_pytorch_tpu.ops import pallas_attention as pa

    pa.forget()
    text = _flagship_step(
        [topo.devices[0]], dict(dp=1), batch=TRAIN_BATCH, executor="unrolled",
        remat_policy=policy,
    ).as_text()
    # the process built one body more than the step holds: the forward at
    # batch 1, traced (and never lowered) by `model.init`'s shape pass
    init = [key for key in pa.tiles_chosen if key[1][0] == 1]
    assert [key[0] for key in init] == ["fwd"]
    assert pa.kernel_bodies - len(init) == 4, pa.tiles_chosen
    assert pa.layouts_built == {pa.TOKEN_MAJOR: 4 + len(init)}
    calls = {"_emit_fwd": forward, "_emit_dq": (12, 1), "_emit_dkv": (12, 1),
             "_emit_split": forward, "_emit_join": (12, 1)}
    for emitter, (sites, bodies) in calls.items():
        assert len(re.findall(rf"call @{emitter}\w*\(", text)) == sites, emitter
        assert len(set(re.findall(rf"func.func private @({emitter}\w*)\(", text))) == bodies
    # lowered bodies: dq, dkv and the join once; the forward kernel and the
    # split twice each where the first pass drops what only remat's second
    # pass reads (the log-sum-exp; q, k, v apart), once where it keeps them
    assert text.count("tpu_custom_call") == 3 + 2 * forward[1]
    assert set(pa.tiles_chosen.values()) == {TOKEN_TILES}
    assert {key[1] for key in pa.tiles_chosen if key not in init} == {
        (TRAIN_BATCH, SEQ, H * D)
    }
    assert f"{TRAIN_BATCH}x{H}x{SEQ}x{D}x" not in text  # nothing head-major
    token_major = f"tensor<{TRAIN_BATCH}x{SEQ}x{H}x{D}x"
    assert not [line for line in text.splitlines()
                if "stablehlo.transpose" in line and token_major in line]
    assert not [line for line in text.splitlines()
                if "stablehlo.slice" in line and f"{TRAIN_BATCH}x{SEQ}x{3 * H * D}x" in line]
    assert len(text) < LOWERED_TEXT_BYTES, len(text)


def test_flagship_step_holds_four_bodies_with_the_new_arguments_spelled_out(
        topo, compiled_kernels, monkeypatch):
    """What the shared kernels owe `flagship.train`: a call that names the
    language-model path's arguments at their defaults (`window=None`, K/V
    heads as many as query heads) lowers to the same kernel bodies (four
    traced, three lowered with the residuals kept) and the same tiles as one
    that leaves them out."""
    from dalle_pytorch_tpu.models import attention
    from dalle_pytorch_tpu.ops import pallas_attention as pa

    real = attention.flash_attention

    def spelled_out(q, k, v, **kw):
        assert q.shape[2] == k.shape[2] and kw["layout"] == pa.TOKEN_MAJOR
        return real(q, k, v, **{"window": None, **kw})

    monkeypatch.setattr(attention, "flash_attention", spelled_out)
    pa.forget()
    text = _flagship_step(
        [topo.devices[0]], dict(dp=1), batch=TRAIN_BATCH, executor="unrolled"
    ).as_text()
    assert text.count("tpu_custom_call") == 3 + 2  # flash bodies + the rotary's
    assert set(pa.tiles_chosen.values()) == {TOKEN_TILES}
    assert len(text) < LOWERED_TEXT_BYTES, len(text)


# the language-model cell's attention: 4 rows, 32 query heads over 4 K/V
# heads of 128, 8192 positions, window 1024 on three layers of four
LM_B, LM_H, LM_HKV, LM_N, LM_D, LM_WINDOW = 4, 32, 4, 8192, 128, 1024


@pytest.mark.parametrize("layout", ["head_major", "token_major"])
@pytest.mark.parametrize("window", [LM_WINDOW, None], ids=["window", "full"])
def test_flash_attention_compiles_with_grouped_heads_and_a_window(one_chip, window, layout):
    """fwd, dq and dkv at the language-model cell's shape: head 128, length
    8192 (where a row no longer stays resident: spans of 4096 keys and 1024
    query rows), 8 query heads a K/V head, the window's loop bounds and DMA
    skip. The tiles come from the shape alone. Token-major a head is one
    128-lane column block of q `[B, N, 32 x 128]` and of k, v
    `[B, N, 4 x 128]`, with the same tiles and spans."""
    from dalle_pytorch_tpu.ops import pallas_attention as pa

    tokens = layout == pa.TOKEN_MAJOR
    # token-major operands arrive as a projection writes them, `[B, N, H x D]`
    # (a 4-D parameter of 4 heads would be tiled (4, 128) and copied out of it)
    shape = lambda h: (LM_B, LM_N, h * LM_D) if tokens else (LM_B, h, LM_N, LM_D)
    heads = lambda t: t.reshape(LM_B, LM_N, -1, LM_D) if tokens else t
    q = jax.ShapeDtypeStruct(shape(LM_H), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape(LM_HKV), jnp.bfloat16, sharding=one_chip)
    attn = functools.partial(pa.flash_attention, window=window, interpret=False, layout=layout)
    loss = lambda q, k, v: attn(heads(q), heads(k), heads(v)).astype(jnp.float32).sum()
    pa.forget()
    text = _compile(jax.grad(loss, (0, 1, 2)), q, kv, kv).as_text()
    assert text.count("tpu_custom_call") == 3
    assert pa.layouts_built == {layout: 3}
    assert set(pa.tiles_chosen.values()) == {(512, 512)}
    assert pa._spans(LM_N, LM_N, 512, 512, LM_D, 2) == (1024, 4096)
    if tokens:
        kernels = dict(re.findall(r"%(\w+_flash)[.\d]* = \(?(\w+\[[\d,]*\])", text))
        assert kernels == {
            "fwd_flash": f"bf16[{LM_B},{LM_N},{LM_H * LM_D}]",
            "dq_flash": f"bf16[{LM_B},{LM_N},{LM_H * LM_D}]",
            "dkv_flash": f"bf16[{LM_B},{LM_N},{LM_HKV * LM_D}]",
        }
        # no bf16 operand or result of a kernel is laid out anew. (Float32
        # copies remain and say nothing of the program: the row sums `delta`
        # are laid out as `lse` is written, head-major too, and this loss
        # converts the whole output to float32, which the module never does.)
        assert not re.search(r"= bf16\[[\d,]*\]\S* (transpose|copy)\(", text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_rotary_pass_compiles(one_chip, compiled_kernels, dtype):
    """The rotary's one pass at the train cell's shape, forward (the fused
    projection `[B, N, 3 x 1024]` in, q, k and v out) and backward (their
    cotangents in, the projection's out): lane rotations of `[rows, 1024]`
    in float32, a part at a time, blocks of 160 rows (80 of float32
    operands) inside the VMEM it plans; nothing is sliced or concatenated
    outside the kernels."""
    from dalle_pytorch_tpu.ops import pallas_rotary as pr

    t = jax.ShapeDtypeStruct((TRAIN_BATCH, SEQ, 3 * H * D), dtype, sharding=one_chip)
    angles = _f32(one_chip, SEQ, 60)

    def both(t, angles):  # the forward's results are returned, so the forward stays
        outs, vjp = jax.vjp(lambda t: pr.rotary_split(angles, t, H, 3), t)
        return outs, vjp(outs)[0]

    text = _compile(both, t, angles).as_text()
    name = "bf16" if dtype == jnp.bfloat16 else "f32"
    kernels = dict(re.findall(r"%(rotary_\w+?)[.\d]* = \(?(\w+\[[\d,]*\])", text))
    assert kernels == {"rotary_split": f"{name}[{TRAIN_BATCH},{SEQ},{H * D}]",
                       "rotary_join": f"{name}[{TRAIN_BATCH},{SEQ},{3 * H * D}]"}
    entry = text[text.index("ENTRY"):]
    assert not re.search(r" (slice|concatenate)\(", entry)
    assert pr._rows(SEQ, H * D, jnp.dtype(dtype).itemsize, 3) == (160 if dtype == jnp.bfloat16 else 80)


@pytest.mark.parametrize("k,n", [(2304, 896), (896, 2304)], ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, compiled_kernels, monkeypatch, k, n):
    """The routed layer's products at the cell's shapes (a buffer of 262144
    rows, 16 experts, 2304 x 896 and back): `gmm_fwd`, `gmm_dlhs` and
    `gmm_drhs` with their prefetched work plan, tiles of 512 rows by 768 or
    896, float32 scratch, parameters float32 and rows bf16."""
    from dalle_pytorch_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    lhs = jax.ShapeDtypeStruct((262144, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((16, k, n), jnp.float32, sharding=one_chip)
    fn = jax.value_and_grad(
        lambda l, r, s: gm.grouped_matmul(l, r, s).astype(jnp.float32).sum(), (0, 1))
    text = _compile(fn, lhs, rhs, _i32(one_chip, 16)).as_text()
    kernels = dict(re.findall(r"%(gmm_\w+?)[.\d]* = (\w+\[[\d,]*\])", text))
    assert kernels == {"gmm_fwd": f"bf16[262144,{n}]", "gmm_dlhs": f"bf16[262144,{k}]",
                       "gmm_drhs": f"f32[16,{k},{n}]"}
    assert (gm._tile(2304), gm._tile(896)) == (768, 896)


@pytest.mark.slow
@pytest.mark.parametrize("policy, second_forward", [
    ("layer_residuals", {}), ("flash_residuals", {}),
    ("nothing_saveable", {("fwd_flash", "remat"): 12})],
    ids=["layer_residuals", "flash_residuals", "nothing_saveable"])
def test_flagship_step_kernels_keep_their_names_and_phases(
        topo, compiled_kernels, policy, second_forward):
    """Compiled, the shared bodies are 36 custom calls again (48 where remat
    saves nothing and the forward kernel runs a second time), each named
    for its kernel (`fwd_flash.N`: the benchmark's regexes and
    `obs/scopes.py` find them by that) with the output signature its
    reader matches, and each under ITS call site's layer and phase: the
    callee's names are relative and XLA prefixes the call site's."""
    from dalle_pytorch_tpu.obs import scopes

    text = _flagship_step(
        [topo.devices[0]], dict(dp=1), batch=TRAIN_BATCH, executor="unrolled",
        remat_policy=policy,
    ).compile().as_text()
    table = scopes.classify(scopes.parse(text))
    by_phase = {}
    for name, (opcode, shape, component, phase) in table.items():
        if component != "attn_kernel" or opcode != "custom-call":
            continue  # a tuple's `get-tuple-element` carries the scope too
        by_phase.setdefault((name.rsplit(".", 1)[0], phase), []).append(shape)
    assert {k: len(v) for k, v in by_phase.items()} == {
        ("fwd_flash", "fwd"): 12, **second_forward,
        ("dq_flash", "bwd"): 12, ("dkv_flash", "bwd"): 12,
    }
    o = f"bf16[{TRAIN_BATCH},{SEQ},{H * D}]"  # a pair of heads a column block
    assert set(by_phase["fwd_flash", "fwd"]) == {f"({o},f32[{TRAIN_BATCH},{H // 2},{SEQ},2])"}
    assert set(by_phase["dq_flash", "bwd"]) == {o}
    assert set(by_phase["dkv_flash", "bwd"]) == {f"({o},{o})"}
    layers = {
        m for line in text.splitlines() if "fwd_flash" in line and "custom-call(" in line
        for m in re.findall(r"/(attn_\d+)/", line)
    }
    assert layers == {f"attn_{i}" for i in range(12)}


def _device_bytes(compiled):
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )


# What the compiler plans for this file's batch-16 step with the trainer's
# default `remat_policy="layer_residuals"` (PR 48; plans for a described v5e,
# not chip runs): 12,671,160,832 bytes on the unrolled executor, which the
# train cell and chip_smoke.py run (8,163,508,736 with `flash_residuals`,
# 5,401,605,120 with `nothing_saveable`), and 15,921,223,680 on the scan
# executor (11,475,951,616 | 7,990,795,776), whose stacked residuals the
# compiler lays out with 0.7 GB more beside them. Each bound is the reading
# and a margin for the compiler's own moves; the scan executor's is the one
# near the chip, which loads 15.75 of its 16 GiB.
FLAGSHIP_PLAN_BYTES = {"unrolled": 12_900_000_000, "scan": 16_400_000_000}
CHIP_LOADS_BYTES = int(15.75 * 1024**3)


@pytest.mark.parametrize("executor", ["unrolled", "scan"])
def test_flagship_train_step_fits_one_chip(topo, compiled_kernels, capsys, executor):
    """Batch 16 with remat, every layer keeping the flash kernels' residuals
    and the feed-forward's two products (170 + 377 MB a layer): on the
    default unrolled executor what chip_smoke.py's trainer phase and the
    train cell run, and on the scan executor. The plan stays under its bound,
    the cell's executor's under 13e9, the scan executor's under what the
    chip loads."""
    compiled = _flagship_step(
        [topo.devices[0]], dict(dp=1), batch=16, executor=executor
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the flash kernel is in
    plan = _device_bytes(compiled)
    with capsys.disabled():
        print(f"\n[plan] flagship step, {executor}, layer_residuals: {plan:,} bytes")
    assert FLAGSHIP_PLAN_BYTES["unrolled"] < 13e9
    assert plan < FLAGSHIP_PLAN_BYTES[executor] < CHIP_LOADS_BYTES < HBM_BYTES, \
        compiled.memory_analysis()


@pytest.mark.slow
def test_flagship_train_step_plans_grow_with_what_remat_keeps(topo, compiled_kernels, capsys):
    """The unrolled batch-16 step under the four settings, each plan
    printed: a layer's input alone, the flash kernels' residuals beside it,
    the feed-forward's two products beside those, no remat at all. All four
    fit the chip (no remat has since before PR 40; at PR 21 it was 21 MB
    over), in that order."""
    plans = {}
    for label, kw in (("nothing_saveable", dict(remat_policy="nothing_saveable")),
                      ("flash_residuals", dict(remat_policy="flash_residuals")),
                      ("layer_residuals", dict(remat_policy="layer_residuals")),
                      ("no remat", dict(reversible=False))):
        plans[label] = _device_bytes(_flagship_step(
            [topo.devices[0]], dict(dp=1), batch=16, executor="unrolled", **kw,
        ).compile())
        with capsys.disabled():
            print(f"\n[plan] flagship step, unrolled, {label}: {plans[label]:,} bytes")
    assert (plans["nothing_saveable"] < plans["flash_residuals"] < plans["layer_residuals"]
            < FLAGSHIP_PLAN_BYTES["unrolled"] < plans["no remat"] < HBM_BYTES), plans


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


# ------------------------------------------------- the scan executor's decode


def test_scan_decode_holds_its_cache_in_place(topo, one_chip, capsys):
    """The cached sampler of a scan model at `paper64.generate`'s widths (dim
    1024, 16 heads x 64, cache 1281, the four patterns, batch 2; depth 8 of
    the 64, the compile is a third of a minute): the depth-stacked K/V rides
    both loops' carries and nothing but a chunk's own positions is written.
    Read from the optimized HLO: no loop copies a stacked K/V leaf, every
    `dynamic-update-slice` into one is as wide as its chunk (257 positions
    in the prefill, 1 in a token step), a token step materializes no layer's
    view, and the program's temporaries stay under a bound the scanned-in,
    collected-out threading broke (1.63e9 bytes then, 0.63e9 now). What is
    left is ONE relayout of a leaf in the entry computation, between the
    prefill's loop and the token loop, once a batch."""
    from dalle_pytorch_tpu.models.dalle import DALLE, _generate_images_cached_impl
    from dalle_pytorch_tpu.obs import scopes

    depth, batch = 8, 2
    model = DALLE(
        dim=1024, depth=depth, heads=H, dim_head=D, text_seq_len=TEXT,
        num_text_tokens=32768, num_image_tokens=8192, image_fmap_size=FMAP,
        shift_tokens=True, rotary_emb=True, executor="scan", attn_impl="auto",
        attn_types=("full", "axial_row", "axial_col", "conv_like"),
        dtype=jnp.bfloat16,
    )
    variables = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, TEXT), jnp.int32),
            jnp.zeros((1, FMAP * FMAP), jnp.int32),
        )
    )
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
    )
    compiled = jax.jit(
        lambda v, rng, text: _generate_images_cached_impl(
            model, v, rng, text, filter_thres=0.9
        )
    ).lower(
        on_chip(variables),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
        _i32(one_chip, batch, TEXT),
    ).compile()
    text = compiled.as_text()

    leaf = f"bf16[{depth},{batch},{H},{CACHE},{D}]"
    view = f"bf16[{batch},{H},{CACHE},{D}]"
    shapes, copies, writes, in_entry = {}, [], [], False
    for line in text.splitlines():
        if not line.startswith(" "):
            in_entry = line.startswith("ENTRY")
            continue
        got = scopes.instruction(line)
        if got is None:
            continue
        name, opcode, shape = got
        shapes[name] = shape
        if shape == leaf and opcode in ("copy", "copy-start"):
            copies.append((name, in_entry))
        if shape == leaf and opcode == "dynamic-update-slice":
            writes.append(line.split("dynamic-update-slice(", 1)[1].split(",")[1].strip(" %"))
    assert [name for name, entry in copies if not entry] == [], copies
    assert len(copies) <= 1, copies
    widths = sorted(int(shapes[update].split(",")[3]) for update in writes)
    assert widths == [1, 1, TEXT + 1, TEXT + 1], [shapes[u] for u in writes]
    token_step = [
        (name, row) for name, row in scopes.parse(text).items()
        if row[1] == view and "decode_image_step" in (row[2] or "")
    ]
    assert token_step == [], token_step
    assert compiled.memory_analysis().temp_size_in_bytes < 800e6, compiled.memory_analysis()
    with capsys.disabled():
        layouts = sorted(set(re.findall(re.escape(leaf) + r"(\{[^{}]*\})", text)))
        print(f"\n[scan decode] {leaf} is laid out as {layouts}; "
              f"temporaries {compiled.memory_analysis().temp_size_in_bytes}")


def test_routed_layer_moves_walk_the_rows_present(one_chip, compiled_kernels, monkeypatch):
    """One routed layer, forward + backward, at `mellum2.train.8k`'s widths
    (2,304 x 896 bf16, 16 of 64 experts held, 8 a token; 4,096 of the cell's
    32,768 tokens, so a buffer of 32,768 rows). Read from the optimized HLO:
    no gather anywhere makes a buffer's worth of rows ([buffer_rows, dim], or
    [tokens * 8, dim] on the way back): the moves are loops bounded by the
    rows present whose steps gather one chunk; no float32 [tokens, 8, dim]
    holds every slot's row; every pass over the buffer writes in place; and
    each grouped kernel is there as often as before (three of each)."""
    from collections import Counter

    from dalle_pytorch_tpu.models import moe
    from dalle_pytorch_tpu.obs import scopes
    from dalle_pytorch_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    tokens, dim, width, per_token = 4096, 2304, 896, 8
    buffer_rows, chunk = tokens * per_token, moe.CHUNK_ROWS
    layer = moe.RoutedExperts(dim=dim, expert_dim=width, experts_total=64,
                              experts_per_token=per_token, experts_held=(0, 16),
                              buffer_rows=buffer_rows)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, dim), jnp.bfloat16)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
                          {"params": params["params"]})
    loss = lambda p, x: jnp.sum(layer.apply(p, x).astype(jnp.float32) ** 2)
    compiled = _compile(jax.value_and_grad(loss, (0, 1)), params,
                        jax.ShapeDtypeStruct((1, tokens, dim), jnp.bfloat16, sharding=one_chip))
    text = compiled.as_text()

    kernels = Counter(re.findall(r"%(gmm_[a-z]+)[.\d]* = ", text))
    assert kernels == {"gmm_fwd": 3, "gmm_dlhs": 3, "gmm_drhs": 3}
    gathered, copies = Counter(), []
    for line in text.splitlines():
        got = scopes.instruction(line)
        if got is None:
            continue
        name, opcode, shape = got
        assert shape != f"f32[{tokens},{per_token},{dim}]", line
        if opcode == "gather" and shape.endswith(f",{dim}]"):
            gathered[shape] += 1
        if opcode in ("copy", "copy-start") and shape.startswith(f"bf16[{buffer_rows},"):
            copies.append(name)
    # a chunk a step, and the tokens' own order back by one gather of their rows
    assert set(gathered) == {f"bf16[{chunk},{dim}]", f"bf16[{tokens},{dim}]"}, gathered
    assert copies == [], copies
    assert len(re.findall(r" while\(", text)) >= 8  # the moves, forward and backward


def test_latent_decode_attention_compiles(one_chip, monkeypatch):
    """`decode_latent` at the generation cell's shapes (64 rows, 128 heads
    against one latent of 512 + a shared rotary key of 64, 8,480 positions in
    3 blocks of 2,944): Mosaic takes the block the cache's end cuts short, the
    rotary key with its positions last, and the VMEM the block asks for (two
    buffers of 3.0 MB, the block's masked copy and its float32 scores and
    weights, under the scoped 16 MiB, which two blocks of 4,352 pass); and
    the cache's two leaves reach the kernel as they are declared, with no
    copy of either (a 64-wide last axis was transposed and copied back at
    every call: models/decode_cache.py)."""
    from dalle_pytorch_tpu.ops import latent_decode as ld

    monkeypatch.setattr(ld, "_use_interpret", lambda: False)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn = functools.partial(ld.latent_decode_attention, sm_scale=192**-0.5)
    compiled = _compile(fn, s(64, 128, 512), s(64, 128, 64), s(64, 8480, 512), s(64, 64, 8480),
                        _i32(one_chip, 64))
    text = compiled.as_text()
    assert re.search(r"%decode_latent[.\d]* = bf16\[64,128,512\]", text)
    assert not re.search(r"= bf16\[64,(8480,512|64,8480)\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20
    assert (ld.BLOCK_POSITIONS, ld.even_block(8480, ld.BLOCK_POSITIONS)) == (2944, 2944)
    assert len(re.findall(r"%decode_latent[.\d]* = ", text)) == 1


@pytest.mark.parametrize("rows,k,n", [
    (512, 7680, 2048), (512, 2048, 7680), (384, 6144, 2048), (384, 2048, 6144),
], ids=["gate_up", "down", "verify_gate_up", "verify_down"])
def test_grouped_matmul_compiles_at_a_token_steps_sizes(one_chip, monkeypatch, rows, k, n):
    """`gmm_fwd` as a step of the routed generation cells calls it, 16 held
    experts stored in bf16: a token step of `pangu.decode.8k` (a buffer of
    512 rows) and a verify step of `kexaone.decode.16k` (384 rows), both in
    tiles of 128 rows."""
    from dalle_pytorch_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_use_interpret", lambda: False)
    gm.forget()
    lhs = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((16, k, n), jnp.bfloat16, sharding=one_chip)
    text = _compile(gm.grouped_matmul, lhs, rhs, _i32(one_chip, 16)).as_text()
    assert re.search(rf"%gmm_fwd[.\d]* = bf16\[{rows},{n}\]", text)
    assert gm.row_tiles == {("gmm_fwd", rows, 16): 128}
    assert gm._row_tile(512, 16) == 128 and (gm._tile(7680), gm._tile(2048)) == (768, 1024)
    assert gm._row_tile(384, 16) == 128 and (gm._tile(6144), gm._tile(2048)) == (1024, 1024)


def test_hybrid_token_loop_compiles_with_the_state_held_in_place(one_chip, monkeypatch):
    """The cached sampler of ONE period of `olmo-hybrid-7b-pp2` (three gated
    delta-rule layers and a full one at the published widths, 8 sessions of
    768 positions): Mosaic takes `delta_step`'s blocks of 10 heads x 96 x 192
    on a state whose last axis is 45 whole lane tiles (no padding of 192 to
    256), the loop's body holds the kernel once a linear layer and no copy of
    a state, and the head's product is made once a step (the compiler made it
    twice before `decode_step` fenced it: PERF.md, PR 33)."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import delta_step as ds, pallas_attention

    for module in (ds, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    path = Path(__file__).resolve().parent.parent / "benchmark/configs/olmo-hybrid-7b-pp2.json"
    cfg = dict(json.loads(path.read_text()), num_hidden_layers=4)
    mdl = lm.CausalLM.from_config(cfg, 768)
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    variables = on(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = on(jax.eval_shape(lambda: mdl.init_cache(8)))
    assert cache["layer_0"]["attn"]["state"].shape == (8, 96, 30 * 192)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    forced = jax.ShapeDtypeStruct((8, 32), jnp.int32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm._sampler_builder(mdl, (256, 0.9, 1.0, 2)), donate_argnums=(2,)).lower(
        variables, key, cache, forced, start).compile()
    text = compiled.as_text()
    body = text[text.index("region_0"):]
    body = body[:body.index("\n}\n")]
    assert len(re.findall(r"%delta_step[.\d]* = \(f32\[8,1,5760\]\S*, f32\[8,96,5760\]", body)) == 3
    assert not re.search(r"= f32\[8,96,5760\]\S* copy\(", body)
    assert "T(8,128)" in re.search(r"f32\[8,96,5760\]\{[^}]*\}", body).group(0)
    assert ".remat" not in text
    assert " sort(" not in text  # top-k 0.9 counts (`ops/sampling.py:kth_largest`: PR 36)
    assert ds.HEADS_PER_BLOCK == 10


def _kexaone(one_chip, monkeypatch, sessions=24, steps=288, doc=16384):
    """(model, its variables' and its sessions' cache's shapes on the described
    chip) of `kexaone.decode.16k`: the cell's own sizes."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import grouped_decode, grouped_matmul, pallas_attention

    for module in (grouped_decode, grouped_matmul, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = Path(__file__).resolve().parent.parent / "benchmark"
    cfg = json.loads((root / "configs/k-exaone-236b-ep8.json").read_text())
    job = json.loads((root / "workloads/kexaone.decode.16k.json").read_text())["job"]
    assert (job["sessions"], job["steps"], job["document_tokens"]) == (sessions, steps, doc)
    mdl = lm.CausalLM.from_config(cfg, doc + 2 * steps, **job["model"])
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    variables = on(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = on(jax.eval_shape(lambda: mdl.init_cache(sessions)))
    assert cache["layer_0"]["attn"]["k_at"].shape == (sessions, 8, 129, 128)  # a ring's snapshot
    return mdl, variables, cache


def test_verify_step_sampler_compiles_at_the_cells_size(one_chip, monkeypatch, capsys):
    """The token loop of `kexaone.decode.16k` (24 sessions x 16,384 + 576
    positions, 288 verify steps of two positions, the module drafting): the
    two full K/V layers ride the loop's carry in place (no copy of a leaf in
    the body, though a kernel reads them; a step's K/V go in by one named
    `dynamic-update-slice` a row), every routed block's three grouped
    products and every full layer's attention (`decode_grouped`, 16 query
    rows a K/V head) are Mosaic's, and the plan is the weights, the cache and
    under half a GB beside them."""
    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import grouped_decode

    grouped_decode.forget()
    mdl, variables, cache = _kexaone(one_chip, monkeypatch)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lm._verify_sampler_builder(mdl, (288, 0.9, 1.0, 2, None)),
                       donate_argnums=(2,)).lower(
        variables, key, cache, i32(24, 32), i32(24)).compile()
    text, plan = compiled.as_text(), _device_bytes(compiled) / 1e9
    with capsys.disabled():
        print(f"\nkexaone.decode.16k sampler: planned {plan:.2f} GB on the described v5e")
    # 3 x (4 + 1) products and 2 attentions a step, 3 + 1 before the loop
    assert text.count("tpu_custom_call") == 21
    # layer 3's and the module's at a step's two positions; the module's first
    # pass, before the loop, at one
    assert len(re.findall(r"%decode_grouped[.\d]* = bf16\[24,8,16,128\]", text)) == 2
    assert len(re.findall(r"%decode_grouped[.\d]* = bf16\[24,8,8,128\]", text)) == 1
    assert grouped_decode.calls == {(24, 8, 16, 16960): 2432, (24, 8, 8, 16960): 2432}
    kernels = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all(("/global_attend/" in line) == ("%decode_grouped" in line.split("=")[0])
               for line in kernels)
    assert not any("/window_attend/" in line for line in kernels)  # the rings stay XLA's
    assert not re.search(r"= bf16\[24,8,16960,128\]\S* copy\(", text)
    writes = re.findall(r"= bf16\[24,8,16960,128\]\S* dynamic-update-slice\(.*", text)
    assert len(writes) == 144  # 24 rows x (k, v) x (layer 3 + the module's + its first pass)
    assert all("/cache_write/" in w for w in writes)
    assert 12.5 < plan < 13.5


@pytest.mark.parametrize("query_rows,n", [(16, 2), (8, 1)], ids=["verify", "token"])
def test_grouped_decode_kernel_compiles_at_the_cells_shape(one_chip, monkeypatch, query_rows, n):
    """`decode_grouped` alone at a full layer's step of `kexaone.decode.16k`
    (24 rows x 8 K/V heads x 16,960 cached positions of 128, 8 query heads a
    K/V head at two positions a step, or at one): Mosaic takes the leaves as
    the cache holds them (no copy of either), 7 blocks of 2,432 positions of
    which the last overhangs the leaf, and the blocks' two buffers each of K
    and V with a block's float32 scores fit the scoped VMEM limit (16 MiB)
    with most of it to spare."""
    from dalle_pytorch_tpu.ops import grouped_decode as gd

    monkeypatch.setattr(gd, "_use_interpret", lambda: False)
    gd.forget()
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = _compile(functools.partial(gd.grouped_decode_attention, n=n),
                        s(24, 8, query_rows, 128), s(24, 8, 16960, 128), s(24, 8, 16960, 128),
                        _i32(one_chip, 24))
    text = compiled.as_text()
    assert re.search(rf"%decode_grouped[.\d]* = bf16\[24,8,{query_rows},128\]", text)
    assert not re.search(r"= bf16\[24,8,16960,128\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    block = gd.calls[(24, 8, query_rows, 16960)]
    assert (gd.BLOCK_POSITIONS, block, -(-16960 // block)) == (2560, 2432, 7)
    # K and V, two buffers each, and a block's scores and weights in float32
    assert 2 * 2 * block * 128 * 2 + 2 * query_rows * block * 4 < 4 * 2**20


def test_prefill_of_one_document_compiles_at_the_cells_size(one_chip, monkeypatch, capsys):
    """The prefill of one 16,384-token document into the sessions' cache
    (trunk, then the module over all but the last position; flash kernels
    with the window), every weight counted as held: the plan has to leave the
    chip's 15.74 GiB some room, which 24 sessions with rings a turn longer
    (705 slots, 15.73 GiB) did not and with rings of 129 slots and their
    snapshot (15.45 GiB) do (PERF.md, PR 37)."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables, cache = _kexaone(one_chip, monkeypatch)
    tokens = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm._prefill_builder(mdl, ()), donate_argnums=(2,), keep_unused=True).lower(
        variables, tokens, cache, row).compile()
    plan = _device_bytes(compiled) / 2**30
    with capsys.disabled():
        print(f"\nkexaone.decode.16k prefill: planned {plan:.2f} GiB on the described v5e")
    assert "tpu_custom_call" in compiled.as_text()
    assert plan < 15.55


def _deepseek_v32(one_chip, monkeypatch):
    """(model, its variables' shapes on the described chip, the job) of
    `deepseek32.decode.32k`: the cell's own sizes."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import grouped_matmul, index_score, latent_decode, pallas_attention

    for module in (grouped_matmul, index_score, latent_decode, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = Path(__file__).resolve().parent.parent / "benchmark"
    cfg = json.loads((root / "configs/deepseek-v32-exp-ep16.json").read_text())
    job = json.loads((root / "workloads/deepseek32.decode.32k.json").read_text())["job"]
    assert (job["sessions"], job["sessions_per_document"], job["document_tokens"]) == (16, 4, 32768)
    assert (job["question_tokens"], job["answer_tokens"], job["cache_block"]) == (32, 256, 1024)
    mdl = lm.CausalLM.from_config(cfg, 33792, **job["model"])  # 32,768 + 288 in blocks of 1,024
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    variables = on(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return mdl, variables, job, on


def test_index_score_kernel_compiles(one_chip, monkeypatch):
    """`dsa_index` at `deepseek32.decode.32k`'s shapes: 16 rows of 64 index
    heads of 128 against 33,792 cached keys, in blocks of 8,192 positions of
    which the last overhangs the cache."""
    from dalle_pytorch_tpu.ops import index_score as ix, pallas_attention

    for module in (ix, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((16, 64), jnp.float32, sharding=one_chip)
    text = _compile(ix.index_scores, bf16(16, 64, 128), weights, bf16(16, 33792, 128),
                    _i32(one_chip, 16)).as_text()
    assert re.search(r"%dsa_index[.\d]* = f32\[16,1,40960\]", text)
    assert ix.BLOCK_POSITIONS == 8192


def test_selection_and_sparse_attend_compile_without_a_sort(one_chip, monkeypatch):
    """The selection of 2,048 of 33,792 scores a row (threshold by counting,
    compaction by two small products) holds no sort, gather or scatter; the
    sparse attend is ONE gather of the selected positions' rows (the fetch:
    an indexed layer's cache; two out of a two-leaf one) and the dense kernel
    `decode_latent` over them."""
    from dalle_pytorch_tpu.ops import index_select as sel, latent_decode, pallas_attention
    from dalle_pytorch_tpu.ops import sparse_latent_decode as sp

    for module in (latent_decode, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    shape = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def select(scores, lengths):
        mask, count = sel.selected_mask(scores, lengths, 2048)
        return sel.selected_indices(mask, 2048), count

    text = jax.jit(select).lower(shape(jnp.float32, 16, 33792), _i32(one_chip, 16)).compile().as_text()
    assert not re.search(r" (sort|gather|scatter)\(", text)
    bf16 = functools.partial(shape, jnp.bfloat16)
    attend = functools.partial(sp.sparse_latent_decode_attention, sm_scale=0.135)
    for leaves, gathers in (((bf16(16, 33792, 640), None), 1),
                            ((bf16(16, 33792, 512), bf16(16, 64, 33792)), 2)):
        compiled = _compile(attend, bf16(16, 128, 512), bf16(16, 128, 64), *leaves,
                            shape(jnp.int32, 16, 2048), _i32(one_chip, 16))
        text = compiled.as_text()
        assert len(re.findall(r" gather\(", text)) == gathers
        # ONE call over the 2,048 fetched, which the rule walks as one block
        assert len(re.findall(r"%decode_latent[.\d]* = bf16\[16,128,512\]", text)) == 1
        assert latent_decode.even_block(2048, latent_decode.BLOCK_POSITIONS) == 2048
        if gathers == 1:  # fetched where it lies: no copy of the leaf in front of the gather
            assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2**20


def test_sparse_token_loop_compiles_at_the_cells_size(one_chip, monkeypatch, capsys):
    """The token loop of `deepseek32.decode.32k` (16 sessions x 33,792
    positions, 288 steps, top-k 0.9): every layer's two cache leaves (a
    position's latent and rotary key in one row of 640, the index key) ride
    the loop's carry and neither is copied, in the loop or around it (until PR
    41 the compiler kept a positions-major copy of a third leaf, the rotary
    keys, through the loop for their fetch: 346 MB); a step holds ONE gather a
    layer under `mla_attend`, an index-score kernel and a dense-kernel call a
    layer and three grouped products a routed layer; the plan is the weights,
    the cache and 1.1 GB beside them, under the two-leaf program's 13.50 GiB
    (PR 40's tree, compiled here the same way)."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables, job, on = _deepseek_v32(one_chip, monkeypatch)
    cache = on(jax.eval_shape(lambda: mdl.init_cache(16)))
    assert {k: v.shape for k, v in cache["layer_4"]["attn"].items()} == {
        "rows": (16, 33792, 640), "index_k": (16, 33792, 128), "index": ()}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lm._sampler_builder(mdl, (288, 0.9, 1.0, 2)), donate_argnums=(2,)).lower(
        variables, key, cache, i32(16, 32), i32()).compile()
    text, plan = compiled.as_text(), _device_bytes(compiled) / 2**30
    with capsys.disabled():
        print(f"\ndeepseek32.decode.32k sampler: planned {plan:.2f} GiB on the described v5e")
    assert text.count("tpu_custom_call") == 5 + 5 + 12
    assert not re.search(r"= bf16\[16,33792,(640|128)\]\S* copy\(", text)
    assert not re.search(r"bf16\[16,(33792,64|64,33792)\]", text)
    gathers = [line for line in text.splitlines() if re.search(r" gather\(", line)]
    fetches = [line for line in gathers if "mla_attend" in line]
    assert len(fetches) == 5 and all("bf16[16,2048,640]" in line for line in fetches)
    assert 13.0 < plan < 13.4


def test_prefill_chunk_compiles_with_the_cache_at_full_size(one_chip, monkeypatch, capsys):
    """One chunk of 1,024 queries of a document's prefill against a fresh
    cache of 32,768 positions (`lm_extend`): index scores and attention walk
    blocks of 1,024 cached positions up to the chunk's end, so no array of
    queries x positions x heads is ever whole; and the copy of a prefilled
    document to a session (`lm_place`) writes the sessions' cache in place."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables, job, on = _deepseek_v32(one_chip, monkeypatch)
    fresh = on(jax.eval_shape(lambda: mdl.init_cache(1, 32768)))
    tokens = jax.ShapeDtypeStruct((1, job["prefill_chunk"]), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm._extend_builder(mdl, ()), donate_argnums=(2,), keep_unused=True).lower(
        variables, tokens, fresh).compile()
    plan, temp = _device_bytes(compiled) / 2**30, compiled.memory_analysis().temp_size_in_bytes
    cache = on(jax.eval_shape(lambda: mdl.init_cache(16)))
    rows = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    placed = jax.jit(lm._place_builder(mdl, ()), donate_argnums=(0,)).lower(
        cache, fresh, rows).compile()
    # the benchmark loop's set-up check of a document's four copies, beside all that is held
    from benchmark.loops.generate_deepseek_v32 import _copies_off

    rows = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    checked = _copies_off().lower(cache, fresh, rows).compile()
    with capsys.disabled():
        print(f"\ndeepseek32.decode.32k prefill chunk: planned {plan:.2f} GiB "
              f"({temp / 2**30:.2f} of temporaries) on the described v5e; a copy: "
              f"{placed.memory_analysis().temp_size_in_bytes / 2**20:.0f} MiB of temporaries; "
              f"the copies' check: {checked.memory_analysis().temp_size_in_bytes / 2**20:.0f} MiB")
    assert temp < 2 * 2**30 and plan < 11.5
    assert placed.memory_analysis().temp_size_in_bytes < 64 * 2**20
    assert checked.memory_analysis().temp_size_in_bytes < 512 * 2**20


def _nemotron_h(one_chip, monkeypatch, sessions):
    """(model, its variables' and its sessions' cache's shapes on the described
    chip) of `nemotron3.decode.8k`: the cell's own widths and lengths, at
    `sessions` rows (the cell's 192 plan 14.21 GB and take 64 s to compile
    here, PR 43: a step's K/V go in by one `dynamic-update-slice` a row)."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import grouped_decode, grouped_matmul, pallas_attention, ssm_step

    for module in (grouped_decode, grouped_matmul, pallas_attention, ssm_step):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = Path(__file__).resolve().parent.parent / "benchmark"
    cfg = json.loads((root / "configs/nemotron3-nano-30b-ep2.json").read_text())
    job = json.loads((root / "workloads/nemotron3.decode.8k.json").read_text())["job"]
    assert (job["sessions"], max(job["document_tokens"])) == (192, 8192)
    steps = job["question_tokens"] + job["answer_tokens"]
    mdl = lm.CausalLM.from_config(cfg, 8192 + steps, **job.get("model", {}))
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    variables = on(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = on(jax.eval_shape(lambda: mdl.init_cache(sessions)))
    return mdl, variables, cache


def test_state_space_token_loop_compiles_at_the_cells_size(one_chip, monkeypatch, capsys):
    """The token loop of `nemotron3.decode.8k` (the nine layers `MEMEM*EME` at
    the published widths, sessions of up to 8,192 + 256 positions, every row
    at its own index, 256 steps; 24 sessions here, an eighth of the cell's):
    Mosaic takes `ssm_step`'s blocks of a row's 8 groups x 128 x 512 on a state
    [rows, 128, 4096] whose rows are whole lane tiles, the loop's body holds the
    kernel once a Mamba-2 layer and no copy of a state or of the K/V leaves, a
    routed layer is TWO grouped products, and the attention layer's step is
    `decode_grouped` at 16 query rows a K/V head."""
    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import ssm_step

    mdl, variables, cache = _nemotron_h(one_chip, monkeypatch, 24)
    plan = mdl.plan()
    assert [layer.kind for layer in plan] == [
        "ssm", "none", "ssm", "none", "ssm", "full", "none", "ssm", "none"]
    assert sorted(cache) == ["layer_0", "layer_2", "layer_4", "layer_5", "layer_7"]
    attn = cache["layer_0"]["attn"]
    assert attn["state"].shape == (24, 128, 4096) and attn["conv"].shape == (24, 3, 6144)
    assert attn["index"].shape == (24,) and cache["layer_5"]["attn"]["k"].shape == (
        24, 2, 8448, 128)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lm._verify_sampler_builder(mdl, (256, 0.9, 1.0, 2, None)),
                       donate_argnums=(2,)).lower(
        variables, key, cache, i32(24, 32), i32(24)).compile()
    text, gb = compiled.as_text(), _device_bytes(compiled) / 1e9
    with capsys.disabled():
        print(f"\nnemotron3.decode.8k sampler at 24 sessions: planned {gb:.2f} GB")
    # a step: 4 state updates, 4 x 2 grouped products, 1 attention
    assert text.count("tpu_custom_call") == 13
    assert len(re.findall(r"%ssm_step[.\d]* = \(f32\[24,1,4096\]\S*, f32\[24,128,4096\]",
                          text)) == 4
    assert len(re.findall(r"%gmm_fwd[.\d]* = ", text)) == 8
    assert len(re.findall(r"%decode_grouped[.\d]* = bf16\[24,2,16,128\]", text)) == 1
    assert not re.search(r"= f32\[24,128,4096\]\S* copy\(", text)
    assert not re.search(r"= bf16\[24,2,8448,128\]\S* copy\(", text)
    assert ssm_step.GROUPS_PER_BLOCK == 8
    assert gb < HBM_BYTES / 1e9


def _zaya(one_chip, monkeypatch, depth=None):
    """(model, its variables' and its sessions' cache's shapes on the described
    chip) of `zaya1.decode.8k`: the cell's own widths, lengths and 24 sessions,
    at the configuration's 20 layers or the first `depth` of them."""
    import json
    from pathlib import Path

    from dalle_pytorch_tpu.models import lm
    from dalle_pytorch_tpu.ops import grouped_decode, grouped_matmul, pallas_attention

    for module in (grouped_decode, grouped_matmul, pallas_attention):
        monkeypatch.setattr(module, "_use_interpret", lambda: False)
    root = Path(__file__).resolve().parent.parent / "benchmark"
    cfg = json.loads((root / "configs/zaya1-8b-pp2.json").read_text())
    job = json.loads((root / "workloads/zaya1.decode.8k.json").read_text())["job"]
    assert (job["sessions"], job["document_tokens"]) == (24, 8192)
    steps = job["question_tokens"] + job["answer_tokens"]
    mdl = lm.CausalLM.from_config(dict(cfg, num_hidden_layers=depth or cfg["num_hidden_layers"]),
                                  8192 + steps, **job.get("model", {}))
    on = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    variables = on(jax.eval_shape(mdl.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    cache = on(jax.eval_shape(lambda: mdl.init_cache(24)))
    return mdl, variables, cache, steps


def test_convolved_token_loop_compiles_at_the_cells_size(one_chip, monkeypatch, capsys):
    """The token loop of `zaya1.decode.8k` (20 layers at the published widths,
    24 sessions of 8,192 + 256 positions, every row at its own index, 256
    steps): a layer's step is `decode_grouped` at 4 query rows a K/V head over 2
    K/V heads of 128 and THREE grouped products, the K/V leaves ride the loop
    with no copy, the tail is 21 whole lane tiles a row, and the plan (9.4 GB of
    weights, 4.16 GB of cache, 0.54 GB of kept logits) leaves the chip room."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables, cache, steps = _zaya(one_chip, monkeypatch)
    assert {layer.kind for layer in mdl.plan()} == {"cca"} and len(cache) == 20
    attn = cache["layer_0"]["attn"]
    assert attn["k"].shape == (24, 2, 8448, 128) and attn["tail"].shape == (24, 21 * 128)
    assert "logits_dense" not in variables["params"]  # the head is the embedding
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lm._verify_sampler_builder(mdl, (steps, 0.9, 1.0, 2, None)),
                       donate_argnums=(2,)).lower(
        variables, key, cache, i32(24, 32), i32(24)).compile()
    text, gb = compiled.as_text(), _device_bytes(compiled) / 1e9
    with capsys.disabled():
        print(f"\nzaya1.decode.8k sampler at 24 sessions: planned {gb:.2f} GB")
    assert text.count("tpu_custom_call") == 80
    assert len(re.findall(r"%gmm_fwd[.\d]* = ", text)) == 60
    assert len(re.findall(r"%decode_grouped[.\d]* = bf16\[24,2,4,128\]", text)) == 20
    assert not re.search(r"= bf16\[24,2,8448,128\]\S* copy\(", text)
    assert gb < 14.6  # 14.15 planned (PR 47); the chip's loader takes 15.75 GiB


def test_prefill_of_one_document_compiles_for_the_convolved_layers(one_chip, monkeypatch, capsys):
    """The prefill of one 8,192-token document into the sessions' cache, the
    first four layers at the published widths (the 20 plan 15.07 GB with every
    weight held: CPU compile for the described chip, PR 47): the flash kernel
    takes 8 query heads over 2 K/V heads of 128 head-major, and `gmm_fwd` a
    buffer of 8,192 rows over 16 experts of 2,048 x 2,048."""
    from dalle_pytorch_tpu.models import lm

    mdl, variables, cache, _ = _zaya(one_chip, monkeypatch, depth=4)
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    row = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lm._prefill_builder(mdl, ()), donate_argnums=(2,), keep_unused=True).lower(
        variables, tokens, cache, row).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%fwd_flash[.\d]* = ", text)) == 4
    # three grouped products a layer but the last, whose routed sublayer feeds
    # nothing that a prefill keeps (no logits: the cache alone)
    assert len(re.findall(r"%gmm_fwd[.\d]* = ", text)) == 9
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
