"""Pallas flash-attention kernel vs the dense-masked oracle.

Mirrors the test the reference never had for its DeepSpeed CUDA block-sparse
kernel (`/root/reference/dalle_pytorch/attention.py:339-398`): every mask
pattern the framework uses is checked against `dense_attention` on the same
mask, forward and backward. Runs in Pallas interpret mode on CPU.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.ops.attention_core import dense_attention
from dalle_pytorch_tpu.ops import pallas_attention as pa
from dalle_pytorch_tpu.ops.pallas_attention import (
    flash_attention,
    mask_block_layout,
)
from dalle_pytorch_tpu.ops.masks import (
    axial_static_mask,
    block_layout_to_token_mask,
    block_sparse_layout,
    causal_mask,
    conv_like_mask,
)

B, H, D = 2, 3, 32


def _qkv(n, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(B, H, n, D), dtype) for _ in range(3)
    )


def _dense(q, k, v, mask):
    return dense_attention(q, k, v, mask=jnp.asarray(mask)[None, None])


def test_causal_no_mask_matches_dense():
    n = 192
    q, k, v = _qkv(n)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = _dense(q, k, v, causal_mask(n))
    np.testing.assert_allclose(out, ref, atol=2e-5)


@pytest.mark.parametrize("pattern", ["axial_row", "axial_col", "conv", "sparse"])
def test_static_masks_match_dense(pattern):
    fmap, text = 8, 16
    n = text + fmap * fmap  # 80
    if pattern in ("axial_row", "axial_col"):
        mask = axial_static_mask(n - 1, fmap, axis=0 if pattern == "axial_row" else 1)
    elif pattern == "conv":
        mask = conv_like_mask(n - 1, fmap, kernel_size=3)
    else:
        layout = block_sparse_layout(n, block=16, global_block_indices=(0,), seed=3)
        mask = block_layout_to_token_mask(layout, 16)
    mask = mask[:n, :n] & causal_mask(n)
    q, k, v = _qkv(n, seed=1)
    out = flash_attention(q, k, v, mask=mask, causal=False, block_q=32, block_k=32)
    ref = _dense(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_ragged_seq_padding():
    n = 100  # not a multiple of the block size
    q, k, v = _qkv(n, seed=2)
    mask = causal_mask(n)
    out = flash_attention(q, k, v, mask=mask, causal=False, block_q=32, block_k=32)
    ref = _dense(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_rectangular_causal_nk_gt_nq():
    """n_k > n_q with causal=True: k blocks past the last q row are fully
    dead; the DMA-skip clamp must stay in range (regression: the dk/dv
    first-live-q index could point past the last q block, an out-of-bounds
    tile read) and dk/dv for those rows must be exactly zero."""
    n_q, n_k = 64, 160
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, n_q, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, n_k, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, n_k, D), jnp.float32)
    mask = np.arange(n_q)[:, None] >= np.arange(n_k)[None, :]

    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _dense(q, k, v, mask)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, mask=jnp.asarray(mask)[None, None]) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-4)
    # fully-dead k rows (beyond the last q row) get exactly zero dk/dv
    assert np.all(np.asarray(gf[1])[:, :, n_q:, :] == 0)
    assert np.all(np.asarray(gf[2])[:, :, n_q:, :] == 0)


def test_gradients_match_dense_causal():
    n = 96
    q, k, v = _qkv(n, seed=3)
    mask = causal_mask(n)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=32, block_k=32) ** 2).sum()

    def loss_dense(q, k, v):
        return (_dense(q, k, v, mask) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-4)


def test_gradients_match_dense_masked_ragged():
    n = 72
    q, k, v = _qkv(n, seed=4)
    rng = np.random.RandomState(0)
    mask = causal_mask(n)
    mask &= rng.rand(n, n) > 0.3
    np.fill_diagonal(mask, True)  # keep every row non-empty

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, mask=mask, causal=False, block_q=32, block_k=32) ** 3).sum()

    def loss_dense(q, k, v):
        return (_dense(q, k, v, mask) ** 3).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-4)


def test_bf16_inputs():
    n = 64
    q, k, v = _qkv(n, seed=5, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = _dense(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal_mask(n),
    )
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=3e-2)


def test_empty_query_row_rejected():
    mask = causal_mask(64)
    mask[10, :] = False  # query 10 can attend to nothing
    q, k, v = _qkv(64, seed=6)
    with pytest.raises(ValueError, match="fully-masked query"):
        flash_attention(q, k, v, mask=mask, causal=False, block_q=32, block_k=32)


def test_flash_rejects_dynamic_key_mask():
    from dalle_pytorch_tpu.models.attention import Attention

    x = jnp.zeros((2, 16, 32))
    attn = Attention(dim=32, seq_len=16, heads=2, dim_head=16, attn_impl="flash")
    params = attn.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="key-padding"):
        attn.apply(params, x, key_mask=jnp.ones((2, 16), bool))


def test_block_layout_skips_empty_tiles():
    mask = np.zeros((64, 64), dtype=bool)
    mask[:, :16] = True  # every query attends only within the first k block
    _, layout = mask_block_layout(mask, 16, 16)
    assert layout.shape == (4, 4)
    assert (layout[:, 0] == 1).all() and layout.sum() == 4


def test_attention_module_flash_matches_dense():
    from dalle_pytorch_tpu.models.attention import Attention

    n, dim = 80, 64
    x = jnp.asarray(np.random.RandomState(7).randn(2, n, dim), jnp.float32)
    static = axial_static_mask(n - 1, 8, axis=0)[:n, :n]
    kw = dict(dim=dim, seq_len=n, heads=4, dim_head=16, causal=True, static_mask=static)
    dense_attn = Attention(**kw, attn_impl="dense")
    flash_attn = Attention(**kw, attn_impl="flash")
    params = dense_attn.init(jax.random.PRNGKey(0), x)
    out_d, _ = dense_attn.apply(params, x)
    out_f, _ = flash_attn.apply(params, x)
    np.testing.assert_allclose(out_f, out_d, atol=2e-5)


# ---------------------------------------------------------- chosen tiles


def _flagship_axial_mask(n):
    return axial_static_mask(n - 1, 32, axis=0)[:n, :n] & causal_mask(n)


# (batch, heads, n, d, dtype, masked): the flagship in its stated dtype and
# in float32, the auto threshold's length, a ragged and a short one, a
# patterned layer, and what one shard of a dp=4 x tp=4 mesh is handed
TILE_CASES = {
    "flagship_bf16": (1, 1, 1280, 64, jnp.bfloat16, False),
    "flagship_f32": (1, 1, 1280, 64, jnp.float32, False),
    "auto_threshold": (1, 2, 1024, 64, jnp.float32, False),
    "ragged": (1, 2, 257, 64, jnp.float32, False),
    "short": (2, 3, 96, 32, jnp.float32, False),
    "axial_layout": (1, 1, 1280, 64, jnp.float32, True),
    "per_shard": (4, 4, 1280, 64, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", TILE_CASES)
def test_chosen_tiles_are_legal_and_exact(case):
    """The tiles `flash_attention` picks when none are given: they divide
    the padded length, meet the (8, 128) tiling, fit the VMEM figure, a
    multiple of 128 is never padded, and forward and gradients at those
    tiles agree with dense attention on operands of the same dtype."""
    b, h, n, d, dtype, masked = TILE_CASES[case]
    bq, bk = pa.choose_tiles(n, n, d, dtype, masked=masked)  # as `flash_attention` asks
    for block in (bq, bk):
        n_pad = -(-n // block) * block
        assert n_pad % block == 0 and n_pad - n < 128
        assert block % 128 == 0 or (block == n and n <= 128)
        if n % 128 == 0:
            assert n_pad == n  # 1280 = 10 x 128 takes no pad copy
    itemsize = jnp.dtype(dtype).itemsize
    n_pad = -(-n // bq) * bq
    span_q, span_k = pa._spans(n_pad, n_pad, bq, bk, d, itemsize)
    assert n_pad % span_q == 0 and span_q % bq == 0
    assert n_pad % span_k == 0 and span_k % bk == 0
    assert pa.vmem_bytes(bq, bk, span_q, span_k, d, itemsize, masked) <= pa.VMEM_BUDGET

    mask = _flagship_axial_mask(n) if masked else None
    if masked:
        padded, layout = mask_block_layout(mask, bq, bk)
        assert layout.shape == (n_pad // bq, n_pad // bk)
        assert padded.shape == (n_pad, n_pad)
        assert 0 < layout.sum() < layout.size  # the pattern leaves empty tiles

    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(b, h, n, d), dtype) for _ in range(3))
    dense_mask = jnp.asarray(causal_mask(n) if mask is None else mask)[None, None]

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, mask=mask, causal=mask is None)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def loss_dense(q, k, v):
        out = dense_attention(q, k, v, mask=dense_mask)
        return (out.astype(jnp.float32) ** 2).sum(), out

    pa.forget()
    (_, out), gf = jax.value_and_grad(loss_flash, (0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), gd = jax.value_and_grad(loss_dense, (0, 1, 2), has_aux=True)(q, k, v)
    assert set(pa.tiles_chosen.values()) == {(bq, bk)}
    assert out.dtype == dtype
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    if dtype == jnp.float32:
        np.testing.assert_allclose(out, ref, atol=2e-5)
        for a, g in zip(gf, gd):
            np.testing.assert_allclose(a, g, atol=5e-4)
    else:  # against dense attention on bf16 operands, at bf16's resolution
        np.testing.assert_allclose(f32(out), f32(ref), atol=3e-2)
        for a, g in zip(gf, gd):
            assert np.linalg.norm(f32(a) - f32(g)) < 2e-2 * np.linalg.norm(f32(g))


def test_explicit_tiles_override_the_choice():
    q, k, v = _qkv(96, seed=8)
    pa.forget()
    flash_attention(q, k, v, block_q=32, block_k=64)
    assert set(pa.tiles_chosen.values()) == {(32, 64)}


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (jitted callees, kernels, loop bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kernel_dots(fn, *args):
    """(lhs dtype, rhs dtype, result dtype) of every dot in the kernels of
    every pallas_call `fn` traces."""
    return [
        tuple(str(x.aval.dtype) for x in (*eqn.invars, eqn.outvars[0]))
        for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "dot_general"
    ]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_mxu_operands_keep_the_tensors_dtype(dtype, grad):
    """bf16 tensors multiply as bf16 (one MXU pass), float32 tensors as
    float32 (the parity tests' precision); every product accumulates in
    float32 whatever its operands."""
    q, k, v = _qkv(64, dtype=jnp.dtype(dtype))
    fn = lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum()
    if grad:
        fn = jax.grad(fn, (0, 1, 2))
    pa.forget()
    dots = _kernel_dots(fn, q, k, v)
    assert len(dots) == (2 + 3 + 4 if grad else 2)  # fwd; + dq, dkv
    assert set(dots) == {(dtype, dtype, "float32")}


def _layers(x, depth, remat, mask=None):
    """`depth` attention layers over [B, H, N, D], each under
    `jax.checkpoint` as the trainer's remat executor runs them."""
    layer = lambda x: x + flash_attention(x, x, x, mask=mask, causal=mask is None)
    if remat:
        layer = jax.checkpoint(layer)
    for _ in range(depth):
        x = layer(x)
    return x.sum()


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_a_program_builds_each_kernel_body_once(remat):
    """Six layers, forward and backward: 3 kernel bodies traced where each
    call site used to build its own (18). Under remat 4 (and not 24): the
    forward is traced once inside `jax.checkpoint`'s own tracing context
    and once from the VJP's forward rule, which JAX keys apart. As many
    `pallas_call`s in the program as before."""
    x = _qkv(64, seed=9)[0]
    pa.forget()
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: _layers(x, 6, remat)))(x)
    assert pa.kernel_bodies == 3 + remat
    assert {kind for kind, *_ in pa.tiles_chosen} == {"fwd", "dq", "dkv"}
    assert set(pa.tiles_chosen.values()) == {(64, 64)}

    calls = sum(e.primitive.name == "pallas_call" for e in _eqns(jaxpr.jaxpr))
    assert calls == 6 * (3 + remat)
    # a second program of the same shapes builds nothing anew
    jax.make_jaxpr(jax.grad(lambda x: _layers(x, 2, remat)))(x)
    assert pa.kernel_bodies == 3 + remat


def test_masked_flash_trains_under_remat():
    """A patterned layer under `jax.checkpoint`: the padded mask and its
    layout reach the kernels as host constants, so no tracer of the
    checkpoint's trace is left in the VJP's closures (it used to leak)."""
    n = 80
    mask = axial_static_mask(n - 1, 8, axis=0)[:n, :n] & causal_mask(n)
    x = _qkv(n, seed=10)[0]
    got = jax.jit(jax.grad(lambda x: _layers(x, 2, True, mask=mask)))(x)

    def dense_layers(x):
        for _ in range(2):
            x = x + _dense(x, x, x, mask)
        return x.sum()

    want = jax.grad(dense_layers)(x)
    np.testing.assert_allclose(got, want, atol=5e-4)


# ------------------------------------------- grouped K/V heads and a window


def _grouped_dense(q, k, v, window):
    """Dense float32 oracle: query head j reads K/V head j // group; key p is
    seen by query t iff 0 <= t - p < window."""
    n, group = q.shape[2], q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    t, p = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    live = (p <= t) & ((t - p < window) if window else True)
    return dense_attention(q, k, v, mask=live[None, None])


def _grouped_qkv(n, heads, kv_heads, seed=0):
    rng = np.random.RandomState(seed)
    q, w = (jnp.asarray(rng.randn(2, heads, n, 16), jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, kv_heads, n, 16), jnp.float32) for _ in range(2))
    return q, k, v, w


@pytest.mark.parametrize("n,heads,kv_heads,window,blocks,budget", [
    (32, 4, 2, 8, (None, None), None),       # the small model's layer
    (300, 4, 2, 40, (128, 128), None),       # a length the tile does not divide
    (300, 4, 1, None, (128, 128), None),     # groups alone
    (512, 2, 2, 100, (128, 128), None),      # a window alone
    (1024, 4, 2, 130, (128, 128), 200 << 10),  # several spans: the DMA skip
    (1000, 4, 2, 300, (128, 256), 200 << 10),  # both, ragged, oblong tiles
])
def test_window_and_grouped_heads_match_dense_forward_and_backward(
        monkeypatch, n, heads, kv_heads, window, blocks, budget):
    if budget:  # so little VMEM that a row is held in several spans
        monkeypatch.setattr(pa, "VMEM_BUDGET", budget)
        assert pa._spans(n, n, *blocks, 16, 4)[0] < n
    q, k, v, w = _grouped_qkv(n, heads, kv_heads)
    attn = lambda q, k, v: flash_attention(
        q, k, v, window=window, block_q=blocks[0], block_k=blocks[1])
    np.testing.assert_allclose(attn(q, k, v), _grouped_dense(q, k, v, window), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(attn(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_dense(*a, window) * w), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, r, atol=5e-5, err_msg=name)


def test_a_window_as_long_as_the_sequence_is_plain_causal():
    q, k, v, _ = _grouped_qkv(96, 2, 2)
    np.testing.assert_allclose(
        flash_attention(q, k, v, window=96), flash_attention(q, k, v), atol=1e-6)


def test_window_and_groups_refuse_what_they_cannot_do():
    q, k, v, _ = _grouped_qkv(64, 4, 2)
    with pytest.raises(AssertionError, match="cannot share"):
        flash_attention(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1))
    with pytest.raises(AssertionError, match="window"):
        flash_attention(q, k, v, window=8, causal=False)
    with pytest.raises(AssertionError, match="window"):
        flash_attention(q, k, v, window=8, mask=np.tril(np.ones((64, 64), bool)))


# ------------------------------------------------------- token-major entry
#
# q [B, N, Hq, D], k/v [B, N, Hkv, D] as the projection writes them and the
# result as `to_out` reads it: the same kernel bodies behind a second family
# of index maps, held here to the head-major entry (which the tests above
# hold to dense attention).

# (d, query heads, K/V heads, n, window, masked, tiles, VMEM budget): a pair
# of 64-wide heads a 128-lane block, four of 32, a 128-wide head a block;
# one K/V head per query head and eight query heads over one; lengths the
# tile does not divide; rows held in several spans (the DMA skip's clamps)
TOKEN_CASES = {
    "pair_causal_ragged": (64, 4, 4, 200, None, False, (64, 64), None),
    "pair_window": (64, 4, 4, 256, 100, False, (64, 128), None),
    "pair_mask": (64, 2, 2, 96, None, True, (32, 32), None),
    "pair_spans": (64, 2, 2, 1024, 130, False, (128, 128), 1200 << 10),
    "quad_causal": (32, 4, 4, 96, None, False, (32, 32), None),
    "one_causal_ragged": (128, 2, 2, 200, None, False, (64, 64), None),
    "one_grouped_window": (128, 8, 1, 256, 100, False, (64, 64), None),
    "one_grouped_ragged": (128, 8, 1, 200, None, False, (128, 128), None),
    "one_grouped_spans": (128, 4, 2, 1000, 300, False, (128, 256), 1600 << 10),
    "one_mask": (128, 2, 2, 96, None, True, (32, 32), None),
}


def _token_mask(n):
    mask = np.tril(np.ones((n, n), bool))
    mask[:, n // 3: n // 2] = False
    np.fill_diagonal(mask, True)
    return mask


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", TOKEN_CASES)
def test_token_major_entry_equals_the_head_major_one(monkeypatch, case, dtype):
    """Output and all three gradients of `layout="token_major"` against the
    head-major entry on the transposed operands: the same products in the
    same order, so float32 agrees to rounding of the sums and bf16 to a
    unit of its own resolution. `layouts_built` says which index maps each
    of the three bodies got."""
    d, hq, hkv, n, window, masked, blocks, budget = TOKEN_CASES[case]
    if budget:
        monkeypatch.setattr(pa, "VMEM_BUDGET", budget)
        width, size = d * pa.heads_per_block(d, hq, hkv), jnp.dtype(dtype).itemsize
        assert max(pa._spans(n, n, *blocks, width, size)) < n
    rng = np.random.RandomState(3)
    q, w = (jnp.asarray(rng.randn(2, hq, n, d), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, hkv, n, d), dtype) for _ in range(2))
    kw = dict(window=window, mask=_token_mask(n) if masked else None,
              block_q=blocks[0], block_k=blocks[1])
    t = lambda x: x.transpose(0, 2, 1, 3)
    f32 = lambda x: x.astype(jnp.float32)

    def head(q, k, v):
        out = flash_attention(q, k, v, **kw)
        return (f32(out) * f32(w)).sum(), out

    def token(q, k, v):
        out = t(flash_attention(t(q), t(k), t(v), layout=pa.TOKEN_MAJOR, **kw))
        return (f32(out) * f32(w)).sum(), out

    pa.forget()
    (_, want), grads_want = jax.value_and_grad(head, (0, 1, 2), has_aux=True)(q, k, v)
    assert pa.layouts_built == {pa.HEAD_MAJOR: 3}
    pa.forget()
    (_, got), grads_got = jax.value_and_grad(token, (0, 1, 2), has_aux=True)(q, k, v)
    assert pa.layouts_built == {pa.TOKEN_MAJOR: 3}
    assert set(pa.tiles_chosen.values()) == {blocks}
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    for a, b, name in zip((got, *grads_got), (want, *grads_want), ("o", "dq", "dk", "dv")):
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
        else:  # a sum rounded once to bf16 on either side
            assert np.linalg.norm(f32(a) - f32(b)) < 1e-2 * np.linalg.norm(f32(b)), name


def test_heads_per_block_follows_the_head_size():
    """A head of 128 lanes is a column block; narrower heads share one (a
    pair at 64) when K/V heads are as many as query heads and fill whole
    blocks; nothing else is token-major, and the entry says so."""
    assert pa.heads_per_block(128, 32, 4) == 1
    assert pa.heads_per_block(256, 8, 8) == 1
    assert pa.heads_per_block(64, 16, 16) == 2
    assert pa.heads_per_block(32, 8, 8) == 4
    assert pa.heads_per_block(64, 16, 4) is None  # a pair would straddle K/V heads
    assert pa.heads_per_block(64, 3, 3) is None   # half a block left over
    assert pa.heads_per_block(192, 4, 4) is None and pa.heads_per_block(48, 8, 8) is None
    q = jnp.zeros((1, 64, 4, 64))
    with pytest.raises(AssertionError, match="head_major"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], layout=pa.TOKEN_MAJOR)
    with pytest.raises(AssertionError):
        flash_attention(q, q, q, layout="rows")


def test_token_major_tiles_plan_for_the_block_not_the_head():
    """The flagship's call: 16 heads of 64 are 8 blocks of 128 lanes, and
    the tiles are those of a 128-wide head (the operands in VMEM are twice
    as wide as a head-major call's)."""
    x = jax.ShapeDtypeStruct((1, 1280, 2, 64), jnp.bfloat16)
    pa.forget()
    jax.eval_shape(lambda x: flash_attention(x, x, x, layout=pa.TOKEN_MAJOR), x)
    want = pa.choose_tiles(1280, 1280, 128, jnp.bfloat16)
    assert set(pa.tiles_chosen.values()) == {want}
    assert pa.layouts_built == {pa.TOKEN_MAJOR: 1}


def _module_pair(**kw):
    from dalle_pytorch_tpu.models.attention import Attention

    return Attention(**kw, attn_impl="dense"), Attention(**kw, attn_impl="flash")


def _module_grads(attn, params, x, **call):
    def loss(params, x):
        out, _ = attn.apply(params, x, **call)
        return (out ** 2).sum()

    return jax.grad(loss, (0, 1))(params, x)


@pytest.mark.parametrize("path", ["dalle_pair", "dalle_masked", "dalle_128", "grouped",
                                  "grouped_whole_norm", "head_major_fallback"])
def test_attention_module_flash_keeps_its_operands_token_major(path):
    """`attn_impl="flash"` against `"dense"` through the whole module,
    output, parameter and input gradients. The DALL-E path (angle-table
    rotary on q, k and v, each channel its own angle; a static mask) at head
    sizes 64 and 128 builds token-major bodies alone, its rotary the one-pass
    kernel on `[B, n, heads x dh]`, held here to `apply_rotary` on
    `[B, heads, n, dh]`. The grouped path (q/k norm, rotate-half rotary on q
    and k, a window, K/V heads shared) and a module whose heads fill no
    column block stay head-major."""
    from dalle_pytorch_tpu.ops.rotary import rotary_cos_sin

    n, dim = 80, 48
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, n, dim), jnp.float32)
    call, layout = {}, pa.TOKEN_MAJOR
    if path.startswith("dalle") or path == "head_major_fallback":
        heads, dh = {"head_major_fallback": (3, 64), "dalle_128": (2, 128)}.get(path, (2, 64))
        kw = dict(dim=dim, seq_len=n, heads=heads, dim_head=dh, causal=True)
        if path == "dalle_masked":
            kw["static_mask"] = axial_static_mask(n - 1, 8, axis=0)[:n, :n]
        if path == "head_major_fallback":
            layout = pa.HEAD_MAJOR  # three heads of 64 leave half a block over
        call["rotary"] = jnp.asarray(rng.randn(n, 24), jnp.float32)
    else:
        layout = pa.HEAD_MAJOR
        kw = dict(dim=dim, seq_len=n, heads=4, dim_head=128, kv_heads=2, causal=True,
                  window=24, use_bias=False,
                  qk_norm="whole" if path == "grouped_whole_norm" else True)
        call["rotary_cs"] = rotary_cos_sin(
            np.arange(n), {"type": "default", "dim": 128, "theta": 10000.0})
    dense_attn, flash_attn = _module_pair(**kw)
    params = dense_attn.init(jax.random.PRNGKey(0), x, **call)
    # gains off 1 so that a norm applied on the wrong axis would show
    params = jax.tree.map(lambda p: p * (1 + 0.1 * rng.randn(*p.shape).astype(p.dtype)), params)
    want, _ = dense_attn.apply(params, x, **call)
    pa.forget()
    got, _ = flash_attn.apply(params, x, **call)
    np.testing.assert_allclose(got, want, atol=5e-5)
    grads_got = _module_grads(flash_attn, params, x, **call)
    assert pa.layouts_built == {layout: 3}
    grads_want = _module_grads(dense_attn, params, x, **call)
    for a, b in zip(jax.tree.leaves(grads_got), jax.tree.leaves(grads_want)):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=1e-3)


def test_token_major_module_under_a_mesh_takes_its_heads_columns_of_q_k_and_v():
    """Under `train_mesh` the kernels run in a shard_map over batch and
    heads. Token-major, a shard's operand is its heads' columns of all three
    of q, k and v out of the fused projection (the `[B, n, 3, heads, dh]`
    view split on the head axis), turned and attended there: equal to the
    module without a mesh, values and gradients, and built token-major (two
    heads of 64 a shard are one column block; a fallback to head-major
    would pass the values and fail the counter)."""
    from dalle_pytorch_tpu.models.attention import Attention
    from dalle_pytorch_tpu.parallel import make_mesh

    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    n, dim = 48, 32
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, n, dim), jnp.float32)
    rotary = jnp.asarray(rng.randn(n, 20), jnp.float32)
    kw = dict(dim=dim, seq_len=n, heads=4, dim_head=64, causal=True, attn_impl="flash")
    plain, sharded = Attention(**kw), Attention(**kw, train_mesh=mesh)
    params = plain.init(jax.random.PRNGKey(0), x, rotary=rotary)
    want = jax.jit(lambda p, x: _module_grads(plain, p, x, rotary=rotary))(params, x)
    pa.forget()
    with mesh:
        got = jax.jit(lambda p, x: _module_grads(sharded, p, x, rotary=rotary))(params, x)
    assert pa.layouts_built == {pa.TOKEN_MAJOR: 3}
    # a shard's bodies see its own rows and heads: 8 rows over dp x fsdp, 4 heads over tp
    assert {key[1] for key in pa.tiles_chosen} == {(2, n, 2 * 64)}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_token_major_module_holds_no_head_transpose():
    """On the uncached flash path nothing between `to_qkv` and `to_out` is
    transposed: the module's jaxpr has no `transpose` of a [.., heads, dh]
    array, forward or backward (the dense path has eight; the backward's
    row sums `delta`, two numbers a row and block, are still laid out as
    the kernel wrote `lse`)."""
    n, dim = 64, 32
    x = jnp.zeros((2, n, dim), jnp.float32)
    rotary = jnp.zeros((n, 32), jnp.float32)
    dense_attn, flash_attn = _module_pair(dim=dim, seq_len=n, heads=2, dim_head=64, causal=True)
    params = dense_attn.init(jax.random.PRNGKey(0), x, rotary=rotary)

    def transposes(attn):
        fn = lambda p, x: _module_grads(attn, p, x, rotary=rotary)
        return [
            eqn for eqn in _eqns(jax.make_jaxpr(fn)(params, x).jaxpr)
            if eqn.primitive.name == "transpose" and eqn.invars[0].aval.shape[-1] == 64
        ]

    assert len(transposes(dense_attn)) >= 8
    assert transposes(flash_attn) == []
