"""Scan-executor parity: `executor="scan"` must be math-identical to the
default unrolled executor on its supported configs, with checkpoint
interop both ways (`scan_params_to_unrolled` / `unrolled_params_to_scan`).

The scan executor exists for compile time (one layer body in the HLO
instead of `depth` copies); these tests pin that it changes NOTHING else.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.decode_cache import PER_LAYER, STACKED
from dalle_pytorch_tpu.models.transformer import (
    Transformer,
    scan_params_to_unrolled,
    unrolled_params_to_scan,
)
from dalle_pytorch_tpu.models.dalle import DALLE, generate_images_cached

FMAP = 3
SEQ = 4 + FMAP * FMAP  # text_len (incl bos) 4, image 9
DIM, DEPTH = 32, 3


def pair(**kw):
    base = dict(
        dim=DIM, depth=DEPTH, seq_len=SEQ, heads=2, dim_head=8,
        image_fmap_size=FMAP, rotary_emb=True, shift_tokens=True,
    )
    base.update(kw)
    return (
        Transformer(executor="unrolled", **base),
        Transformer(executor="scan", **base),
    )


def x_input():
    return jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, DIM))


class TestScanParity:
    @pytest.mark.parametrize(
        "kw",
        [
            {},
            {"sandwich_norm": True},
            {"stable": True},
            {"rotary_emb": False, "shift_tokens": False},
            {"reversible": True},  # remat-in-scan
            {"reversible": True,
             "remat_policy": "dots_with_no_batch_dims_saveable"},
        ],
    )
    def test_output_matches_unrolled(self, kw):
        unr, scn = pair(**kw)
        x = x_input()
        vu = unr.init(jax.random.PRNGKey(1), x)
        vs = {"params": unrolled_params_to_scan(vu["params"], DEPTH)}
        out_u = unr.apply(vu, x)
        out_s = scn.apply(vs, x)
        np.testing.assert_allclose(
            np.asarray(out_u), np.asarray(out_s), rtol=2e-5, atol=2e-5
        )

    def test_reverse_model_matches(self):
        unr, scn = pair()
        x = x_input()
        vu = unr.init(jax.random.PRNGKey(1), x)
        vs = {"params": unrolled_params_to_scan(vu["params"], DEPTH)}
        out_u = unr.apply(vu, x, reverse_model=True)
        out_s = scn.apply(vs, x, reverse_model=True)
        np.testing.assert_allclose(
            np.asarray(out_u), np.asarray(out_s), rtol=2e-5, atol=2e-5
        )
        # and reverse != forward (sanity that the flag acted)
        assert not np.allclose(np.asarray(out_s), np.asarray(scn.apply(vs, x)))

    def test_grad_matches_unrolled(self):
        unr, scn = pair(reversible=True)
        x = x_input()
        vu = unr.init(jax.random.PRNGKey(1), x)

        def loss_u(p):
            return unr.apply({"params": p}, x).astype(jnp.float32).sum()

        def loss_s(p):
            return scn.apply({"params": p}, x).astype(jnp.float32).sum()

        gu = jax.grad(loss_u)(vu["params"])
        gs = jax.grad(loss_s)(unrolled_params_to_scan(vu["params"], DEPTH))
        # compare on the unrolled layout
        gs_unrolled = scan_params_to_unrolled(gs, DEPTH)
        flat_u = jax.tree_util.tree_leaves_with_path(gu)
        flat_s = dict(
            (jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_leaves_with_path(gs_unrolled)
        )
        assert len(flat_u) == len(flat_s)
        for k, v in flat_u:
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(flat_s[jax.tree_util.keystr(k)]),
                rtol=1e-4, atol=1e-4,
            )

    def test_conversion_round_trip(self):
        _, scn = pair()
        x = x_input()
        vs = scn.init(jax.random.PRNGKey(1), x)
        back = unrolled_params_to_scan(
            scan_params_to_unrolled(vs["params"], DEPTH), DEPTH
        )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            vs["params"], back,
        )

    @pytest.mark.parametrize(
        "kw, msg",
        [
            ({"attn_types": ("axial_row",), "attn_impl": "flash"}, "masked"),
            ({"shared_attn_ids": (0, 0, 0)}, "sharing"),
            ({"reversible": True, "reversible_impl": "revnet"}, "revnet"),
        ],
    )
    def test_unsupported_configs_raise(self, kw, msg):
        _, scn = pair(**{k: v for k, v in kw.items()})
        with pytest.raises(ValueError, match=msg):
            scn.init(jax.random.PRNGKey(1), x_input())

    @pytest.mark.parametrize(
        "attn_types",
        [
            ("axial_row",),
            ("full", "axial_row", "axial_col", "conv_like"),
            ("sparse",),
        ],
    )
    def test_attn_type_cycling_matches_unrolled(self, attn_types):
        # masked attn types run as dense + depth-stacked scanned pattern
        # masks; every cycled layout must be bit-comparable with the
        # unrolled executor's per-layer static masks
        unr, scn = pair(attn_types=attn_types)
        x = x_input()
        vu = unr.init(jax.random.PRNGKey(1), x)
        vs = {"params": unrolled_params_to_scan(vu["params"], DEPTH)}
        out_u = unr.apply(vu, x)
        out_s = scn.apply(vs, x)
        np.testing.assert_allclose(
            np.asarray(out_u), np.asarray(out_s), rtol=2e-5, atol=2e-5
        )

    def test_attn_type_cycling_grads_match(self):
        unr, scn = pair(attn_types=("full", "axial_row"), reversible=True)
        x = x_input()
        vu = unr.init(jax.random.PRNGKey(1), x)

        def loss_u(p):
            return unr.apply({"params": p}, x).astype(jnp.float32).sum()

        def loss_s(p):
            return scn.apply({"params": p}, x).astype(jnp.float32).sum()

        gu = jax.grad(loss_u)(vu["params"])
        gs = scan_params_to_unrolled(
            jax.grad(loss_s)(unrolled_params_to_scan(vu["params"], DEPTH)),
            DEPTH,
        )
        flat_s = dict(
            (jax.tree_util.keystr(k), v)
            for k, v in jax.tree_util.tree_leaves_with_path(gs)
        )
        for k, v in jax.tree_util.tree_leaves_with_path(gu):
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(flat_s[jax.tree_util.keystr(k)]),
                rtol=1e-4, atol=1e-4,
            )


PATTERNS = ("full", "axial_row", "axial_col", "conv_like")


class TestScanCachedChunks:
    """What the scan executor's in-place cache write must carry, below the
    samplers: the depth-stacked cache rides the layer scan's carry, each
    layer writes its chunk at `[layer]` and attends over `stack[layer]`.
    A prefill chunk of 4 positions, then single steps, against the
    unrolled executor's per-layer cache: outputs and every cache leaf."""

    STEPS = 5

    @pytest.mark.parametrize("attn_types", [None, PATTERNS], ids=["full", "patterns"])
    @pytest.mark.parametrize(
        "carries",
        ["lockstep", "reverse_model", "per_row", "int8", "per_row_int8"],
    )
    def test_cached_chunks_match_unrolled(self, carries, attn_types):
        unr, scn = pair(attn_types=attn_types)
        x = x_input()  # [2, SEQ, DIM]
        vu = unr.init(jax.random.PRNGKey(1), x)
        vs = {"params": unrolled_params_to_scan(vu["params"], DEPTH)}
        per_row = carries.startswith("per_row")
        reverse = carries == "reverse_model"
        kw = dict(
            depth=DEPTH, batch=2, max_len=SEQ + 1, heads=2, dim_head=8, dim=DIM,
            image_fmap_size=FMAP, shift_tokens=True, per_row=per_row,
            kv_dtype="int8" if carries.endswith("int8") else None,
        )
        cu = decode_cache.make(PER_LAYER, **kw)
        cs = decode_cache.make(STACKED, **kw)

        def both(chunk, cu, cs):
            ou, cu = unr.apply(vu, chunk, cache=cu, reverse_model=reverse)
            os_, cs = scn.apply(vs, chunk, cache=cs, reverse_model=reverse)
            np.testing.assert_allclose(
                np.asarray(ou), np.asarray(os_), rtol=2e-5, atol=2e-5
            )
            return cu, cs

        cu, cs = both(x[:, :4], cu, cs)
        for t in range(4, 4 + self.STEPS):
            if per_row:
                # row 1 stops at position 6 (a retired slot rewrites its
                # last position), row 0 goes on: the rows sit apart
                pos = jnp.array([t, min(t, 6)], jnp.int32)
                cu = decode_cache.set_index(cu, pos)
                cs = decode_cache.set_index(cs, pos)
            cu, cs = both(x[:, t : t + 1], cu, cs)

        # the same leaves, whichever way they are held: int8 K/V bit for bit
        assert jax.tree.structure(cs) == jax.tree.structure(
            decode_cache.make(STACKED, **kw)
        )
        stacked = jax.tree.map(
            lambda *leaves: jnp.stack(leaves), *(cu[f"layer_{i}"] for i in range(DEPTH))
        )
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(stacked), jax.tree.leaves(cs)
        ):
            assert a.shape == b.shape and a.dtype == b.dtype, path
            if a.dtype == jnp.int8:
                # a rounding tie may fall either way on a last float32 bit
                assert np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32)).max() <= 1, path
            else:
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
                    err_msg=jax.tree_util.keystr(path),
                )
        if reverse:
            # sanity that the flag acted on the cached path too
            fwd, _ = scn.apply(
                vs, x[:, :4], cache=decode_cache.make(STACKED, **kw)
            )
            rev, _ = scn.apply(
                vs, x[:, :4], cache=decode_cache.make(STACKED, **kw),
                reverse_model=True,
            )
            assert not np.allclose(np.asarray(fwd), np.asarray(rev))

    def test_decode_resume_window_is_read_and_not_returned(self):
        """`ring_end` rides the carry like the rest, every layer reads its
        own slice, and the cache that comes back has the leaves it had."""
        _, scn = pair()
        x = x_input()
        vs = scn.init(jax.random.PRNGKey(1), x)
        cache = decode_cache.make(
            STACKED, depth=DEPTH, batch=2, max_len=SEQ + 1, heads=2, dim_head=8,
            dim=DIM, image_fmap_size=FMAP, shift_tokens=True,
        )
        ring_end = jnp.broadcast_to(jnp.array([7, 9], jnp.int32), (DEPTH, 2))
        _, out = scn.apply(vs, x, cache={**cache, "ring_end": ring_end})
        assert jax.tree.structure(out) == jax.tree.structure(cache)
        _, plain = scn.apply(vs, x, cache=cache)
        assert not np.allclose(
            np.asarray(out["shift_attn"]), np.asarray(plain["shift_attn"])
        )


class TestScanCLIP:
    """CLIP's two non-causal encoders under the scan executor (incl. the
    text encoder's dynamic key-padding mask through nn.broadcast)."""

    def test_loss_parity(self):
        from dalle_pytorch_tpu.models.clip import CLIP

        kw = dict(
            dim_text=32, dim_image=32, dim_latent=16, num_text_tokens=50,
            text_enc_depth=2, text_seq_len=8, text_heads=2,
            visual_enc_depth=2, visual_heads=2, visual_image_size=16,
            visual_patch_size=8,
        )
        cu, cs = CLIP(executor="unrolled", **kw), CLIP(executor="scan", **kw)
        text = jnp.array([[3, 5, 2, 0, 0, 0, 0, 0], [7, 1, 4, 9, 0, 0, 0, 0]])
        mask = text > 0
        imgs = jax.random.uniform(jax.random.PRNGKey(0), (2, 16, 16, 3))
        vs = cs.init(jax.random.PRNGKey(1), text, imgs, text_mask=mask,
                     return_loss=True)
        loss_s = cs.apply(vs, text, imgs, text_mask=mask, return_loss=True)

        pu = dict(vs["params"])
        for name, depth in (("text_transformer", 2), ("visual_transformer", 2)):
            pu[name] = scan_params_to_unrolled(vs["params"][name], depth)
        loss_u = cu.apply({"params": pu}, text, imgs, text_mask=mask,
                          return_loss=True)
        np.testing.assert_allclose(float(loss_s), float(loss_u), rtol=1e-5)


class TestScanDALLE:
    """End-to-end through the DALLE wrapper: scan-trained params must
    produce the same loss as unrolled, and the converted checkpoint must
    drive the unrolled cached decode."""

    def _model(self, executor):
        return DALLE(
            dim=DIM, depth=DEPTH, heads=2, dim_head=8,
            num_image_tokens=16, image_fmap_size=FMAP,
            num_text_tokens=30, text_seq_len=4,
            shift_tokens=True, rotary_emb=True, executor=executor,
        )

    def test_loss_parity_and_cached_decode(self):
        mu, ms = self._model("unrolled"), self._model("scan")
        text = jnp.array([[3, 5, 2, 0], [7, 1, 0, 0]], jnp.int32)
        img = jnp.arange(2 * FMAP * FMAP, dtype=jnp.int32).reshape(2, -1) % 16
        vs = ms.init(jax.random.PRNGKey(0), text, img)
        loss_s, _ = ms.apply(vs, text, img, return_loss=True)

        pu = dict(vs["params"])
        pu["transformer"] = scan_params_to_unrolled(
            vs["params"]["transformer"], DEPTH
        )
        loss_u, _ = mu.apply({"params": pu}, text, img, return_loss=True)
        np.testing.assert_allclose(float(loss_s), float(loss_u), rtol=1e-5)

        # converted checkpoint drives the unrolled KV-cached sampler
        imgs = generate_images_cached(
            mu, {"params": pu}, jax.random.PRNGKey(2), text[:1]
        )
        assert imgs.shape == (1, FMAP * FMAP)

    def test_native_cached_decode_matches_unrolled(self):
        """The scan executor's OWN KV-cached decode (depth-stacked cache
        carried through the layer scan) must produce the same tokens as the unrolled
        cached sampler on the converted checkpoint — no conversion needed."""
        mu, ms = self._model("unrolled"), self._model("scan")
        text = jnp.array([[3, 5, 2, 0]], jnp.int32)
        img = jnp.arange(FMAP * FMAP, dtype=jnp.int32)[None] % 16
        vs = ms.init(jax.random.PRNGKey(0), text, img)
        near_greedy = dict(temperature=1e-4, filter_thres=0.999)
        toks_scan = generate_images_cached(
            ms, vs, jax.random.PRNGKey(2), text, **near_greedy
        )
        pu = dict(vs["params"])
        pu["transformer"] = scan_params_to_unrolled(
            vs["params"]["transformer"], DEPTH
        )
        toks_unrolled = generate_images_cached(
            mu, {"params": pu}, jax.random.PRNGKey(2), text, **near_greedy
        )
        np.testing.assert_array_equal(
            np.asarray(toks_scan), np.asarray(toks_unrolled)
        )
        # and the scan model's uncached full-reforward sampler agrees
        from dalle_pytorch_tpu.models.dalle import generate_images

        toks_full = generate_images(
            ms, vs, jax.random.PRNGKey(2), text, **near_greedy
        )
        np.testing.assert_array_equal(
            np.asarray(toks_scan), np.asarray(toks_full)
        )

    def test_cached_decode_with_pattern_masks_matches_unrolled(self):
        """Scan-native cached decode WITH the attn-type cycle: the traced
        per-layer pattern masks row-slice at the decode position exactly
        like the unrolled executor's static masks, so both cached
        samplers emit identical tokens from the same (converted)
        checkpoint — generate.py needs no layout conversion for masked
        scan checkpoints."""
        attn_types = ("full", "axial_row", "axial_col", "conv_like")
        kw = dict(
            dim=DIM, depth=DEPTH, heads=2, dim_head=8,
            num_image_tokens=16, image_fmap_size=FMAP,
            num_text_tokens=30, text_seq_len=4,
            shift_tokens=True, rotary_emb=True, attn_types=attn_types,
        )
        ms = DALLE(executor="scan", **kw)
        text = jnp.array([[3, 5, 2, 0]], jnp.int32)
        img = jnp.arange(FMAP * FMAP, dtype=jnp.int32)[None] % 16
        vs = ms.init(jax.random.PRNGKey(0), text, img)
        toks_scan = generate_images_cached(
            ms, vs, jax.random.PRNGKey(2), text,
            temperature=1e-4, filter_thres=0.999,
        )

        mu = DALLE(executor="unrolled", **kw)
        pu = dict(vs["params"])
        pu["transformer"] = scan_params_to_unrolled(
            vs["params"]["transformer"], DEPTH
        )
        toks_unrolled = generate_images_cached(
            mu, {"params": pu}, jax.random.PRNGKey(2), text,
            temperature=1e-4, filter_thres=0.999,
        )
        np.testing.assert_array_equal(
            np.asarray(toks_scan), np.asarray(toks_unrolled)
        )
