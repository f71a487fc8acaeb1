import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.models.dalle import (
    DALLE,
    generate_images,
    generate_texts,
    forward_with_cond_scale,
)

TEXT_SEQ = 6
FMAP = 3
IMG_SEQ = FMAP * FMAP
NUM_TEXT = 20
NUM_IMG = 16


def make_dalle(**kw):
    defaults = dict(
        dim=32,
        depth=2,
        num_image_tokens=NUM_IMG,
        image_fmap_size=FMAP,
        num_text_tokens=NUM_TEXT,
        text_seq_len=TEXT_SEQ,
        heads=2,
        dim_head=8,
        shift_tokens=False,
        rotary_emb=True,
    )
    defaults.update(kw)
    return DALLE(**defaults)


@pytest.fixture
def batch():
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (2, TEXT_SEQ), 1, NUM_TEXT)
    text = text.at[:, -2:].set(0)  # trailing padding
    image = jax.random.randint(jax.random.PRNGKey(1), (2, IMG_SEQ), 0, NUM_IMG)
    return text, image


def init_vars(model, text, image):
    return model.init(jax.random.PRNGKey(42), text, image)


class TestDALLEForward:
    def test_logits_shape_and_mask(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        logits = model.apply(variables, text, image)
        total_seq = TEXT_SEQ + IMG_SEQ
        total_tokens = NUM_TEXT + TEXT_SEQ + NUM_IMG
        assert logits.shape == (2, total_seq, total_tokens)

        arr = np.asarray(logits)
        text_vocab = NUM_TEXT + TEXT_SEQ
        # text positions may only produce text tokens
        assert (arr[:, : TEXT_SEQ, text_vocab:] < -1e30).all()
        assert np.isfinite(arr[:, : TEXT_SEQ, :text_vocab]).all()
        # image positions may only produce image tokens
        assert (arr[:, TEXT_SEQ:, :text_vocab] < -1e30).all()
        assert np.isfinite(arr[:, TEXT_SEQ:, text_vocab:]).all()

    def test_inverse_mask_rotated(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        logits = model.apply(variables, text, image, inverse_mapping=True)
        arr = np.asarray(logits)
        text_vocab = NUM_TEXT + TEXT_SEQ
        # image occupies the FRONT of the sequence in inverse mode
        assert (arr[:, :IMG_SEQ, :text_vocab] < -1e30).all()
        assert (arr[:, IMG_SEQ:, text_vocab:] < -1e30).all()

    def test_loss_modes(self, batch):
        """forward / forward_forward / forward_reverse_partial objectives."""
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)

        loss, acc = model.apply(variables, text, image, return_loss=True)
        assert np.isfinite(float(loss)) and acc is None

        inv_loss, inv_acc = model.apply(
            variables, text, image, return_loss=True, inverse_mapping=True
        )
        assert np.isfinite(float(inv_loss))
        assert 0.0 <= float(inv_acc) <= 1.0

        rev_loss, _ = model.apply(
            variables, text, image, return_loss=True,
            inverse_mapping=True, reverse_model=True,
        )
        assert np.isfinite(float(rev_loss))
        assert float(rev_loss) != float(inv_loss)

    def test_grads_flow(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)

        def loss_fn(params):
            loss, _ = model.apply({"params": params}, text, image, return_loss=True)
            return loss

        grads = jax.grad(loss_fn)(variables["params"])
        total = sum(float(jnp.abs(g).sum()) for g in jax.tree.leaves(grads))
        assert np.isfinite(total) and total > 0

    def test_unique_pad_tokens_distinguish_positions(self, batch):
        """Zero-padding at different positions embeds differently (`:606-609`)."""
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        t1 = jnp.zeros((1, TEXT_SEQ), jnp.int32).at[0, 0].set(5)
        t2 = jnp.zeros((1, TEXT_SEQ), jnp.int32).at[0, 1].set(5)
        l1 = model.apply(variables, t1, image[:1])
        l2 = model.apply(variables, t2, image[:1])
        assert not np.allclose(np.asarray(l1), np.asarray(l2))

    def test_feature_flag_matrix(self, batch):
        text, image = batch
        for kw in (
            {"stable": True},
            {"sandwich_norm": True},
            {"shift_tokens": True},
            {"rotary_emb": False},
            {"share_input_output_emb": True},
            {"attn_types": ("full", "axial_row")},
            {"reversible": True},
        ):
            model = make_dalle(**kw)
            variables = init_vars(model, text, image)
            loss, _ = model.apply(variables, text, image, return_loss=True)
            assert np.isfinite(float(loss)), kw

    def test_null_cond_prob_drops_text(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        l_cond = model.apply(variables, text, image)
        l_null = model.apply(
            variables, text, image, null_cond_prob=1.0,
            rngs={"null_cond": jax.random.PRNGKey(0)},
        )
        assert not np.allclose(np.asarray(l_cond), np.asarray(l_null))
        # null-conditioning equals passing all-padding text
        l_zeros = model.apply(variables, jnp.zeros_like(text), image)
        np.testing.assert_allclose(np.asarray(l_null), np.asarray(l_zeros), atol=1e-5)


class TestGeneration:
    def test_generate_images_tokens_in_range(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        toks = generate_images(
            model, variables, jax.random.PRNGKey(0), text, filter_thres=0.9
        )
        assert toks.shape == (2, IMG_SEQ)
        arr = np.asarray(toks)
        assert (arr >= 0).all() and (arr < NUM_IMG).all()

    def test_generate_with_priming(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        toks = generate_images(
            model,
            variables,
            jax.random.PRNGKey(0),
            text,
            init_image_tokens=image,
            num_init_img_tokens=4,
        )
        np.testing.assert_array_equal(np.asarray(toks[:, :4]), np.asarray(image[:, :4]))

    def test_cond_scale_two_forward_blend(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        l1 = forward_with_cond_scale(model, variables, text, image, cond_scale=1.0)
        l3 = forward_with_cond_scale(model, variables, text, image, cond_scale=3.0)
        assert not np.allclose(np.asarray(l1), np.asarray(l3))

    def test_generate_texts(self, batch):
        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        out = generate_texts(
            model, variables, jax.random.PRNGKey(0), text, prefix_len=2
        )
        assert out.shape == (2, TEXT_SEQ)
        np.testing.assert_array_equal(np.asarray(out[:, :2]), np.asarray(text[:, :2]))
        arr = np.asarray(out)
        assert (arr >= 0).all() and (arr < NUM_TEXT + TEXT_SEQ).all()


class TestCachedDecode:
    """Cached decode must reproduce the uncached oracle exactly.

    This is the test seam for the reference's broken cached-mask path
    (`dalle_pytorch.py:669-671` `assert False`): we re-derive the semantics
    and pin them against the full re-forward."""

    def _teacher_forced_rows(self, model, variables, text, image):
        """Run prefill + per-token cached steps feeding `image`; collect the
        logits row for every image slot."""
        from dalle_pytorch_tpu.models.dalle import init_decode_cache, DALLE

        b = text.shape[0]
        row, cache = model.apply(
            variables, text, init_decode_cache(model, b, jnp.float32),
            method=DALLE.decode_prefill,
        )
        rows = [row]
        for i in range(IMG_SEQ - 1):
            row, cache = model.apply(
                variables, image[:, i], jnp.asarray(i), cache,
                method=DALLE.decode_image_step,
            )
            rows.append(row)
        return jnp.stack(rows, axis=1)  # [B, IMG_SEQ, V]

    @pytest.mark.parametrize(
        "kw",
        [
            dict(),
            dict(shift_tokens=True),
            dict(shift_tokens=True, attn_types=("full", "axial_row")),
            dict(rotary_emb=False, stable=True, sandwich_norm=True),
            dict(reversible=True, reversible_impl="revnet", shift_tokens=True),
        ],
        ids=["plain", "shift", "shift+axial", "posemb+stable+sandwich", "revnet"],
    )
    def test_cached_matches_full_forward(self, batch, kw):
        model = make_dalle(**kw)
        text, image = batch
        variables = init_vars(model, text, image)

        full = model.apply(variables, text, image)  # [B, total, V]
        oracle = full[:, TEXT_SEQ:]  # rows for image slots 0..IMG_SEQ-1
        cached = self._teacher_forced_rows(model, variables, text, image)

        # compare image-vocab columns only (text cols are -inf masked in the
        # full path; cached rows are masked later, at sampling)
        v0 = NUM_TEXT + TEXT_SEQ
        np.testing.assert_allclose(
            np.asarray(cached[..., v0:]),
            np.asarray(oracle[..., v0:]),
            rtol=1e-4,
            atol=1e-4,
        )

    def test_cached_generation_matches_uncached(self, batch):
        from dalle_pytorch_tpu.models.dalle import generate_images_cached

        model = make_dalle(shift_tokens=True)
        text, image = batch
        variables = init_vars(model, text, image)
        rng = jax.random.PRNGKey(7)
        slow = generate_images(model, variables, rng, text, filter_thres=0.9)
        fast = generate_images_cached(model, variables, rng, text, filter_thres=0.9)
        np.testing.assert_array_equal(np.asarray(slow), np.asarray(fast))

    def test_fused_pixel_sampler_matches_two_step(self, batch):
        """vae=/vae_params= fuses the dVAE pixel decode into the sampler
        program: tokens identical to the unfused sampler, pixels identical
        to decoding those tokens separately — one dispatch instead of
        two (the generate.py production path)."""
        from dalle_pytorch_tpu.models.dalle import generate_images_cached
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE

        # fmap 4 (not the suite's 3): the dVAE needs a power-of-2 image
        # size, fmap = image_size / 2^num_layers
        fmap = 4
        model = make_dalle(shift_tokens=True, image_fmap_size=fmap)
        text = batch[0]
        image = jnp.tile(batch[1], (1, 2))[:, : fmap * fmap] % NUM_IMG
        variables = init_vars(model, text, image)
        vae = DiscreteVAE(
            image_size=4 * fmap, num_layers=2, num_tokens=NUM_IMG,
            codebook_dim=16, hidden_dim=16,
        )
        vparams = jax.jit(vae.init)(
            jax.random.PRNGKey(5), jnp.zeros((1, 4 * fmap, 4 * fmap, 3))
        )["params"]

        rng = jax.random.PRNGKey(7)
        toks = generate_images_cached(model, variables, rng, text)
        ftoks, pixels = generate_images_cached(
            model, variables, rng, text, vae=vae, vae_params=vparams
        )
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(ftoks))
        want = vae.apply({"params": vparams}, toks, method=DiscreteVAE.decode)
        np.testing.assert_allclose(
            np.asarray(pixels), np.asarray(want), atol=1e-6
        )
        assert pixels.shape == (text.shape[0], 4 * fmap, 4 * fmap, 3)

    def test_cached_generation_priming_and_guidance(self, batch):
        from dalle_pytorch_tpu.models.dalle import generate_images_cached

        model = make_dalle()
        text, image = batch
        variables = init_vars(model, text, image)
        toks = generate_images_cached(
            model,
            variables,
            jax.random.PRNGKey(0),
            text,
            cond_scale=2.0,
            init_image_tokens=image,
            num_init_img_tokens=4,
        )
        arr = np.asarray(toks)
        np.testing.assert_array_equal(arr[:, :4], np.asarray(image[:, :4]))
        assert (arr >= 0).all() and (arr < NUM_IMG).all()


def _masked_dense_loss(model, logits, text, image, inverse_mapping):
    """The loss over the masked full [B, N, V] logits, as `DALLE._dense_loss`
    computed it until the split loss took its place: the independent
    reference. `text` is the bos-padded, pad-remapped ids of `embed_text`."""
    from dalle_pytorch_tpu.models.dalle import cross_entropy

    offsetted_image = image + model.total_text_tokens
    if inverse_mapping:
        labels = jnp.concatenate([offsetted_image[:, 1:], text], axis=1)
        split = model.image_seq_len
        loss_text = cross_entropy(logits[:, split:], labels[:, split:])
        loss_img = cross_entropy(logits[:, : split - 1], labels[:, : split - 1])
        pred3 = jnp.argmax(logits[:, split : split + 3], axis=-1)
        accuracy = jnp.mean(
            jnp.all(pred3 == labels[:, split : split + 3], axis=-1).astype(jnp.float32)
        )
        ct, ci = model.text_loss_coeff_inv, model.img_loss_coeff_inv
        return (ct * loss_text + ci * loss_img) / (ct + ci), accuracy
    labels = jnp.concatenate([text[:, 1:], offsetted_image], axis=1)
    split = model.text_seq_len
    loss_text = cross_entropy(logits[:, :split], labels[:, :split])
    loss_img = cross_entropy(logits[:, split:], labels[:, split:])
    ct = model.text_loss_coeff
    ci = model.loss_img_weight if model.img_loss_coeff is None else model.img_loss_coeff
    return (ct * loss_text + ci * loss_img) / (ct + ci), None


class TestSplitLoss:
    """`return_loss=True` computes a text row's logits over the text columns
    and an image row's over the image columns, and nothing else; the loss,
    its gradients and the inverse objective's accuracy are those of the
    masked full logits that `return_loss=False` still returns."""

    KW = dict(depth=1, text_loss_coeff=2.0, img_loss_coeff=3.0,
              text_loss_coeff_inv=5.0, img_loss_coeff_inv=0.5)

    @staticmethod
    def _reference(model, text, image, inverse):
        def f(params):
            variables = {"params": params}
            logits = model.apply(variables, text, image, inverse_mapping=inverse)
            padded, _ = model.apply(variables, text, method=DALLE.embed_text)
            return _masked_dense_loss(model, logits, padded, image, inverse)
        return f

    @pytest.mark.parametrize("stable", [False, True])
    @pytest.mark.parametrize("inverse", [False, True])
    @pytest.mark.parametrize("share_emb", [False, True])
    def test_matches_the_loss_over_the_masked_full_logits(
            self, batch, share_emb, inverse, stable):
        text, image = batch
        model = make_dalle(share_input_output_emb=share_emb, stable=stable, **self.KW)
        params = init_vars(model, text, image)["params"]
        # zeros at init: a bias that is there has to count. The inverse
        # objective's first three text labels are id 5, which the bias makes
        # the text block's argmax: an accuracy of 1, not 0 against 0
        bias = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (model.total_tokens,))
        if inverse:
            text, bias = text.at[:, :3].set(5), bias.at[5].add(50.0)
        if share_emb:
            params = dict(params, logits_bias=bias)
        else:
            params = dict(params, logits_dense=dict(params["logits_dense"], bias=bias))

        def split(p):
            return model.apply({"params": p}, text, image, return_loss=True,
                               inverse_mapping=inverse)

        (want, want_acc), g_want = jax.value_and_grad(
            self._reference(model, text, image, inverse), has_aux=True)(params)
        (got, got_acc), g_got = jax.value_and_grad(split, has_aux=True)(params)

        assert got.dtype == jnp.float32
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        if inverse:
            assert float(got_acc) == float(want_acc) == 1.0
        else:
            assert got_acc is None and want_acc is None
        TestFusedCE._assert_grad_parity(g_want, g_got, atol=1e-5)
        # the columns of the head a row's block does not touch still get
        # their gradient from the other block: no leaf is left at zero
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(g_got))

    @pytest.mark.parametrize("inverse", [False, True])
    def test_a_short_image_keeps_its_masked_logits_and_has_no_loss(self, batch, inverse):
        """`image.shape[1] < image_seq_len`: the full logits split by the
        row rule of `_logits_blocked` as before; a loss there has one row
        more than labels, which the masked dense loss refused with a
        broadcasting error and the split loss refuses by name."""
        text, image = batch
        model = make_dalle(**self.KW)
        variables = init_vars(model, text, image)
        short = image[:, : IMG_SEQ - 2]
        logits = np.asarray(model.apply(variables, text, short, inverse_mapping=inverse))
        assert logits.shape == (2, TEXT_SEQ + 1 + IMG_SEQ - 2, model.total_tokens)
        text_rows = np.arange(logits.shape[1]) < TEXT_SEQ
        if inverse:
            text_rows = np.arange(logits.shape[1]) >= IMG_SEQ
        text_cols = np.arange(model.total_tokens) < model.total_text_tokens
        blocked = text_rows[:, None] != text_cols[None, :]
        assert (logits[:, blocked] < -1e30).all()
        assert np.isfinite(logits[:, ~blocked]).all()
        with pytest.raises(ValueError):
            self._reference(model, text, short, inverse)(variables["params"])
        with pytest.raises(AssertionError, match="image tokens"):
            model.apply(variables, text, short, return_loss=True,
                        inverse_mapping=inverse)


class TestFusedCE:
    """Vocab-chunked CE (ops/losses.py) must match the dense loss path
    bit-for-bit in semantics: same loss, same grads."""

    @staticmethod
    def _assert_grad_parity(g_dense, g_fused, atol=2e-5, label=""):
        flat_d = {jax.tree_util.keystr(k): v
                  for k, v in jax.tree_util.tree_leaves_with_path(g_dense)}
        flat_f = {jax.tree_util.keystr(k): v
                  for k, v in jax.tree_util.tree_leaves_with_path(g_fused)}
        assert flat_d.keys() == flat_f.keys()
        for k in flat_d:
            np.testing.assert_allclose(
                np.asarray(flat_d[k]), np.asarray(flat_f[k]), atol=atol,
                err_msg=f"{label}grad mismatch at {k}",
            )

    def _pair(self, share_emb=False):
        kw = dict(
            dim=32, depth=2, heads=2, dim_head=16, num_image_tokens=48,
            image_fmap_size=4, num_text_tokens=60, text_seq_len=12,
            shift_tokens=True, rotary_emb=True,
            share_input_output_emb=share_emb,
        )
        return DALLE(fused_ce=False, **kw), DALLE(fused_ce=True, **kw)

    @pytest.mark.slow  # ~22 s/param: dense + fused grads compile two big
    # programs (tier-1 budget); TestFusedCEMultiStep keeps fused-CE
    # training covered in the fast tier
    @pytest.mark.parametrize("share_emb", [False, True])
    def test_loss_and_grad_parity(self, share_emb):
        dense, fused = self._pair(share_emb)
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (3, 12), 1, 60)
        image = jax.random.randint(rng, (3, 16), 0, 48)
        params = dense.init(rng, text, image)["params"]

        def loss_of(model):
            def f(p):
                loss, _ = model.apply(
                    {"params": p}, text, image, return_loss=True
                )
                return loss
            return f

        l_dense = loss_of(dense)(params)
        l_fused = loss_of(fused)(params)
        np.testing.assert_allclose(
            float(l_dense), float(l_fused), rtol=2e-5,
            err_msg="fused CE loss diverged from dense path",
        )
        g_dense = jax.grad(loss_of(dense))(params)
        g_fused = jax.grad(loss_of(fused))(params)
        self._assert_grad_parity(g_dense, g_fused)

    @pytest.mark.slow  # ~17 s/param: same two-program compile as above
    # for the inverse path (tier-1 budget)
    @pytest.mark.parametrize("share_emb", [False, True])
    def test_fused_inverse_parity(self, share_emb):
        """The fused inverse path (vocab-chunked CE + [B,3,V] dense
        accuracy block) must match the dense inverse path: same loss,
        same 3-token accuracy, same grads."""
        dense, fused = self._pair(share_emb)
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (2, 12), 1, 60)
        image = jax.random.randint(rng, (2, 16), 0, 48)
        params = dense.init(rng, text, image)["params"]

        def loss_of(model):
            def f(p):
                loss, _ = model.apply(
                    {"params": p}, text, image, return_loss=True,
                    inverse_mapping=True,
                )
                return loss
            return f

        ld, accd = dense.apply(
            {"params": params}, text, image, return_loss=True, inverse_mapping=True
        )
        lf, accf = fused.apply(
            {"params": params}, text, image, return_loss=True, inverse_mapping=True
        )
        np.testing.assert_allclose(float(ld), float(lf), rtol=2e-5)
        np.testing.assert_allclose(float(accd), float(accf), rtol=1e-6)

        g_dense = jax.grad(loss_of(dense))(params)
        g_fused = jax.grad(loss_of(fused))(params)
        self._assert_grad_parity(g_dense, g_fused, label="inverse ")

    def test_chunk_boundary_labels(self):
        """Labels on chunk edges (0, chunk-1, chunk, V-1) gather correctly."""
        from dalle_pytorch_tpu.ops.losses import chunked_masked_ce
        import jax.numpy as jnp

        B, N, D, V, chunk = 2, 6, 8, 10, 4  # V not a multiple of chunk
        rng = jax.random.PRNGKey(0)
        h = jax.random.normal(rng, (B, N, D))
        kernel = jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.3
        bias = jax.random.normal(jax.random.PRNGKey(2), (V,)) * 0.1
        row_is_text = jnp.array([True] * 3 + [False] * 3)
        num_text_vocab = 5
        labels = jnp.array([[0, 3, 4, 5, 8, 9], [1, 2, 0, 7, 6, 5]])

        got = chunked_masked_ce(
            h, kernel, bias, labels,
            row_is_text=row_is_text, num_text_vocab=num_text_vocab,
            chunk=chunk,
        )
        # dense oracle
        logits = (h @ kernel + bias).astype(jnp.float32)
        vocab_is_text = jnp.arange(V) < num_text_vocab
        allowed = row_is_text[:, None] == vocab_is_text[None, :]
        logits = jnp.where(allowed[None], logits, -1e30)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        want = logz - gold
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


class TestFusedCEMultiStep:
    """Regression: jax 0.9's jit C++ fastpath drops hoisted constant
    arguments from call 3 onward ("Execution supplied N buffers but
    compiled program expected M"). A module-level `jnp.float32` constant
    in ops/losses.py triggered it for every fused-CE train step — parity
    tests (1-2 calls) never saw it; any real training run crashed at
    step 3. Pin: 5 donated jitted steps must survive."""

    @pytest.mark.parametrize("executor", ["unrolled", "scan"])
    def test_five_donated_steps(self, executor):
        from dalle_pytorch_tpu.training import (
            TrainState, make_optimizer, make_dalle_train_step,
        )

        model = DALLE(
            dim=32, depth=2, heads=2, dim_head=16, num_image_tokens=48,
            image_fmap_size=4, num_text_tokens=60, text_seq_len=12,
            shift_tokens=True, rotary_emb=True,
            reversible=True, reversible_impl="remat",
            remat_policy="dots_with_no_batch_dims_saveable", fused_ce=True,
            executor=executor,
        )
        text = jnp.ones((2, 12), jnp.int32)
        tokens = jnp.zeros((2, 16), jnp.int32)
        params = jax.jit(model.init)(
            jax.random.PRNGKey(0), text, tokens
        )["params"]
        state = TrainState.create(
            apply_fn=model.apply, params=params,
            tx=make_optimizer(3e-4, clip_grad_norm=0.5),
        )
        step = jax.jit(make_dalle_train_step(model), donate_argnums=0)
        batch = {"text": text, "image_tokens": tokens}
        rng = jax.random.PRNGKey(1)
        losses = []
        for _ in range(5):
            rng, r = jax.random.split(rng)
            state, m = step(state, batch, r)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]


class TestCombinedPerfFeatures:
    """The bench's fastest profile stacks flash attention + selective remat
    + fused CE; their composition must agree with the plain model."""

    def test_flash_policy_fusedce_matches_baseline(self):
        kw = dict(
            dim=32, depth=2, heads=2, dim_head=16, num_image_tokens=48,
            image_fmap_size=4, num_text_tokens=60, text_seq_len=12,
            shift_tokens=True, rotary_emb=True,
        )
        base = DALLE(**kw)
        fast = DALLE(
            attn_impl="flash", reversible=True, reversible_impl="remat",
            remat_policy="dots_with_no_batch_dims_saveable", fused_ce=True,
            **kw,
        )
        rng = jax.random.PRNGKey(0)
        text = jax.random.randint(rng, (2, 12), 1, 60)
        image = jax.random.randint(rng, (2, 16), 0, 48)
        params = base.init(rng, text, image)["params"]

        def loss(model, p):
            l, _ = model.apply({"params": p}, text, image, return_loss=True)
            return l

        l_base = float(loss(base, params))
        l_fast = float(loss(fast, params))
        np.testing.assert_allclose(l_base, l_fast, rtol=5e-3)

        g_base = jax.grad(lambda p: loss(base, p))(params)
        g_fast = jax.grad(lambda p: loss(fast, p))(params)
        for a, b in zip(jax.tree.leaves(g_base), jax.tree.leaves(g_fast)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3,
            )
