import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dalle_pytorch_tpu.parallel import (
    make_mesh,
    batch_sharding,
    partition_params,
    state_shardings,
    ring_attention,
)
from dalle_pytorch_tpu.parallel.ring import ring_attention_sharded
from dalle_pytorch_tpu.ops.attention_core import dense_attention


class TestMesh:
    def test_make_mesh_fills_dp(self):
        mesh = make_mesh(fsdp=2, tp=2)
        assert dict(mesh.shape) == {"dp": 2, "fsdp": 2, "tp": 2, "sp": 1}

    def test_make_mesh_all_axes(self):
        mesh = make_mesh(dp=1, fsdp=2, tp=2, sp=2)
        assert dict(mesh.shape) == {"dp": 1, "fsdp": 2, "tp": 2, "sp": 2}

    def test_bad_mesh_raises(self):
        with pytest.raises(AssertionError):
            make_mesh(dp=3, fsdp=3)


class TestPartition:
    def test_rules(self):
        from dalle_pytorch_tpu.models.dalle import DALLE

        model = DALLE(
            dim=32, depth=1, num_image_tokens=16, image_fmap_size=4,
            num_text_tokens=26, text_seq_len=6, heads=2, dim_head=8,
        )
        text = jnp.zeros((1, 6), jnp.int32)
        img = jnp.zeros((1, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), text, img)["params"]
        mesh = make_mesh(dp=2, fsdp=2, tp=2)
        shardings = partition_params(params, mesh)

        flat = {
            "/".join(str(k.key) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
        }
        qkv = next(v for k, v in flat.items() if "to_qkv/kernel" in k)
        assert qkv.spec == P("fsdp", "tp")
        out = next(v for k, v in flat.items() if "to_out/kernel" in k)
        assert out.spec == P("tp", "fsdp")
        scale = next(v for k, v in flat.items() if "scale" in k)
        assert scale.spec == P()

    def test_nondivisible_dims_fall_back_to_replicated(self):
        mesh = make_mesh(dp=1, fsdp=4, tp=2)
        params = {"to_qkv": {"kernel": jnp.zeros((6, 10))}}  # 6 % 4 != 0
        sh = partition_params(params, mesh)
        assert sh["to_qkv"]["kernel"].spec == P(None, "tp")

    def test_scan_executor_stacked_kernels_shard(self):
        """Rank-3 (depth-stacked) scan-executor kernels must pick up the
        fsdp/tp specs with the depth axis unsharded — and a sharded train
        step must actually run on the virtual mesh."""
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training import (
            TrainState, make_optimizer, make_dalle_train_step,
        )

        model = DALLE(
            dim=32, depth=2, num_image_tokens=16, image_fmap_size=4,
            num_text_tokens=26, text_seq_len=6, heads=2, dim_head=8,
            executor="scan", fused_ce=True,
        )
        text = jnp.zeros((4, 6), jnp.int32)
        img = jnp.zeros((4, 16), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), text, img)["params"]
        mesh = make_mesh(dp=2, fsdp=2, tp=2)
        shardings = partition_params(params, mesh)
        flat = {
            "/".join(str(k.key) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
        }
        qkv = next(v for k, v in flat.items()
                   if "layers/attn/to_qkv/kernel" in k)
        assert qkv.spec == P(None, "fsdp", "tp")
        ff_up = next(v for k, v in flat.items()
                     if "layers/ff/Dense_0/kernel" in k)
        assert ff_up.spec == P(None, "fsdp", "tp")
        ff_down = next(v for k, v in flat.items()
                       if "layers/ff/Dense_1/kernel" in k)
        assert ff_down.spec == P(None, "tp", "fsdp")
        scales = next(v for k, v in flat.items() if "attn_scale_stack" in k)
        assert scales.spec == P()

        # one sharded train step end to end
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, shardings
        )
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=make_optimizer(1e-3),
        )
        from dalle_pytorch_tpu.parallel.mesh import batch_sharding
        from jax.sharding import NamedSharding

        bsh = batch_sharding(mesh)
        batch = {
            "text": jax.device_put(text, bsh),
            "image_tokens": jax.device_put(img, bsh),
        }
        step = jax.jit(make_dalle_train_step(model))
        with mesh:
            state2, metrics = step(state, batch, jax.random.PRNGKey(1))
        assert np.isfinite(float(metrics["loss"]))


class TestRingAttention:
    def test_matches_dense_causal(self):
        mesh = make_mesh(dp=1, sp=8)
        b, h, n, d = 2, 2, 32, 8
        rng = jax.random.PRNGKey(0)
        q, k, v = jax.random.normal(rng, (3, b, h, n, d))

        out_ring = ring_attention_sharded(mesh, q, k, v, causal=True)

        causal = jnp.tril(jnp.ones((n, n), bool))[None, None]
        out_dense = dense_attention(q, k, v, mask=causal)
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_dense), rtol=2e-4, atol=2e-5
        )

    def test_noncausal_matches_dense(self):
        mesh = make_mesh(dp=2, sp=4)
        q, k, v = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 2, 16, 8))
        out_ring = ring_attention_sharded(mesh, q, k, v, causal=False)
        out_dense = dense_attention(q, k, v, mask=None)
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_dense), rtol=2e-4, atol=2e-5
        )


class TestShardedTrainStep:
    def test_sharded_step_matches_unsharded(self):
        """dp2 x fsdp2 x tp2 sharded step == single-device step, bitwise-ish.

        This is the real replacement for the reference's DummyBackend test
        seam: the same step function, sharded vs not, must agree.
        """
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training import TrainState, make_optimizer, make_dalle_train_step

        model = DALLE(
            dim=32, depth=2, num_image_tokens=16, image_fmap_size=4,
            num_text_tokens=26, text_seq_len=6, heads=2, dim_head=8,
        )
        text = jax.random.randint(jax.random.PRNGKey(0), (8, 6), 1, 26)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 16)
        batch = {"text": text, "image_tokens": tokens}
        params = model.init(jax.random.PRNGKey(2), text, tokens)["params"]
        tx = make_optimizer(1e-3, clip_grad_norm=0.5)
        state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
        step = make_dalle_train_step(model)
        rng = jax.random.PRNGKey(3)

        ref_state, ref_metrics = jax.jit(step)(state, batch, rng)

        mesh = make_mesh(dp=2, fsdp=2, tp=2)
        state_sh = state_shardings(state, mesh)
        bs = batch_sharding(mesh)
        batch_sh = {k: jax.device_put(v, bs) for k, v in batch.items()}
        sharded_state = jax.device_put(state, state_sh)
        sharded_step = jax.jit(
            step, in_shardings=(state_sh, {k: bs for k in batch}, None),
            out_shardings=(state_sh, None),
        )
        new_state, metrics = sharded_step(sharded_state, batch_sh, rng)

        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(ref_state.params), jax.tree.leaves(new_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )


    @pytest.mark.parametrize("executor", ["unrolled", "scan"])
    def test_sharded_flash_step_matches_unsharded(self, executor):
        """A Pallas call is a single-device program: under a multi-device
        pjit the TPU compiler refuses the bare kernel ("Mosaic kernels
        cannot be automatically partitioned"), so with the trainer's mesh
        on the model (`train_mesh`) the flash kernel runs under shard_map —
        batch over (dp, fsdp), heads over tp — and the step still equals
        the single-device one."""
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training import TrainState, make_optimizer, make_dalle_train_step

        kw = dict(
            dim=32, depth=2, num_image_tokens=16, image_fmap_size=4,
            num_text_tokens=26, text_seq_len=6, heads=2, dim_head=8,
            attn_impl="flash", executor=executor,
        )
        mesh = make_mesh(dp=2, fsdp=2, tp=2)
        plain, sharded_model = DALLE(**kw), DALLE(train_mesh=mesh, **kw)
        text = jax.random.randint(jax.random.PRNGKey(0), (8, 6), 1, 26)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 16)
        batch = {"text": text, "image_tokens": tokens}
        params = plain.init(jax.random.PRNGKey(2), text, tokens)["params"]
        tx = make_optimizer(1e-3, clip_grad_norm=0.5)
        rng = jax.random.PRNGKey(3)

        state = TrainState.create(apply_fn=plain.apply, params=params, tx=tx)
        ref_state, ref_metrics = jax.jit(make_dalle_train_step(plain))(state, batch, rng)

        state = TrainState.create(apply_fn=sharded_model.apply, params=params, tx=tx)
        state_sh = state_shardings(state, mesh)
        bs = batch_sharding(mesh)
        sharded_step = jax.jit(
            make_dalle_train_step(sharded_model),
            in_shardings=(state_sh, {k: bs for k in batch}, None),
            out_shardings=(state_sh, None),
        )
        new_state, metrics = sharded_step(
            jax.device_put(state, state_sh),
            {k: jax.device_put(v, bs) for k, v in batch.items()}, rng,
        )
        np.testing.assert_allclose(
            float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree.leaves(ref_state.params), jax.tree.leaves(new_state.params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )


class TestRingInModel:
    """attn_impl="ring": sequence-parallel DALLE must match the dense model
    bit-for-bit in function value and gradients (long-context training path,
    beyond the reference's sparsity-only sequence scaling, SURVEY.md §5.7)."""

    def _models(self, mesh):
        from dalle_pytorch_tpu.models.dalle import DALLE

        kw = dict(
            dim=32, depth=2, heads=2, dim_head=16, num_image_tokens=32,
            image_fmap_size=4, num_text_tokens=30, text_seq_len=8,
            shift_tokens=True, rotary_emb=True,
        )
        dense = DALLE(attn_impl="dense", **kw)
        ring = DALLE(attn_impl="ring", sp_mesh=mesh, **kw)
        return dense, ring

    @pytest.mark.slow  # ~50 s: grads through the 8-way ring compile the
    # largest program in the suite (tier-1 budget); the cheaper ring
    # tests above keep the fast-tier parity signal
    def test_forward_and_grads_match_dense(self):
        mesh = make_mesh(dp=1, sp=8)
        dense, ring = self._models(mesh)
        text = jnp.asarray(
            np.random.RandomState(0).randint(1, 30, size=(2, 8)), jnp.int32
        )
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 32, size=(2, 16)), jnp.int32
        )
        params = dense.init(jax.random.PRNGKey(0), text, toks)

        def loss(v, m):
            return m.apply(v, text, toks, return_loss=True)[0]

        l_dense = loss(params, dense)
        l_ring = loss(params, ring)
        np.testing.assert_allclose(
            float(l_dense), float(l_ring), rtol=2e-5
        )
        g_dense = jax.grad(loss)(params, dense)
        g_ring = jax.grad(loss)(params, ring)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_dense), jax.tree_util.tree_leaves(g_ring)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)

    def test_ring_requires_mesh(self):
        from dalle_pytorch_tpu.models.dalle import DALLE

        model = DALLE(
            dim=32, depth=1, heads=2, dim_head=16, num_image_tokens=32,
            image_fmap_size=4, num_text_tokens=30, text_seq_len=8,
            attn_impl="ring",
        )
        text = jnp.ones((1, 8), jnp.int32)
        toks = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(AssertionError, match="sp_mesh"):
            model.init(jax.random.PRNGKey(0), text, toks)


@pytest.mark.slow
class TestLongContextRing:
    """Long-context claim with substance: ring attention at seq 4096
    (4x the flagship's 1280) sharded over all 8 virtual devices, parity
    vs the dense oracle AND through a DALLE gradient step."""

    def test_seq4096_parity(self):
        mesh = make_mesh(dp=1, sp=8)
        b, h, n, d = 1, 2, 4096, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, n, d)) * 0.5 for kk in ks)
        out_ring = ring_attention_sharded(mesh, q, k, v, causal=True)
        causal = jnp.tril(jnp.ones((n, n), bool))[None, None]
        out_dense = dense_attention(q, k, v, mask=causal)
        np.testing.assert_allclose(
            np.asarray(out_ring), np.asarray(out_dense), rtol=2e-3, atol=2e-4
        )

    def test_long_seq_train_step_grads_finite(self):
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training import (
            TrainState, make_optimizer, make_dalle_train_step,
        )

        mesh = make_mesh(dp=1, sp=8)
        # text 1024 + 32x32 image grid = seq 2048 over 8 sp shards
        model = DALLE(
            dim=64, depth=2, heads=4, dim_head=16, num_image_tokens=64,
            image_fmap_size=32, num_text_tokens=64, text_seq_len=1024,
            shift_tokens=True, rotary_emb=True,
            attn_impl="ring", sp_mesh=mesh,
        )
        text = jnp.ones((1, 1024), jnp.int32)
        tokens = jnp.zeros((1, 1024), jnp.int32)
        params = jax.jit(model.init)(jax.random.PRNGKey(0), text, tokens)["params"]
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=make_optimizer(1e-3)
        )
        step = jax.jit(make_dalle_train_step(model))
        state, metrics = step(
            state, {"text": text, "image_tokens": tokens}, jax.random.PRNGKey(1)
        )
        assert np.isfinite(float(metrics["loss"]))
