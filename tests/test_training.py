import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dalle_pytorch_tpu.models.dvae import DiscreteVAE
from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.training import (
    TrainState,
    make_optimizer,
    make_vae_train_step,
    make_dalle_train_step,
    set_learning_rate,
    get_learning_rate,
    ReduceLROnPlateau,
    ExponentialDecay,
)


def small_dalle():
    return DALLE(
        dim=32, depth=1, num_image_tokens=16, image_fmap_size=4,
        num_text_tokens=26, text_seq_len=6, heads=2, dim_head=8,
    )


def dalle_state(model, batch):
    params = model.init(
        jax.random.PRNGKey(0), batch["text"], batch["image_tokens"]
    )["params"]
    return TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer(1e-3, 0.5)
    )


@pytest.fixture
def batch():
    return {
        "text": jax.random.randint(jax.random.PRNGKey(0), (4, 6), 1, 26),
        "image_tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 16),
    }


class TestVaeStep:
    def test_loss_decreases(self):
        vae = DiscreteVAE(
            image_size=16, num_tokens=16, codebook_dim=16, num_layers=1,
            hidden_dim=16, straight_through=False,
        )
        img = jax.random.uniform(jax.random.PRNGKey(0), (4, 16, 16, 3))
        params = vae.init(
            {"params": jax.random.PRNGKey(0), "gumbel": jax.random.PRNGKey(1)}, img
        )["params"]
        state = TrainState.create(
            apply_fn=vae.apply, params=params, tx=make_optimizer(3e-3)
        )
        step = jax.jit(make_vae_train_step(vae))
        rng = jax.random.PRNGKey(2)
        first = last = None
        for i in range(30):
            rng, r = jax.random.split(rng)
            state, metrics = step(state, img, r, jnp.float32(0.9))
            if first is None:
                first = float(metrics["loss"])
            last = float(metrics["loss"])
        assert last < first

    def test_grad_accum_equivalence(self):
        vae = DiscreteVAE(
            image_size=16, num_tokens=8, codebook_dim=8, num_layers=1,
            hidden_dim=8, straight_through=False, temperature=1.0,
        )
        img = jax.random.uniform(jax.random.PRNGKey(0), (4, 16, 16, 3))
        params = vae.init(
            {"params": jax.random.PRNGKey(0), "gumbel": jax.random.PRNGKey(1)}, img
        )["params"]

        # identical halves => accumulated grads == single-batch grads
        img2 = jnp.concatenate([img[:2], img[:2]])
        state = TrainState.create(
            apply_fn=vae.apply, params=params, tx=make_optimizer(1e-3)
        )
        rng = jax.random.PRNGKey(5)
        s1, m1 = jax.jit(make_vae_train_step(vae, grad_accum=2))(
            state, img2, rng, jnp.float32(1.0)
        )
        # gumbel rngs differ between microbatches, so compare only finiteness
        assert np.isfinite(float(m1["loss"]))


class TestDalleStep:
    @pytest.mark.parametrize(
        "mode", ["forward_only", "forward_forward", "forward_reverse_partial", "reverse_only"]
    )
    def test_modes(self, batch, mode):
        model = small_dalle()
        state = dalle_state(model, batch)
        step = jax.jit(make_dalle_train_step(model, mode=mode))
        new_state, metrics = step(state, batch, jax.random.PRNGKey(0))
        assert np.isfinite(float(metrics["loss"]))
        if mode != "forward_only":
            assert "accuracy" in metrics
        if mode == "forward_forward":
            np.testing.assert_allclose(
                float(metrics["loss"]),
                float(metrics["forward_loss"]) + float(metrics["inverse_loss"]),
                rtol=1e-5,
            )
        assert int(new_state.step) == 1

    def test_in_step_vae_encode(self):
        """Frozen-VAE encode fused into the train step (ref `:619-627`)."""
        vae = DiscreteVAE(
            image_size=16, num_tokens=16, codebook_dim=8, num_layers=2, hidden_dim=8
        )
        img = jax.random.uniform(jax.random.PRNGKey(0), (4, 16, 16, 3))
        vae_params = vae.init(
            {"params": jax.random.PRNGKey(0), "gumbel": jax.random.PRNGKey(1)}, img
        )["params"]
        model = small_dalle()
        text = jax.random.randint(jax.random.PRNGKey(0), (4, 6), 1, 26)
        tok_probe = vae.apply(
            {"params": vae_params}, img, method=DiscreteVAE.get_codebook_indices
        )
        params = model.init(jax.random.PRNGKey(2), text, tok_probe)["params"]
        state = TrainState.create(
            apply_fn=model.apply, params=params, tx=make_optimizer(1e-3)
        )
        step = jax.jit(make_dalle_train_step(model, vae=vae))
        new_state, metrics = step(
            state, {"text": text, "images": img}, jax.random.PRNGKey(3),
            vae_params=vae_params,
        )
        assert np.isfinite(float(metrics["loss"]))

    @pytest.mark.parametrize("grad_accum,n_steps", [(1, 3), (2, 2)])
    def test_multi_step_matches_sequential(self, batch, grad_accum, n_steps):
        """One make_multi_step dispatch == n sequential step dispatches,
        bit-compatible params and per-key RNG stream (the trainer's
        fold_in(rng, global_step) keys are passed stacked). grad_accum=2
        covers the nested-scan combination the bench's OOM ladder
        produces on hardware."""
        from dalle_pytorch_tpu.training import make_multi_step, stack_batches

        model = small_dalle()
        state = dalle_state(model, batch)
        step = make_dalle_train_step(model, grad_accum=grad_accum)
        rng = jax.random.PRNGKey(7)
        keys = jnp.stack([jax.random.fold_in(rng, i) for i in range(n_steps)])

        seq_state = state
        losses = []
        jstep = jax.jit(step)
        for i in range(n_steps):
            seq_state, m = jstep(seq_state, batch, keys[i])
            losses.append(float(m["loss"]))

        batches = stack_batches([batch] * n_steps)
        multi = jax.jit(make_multi_step(step, n_steps))
        multi_state, mm = multi(state, batches, keys)

        assert int(multi_state.step) == n_steps
        np.testing.assert_allclose(
            float(mm["loss"]), np.mean(losses), rtol=1e-5
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            ),
            multi_state.params, seq_state.params,
        )

    def test_grad_accum_matches_full_batch(self, batch):
        model = small_dalle()
        state = dalle_state(model, batch)
        rng = jax.random.PRNGKey(0)
        _, m_full = jax.jit(make_dalle_train_step(model))(state, batch, rng)
        _, m_acc = jax.jit(make_dalle_train_step(model, grad_accum=2))(
            state, batch, rng
        )
        np.testing.assert_allclose(
            float(m_full["loss"]), float(m_acc["loss"]), rtol=1e-4
        )


class TestThroughputMeter:
    def test_stride_never_hits_exact_multiple(self, monkeypatch):
        """steps_per_dispatch strides (3,6,8,11,...) never land on a
        multiple of 10; the meter must still initialize and fire on
        interval crossings, scaling by the true step delta."""
        from dalle_pytorch_tpu.training.metrics import ThroughputMeter

        t = [100.0]
        monkeypatch.setattr(
            "dalle_pytorch_tpu.training.metrics.time",
            type("T", (), {"time": staticmethod(lambda: t[0])}),
        )
        meter = ThroughputMeter(interval=10)
        assert meter.update(3, batch_size=8) is None  # initializes here
        t[0] += 1.0
        assert meter.update(6, 8) is None
        t[0] += 1.0
        rate = meter.update(11, 8)  # crosses 10
        # 8 samples/step * (11-3) steps over 2.0s
        assert rate == pytest.approx(8 * 8 / 2.0)
        t[0] += 4.0
        assert meter.update(14, 8) is None
        assert meter.update(21, 8) == pytest.approx(8 * 10 / 4.0)

    def test_stride_one_matches_classic_cadence(self, monkeypatch):
        from dalle_pytorch_tpu.training.metrics import ThroughputMeter

        t = [0.0]
        monkeypatch.setattr(
            "dalle_pytorch_tpu.training.metrics.time",
            type("T", (), {"time": staticmethod(lambda: t[0])}),
        )
        meter = ThroughputMeter(interval=10)
        fired = []
        for step in range(1, 31):
            t[0] += 0.5
            r = meter.update(step, 4)
            if r is not None:
                fired.append((step, r))
        assert [s for s, _ in fired] == [10, 20, 30]
        # 9 steps over 4.5s for the first window, then exactly 10/5.0
        assert fired[0][1] == pytest.approx(4 * 9 / 4.5)
        assert fired[1][1] == pytest.approx(4 * 10 / 5.0)


class TestProfilerHook:
    def test_stride_skips_exact_step(self, monkeypatch, tmp_path):
        """steps_per_dispatch can step OVER profile_step; the hook must
        trace the first dispatch at/after it and only then stop training
        (previously it stopped without ever tracing)."""
        from dalle_pytorch_tpu.training.metrics import ProfilerHook

        calls = []
        # (the hook imports jax when it fires, not at module import: the
        # metrics registry also serves parents that must stay off jax)
        monkeypatch.setattr(
            "jax.profiler.start_trace", lambda d: calls.append(("start", d))
        )
        monkeypatch.setattr(
            "jax.profiler.stop_trace", lambda: calls.append(("stop",))
        )
        hook = ProfilerHook(True, profile_step=200, out_dir=str(tmp_path / "p"))
        # stride-3 window sequence around 200: 198 -> 201 -> 204
        hook.before_step(198)
        assert not calls and hook.after_step(201) is False
        hook.before_step(201)
        assert calls == [("start", str(tmp_path / "p"))]
        assert hook.after_step(204) is True  # traced, now stop
        assert calls[-1] == ("stop",)
        hook.before_step(204)  # must not restart
        assert len(calls) == 2


class TestLRControl:
    def test_set_get_lr(self, batch):
        model = small_dalle()
        state = dalle_state(model, batch)
        assert get_learning_rate(state) == pytest.approx(1e-3)
        state = set_learning_rate(state, 5e-4)
        assert get_learning_rate(state) == pytest.approx(5e-4)
        # the new lr is actually used by the next update
        step = jax.jit(make_dalle_train_step(model))
        new_state, _ = step(state, batch, jax.random.PRNGKey(0))
        assert get_learning_rate(new_state) == pytest.approx(5e-4)

    def test_plateau_reduces_after_patience(self):
        sched = ReduceLROnPlateau(factor=0.5, patience=2, cooldown=1, min_lr=1e-6)
        lr = 1.0
        lr = sched.step(1.0, lr)  # best
        for _ in range(3):
            lr = sched.step(2.0, lr)  # bad x3 > patience
        assert lr == pytest.approx(0.5)
        lr2 = sched.step(2.0, lr)  # cooldown swallows one bad epoch
        assert lr2 == pytest.approx(0.5)

    def test_exponential(self):
        sched = ExponentialDecay(gamma=0.5)
        assert sched.step(0.0, 1.0) == pytest.approx(0.5)


class TestFullStateResume:
    def test_resume_matches_uninterrupted_run(self, batch, tmp_path):
        """train(2N) == train(N) -> save -> load -> train(N): the loss
        trajectory must be identical, proving Adam moments + injected lr
        + step counter survive the checkpoint round trip (the reference's
        opt/scheduler reload, `/root/reference/train_dalle.py:330-338`)."""
        from dalle_pytorch_tpu.training.config import TrainConfig
        from dalle_pytorch_tpu.training.pipeline import (
            save_dalle_checkpoint,
            load_dalle_checkpoint,
            restore_opt_state,
        )

        model = small_dalle()
        step = jax.jit(make_dalle_train_step(model))

        def run(state, start, n):
            losses = []
            for i in range(start, start + n):
                state, metrics = step(state, batch, jax.random.PRNGKey(100 + i))
                losses.append(float(metrics["loss"]))
            return state, losses

        # uninterrupted: 4 steps
        state_a, losses_a = run(dalle_state(model, batch), 0, 4)

        # interrupted: 2 steps, checkpoint, reload, 2 more
        state_b, losses_b1 = run(dalle_state(model, batch), 0, 2)
        ckpt = tmp_path / "dalle.npz"
        save_dalle_checkpoint(
            str(ckpt), TrainConfig(), jax.device_get(state_b.params), None,
            epoch=0, vae_class_name="DiscreteVAE",
            opt_state=jax.device_get(state_b.opt_state),
            train_meta={"global_step": 2},
        )
        _, params, _, meta, opt_leaves = load_dalle_checkpoint(str(ckpt))
        fresh = TrainState.create(
            apply_fn=model.apply, params=params, tx=make_optimizer(1e-3, 0.5)
        )
        resumed = fresh.replace(
            opt_state=restore_opt_state(fresh.opt_state, opt_leaves),
            step=int(meta["train"]["global_step"]),
        )
        _, losses_b2 = run(resumed, 2, 2)

        np.testing.assert_allclose(losses_a, losses_b1 + losses_b2, rtol=1e-5)

    def test_restore_opt_state_mismatch_falls_back(self, batch):
        from dalle_pytorch_tpu.training.pipeline import restore_opt_state

        model = small_dalle()
        state = dalle_state(model, batch)
        leaves = [np.zeros((2, 2))] * 3  # wrong length/shapes
        restored = restore_opt_state(state.opt_state, leaves)
        assert jax.tree_util.tree_structure(restored) == jax.tree_util.tree_structure(
            state.opt_state
        )
