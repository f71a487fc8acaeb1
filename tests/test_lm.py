"""The causal language model over the shared trunk, against its plain
reference (`benchmark/reference/mellum_ref.py`), at a small size on the CPU:
hidden 64, 4 query heads over 2 K/V heads of 16, 8 experts with 2 per token
of which 4 are held, window 8, length 32, one period of 4 layers (window,
window, window, full). Flash kernels interpreted, float32."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build_lm
from benchmark.reference import mellum_ref
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.models.transformer import Transformer
from dalle_pytorch_tpu.training import TrainState, make_lm_train_step, make_optimizer

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 32, 7
OPT = {"learning_rate": 3e-4, "clip_grad_norm": 0.5, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-mellum.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def pair(cfg):
    """(program model, its seeded variables, the reference's weights)."""
    mdl = CausalLM.from_config(cfg, N, dtype="float32", reversible=True)
    return mdl, build_lm.seeded_variables(cfg, mdl, SEED), mellum_ref.init_params(cfg, SEED)


def _tokens(rows=2, seed=0, vocab=96):
    return jnp.asarray(np.random.default_rng(seed).integers(0, vocab, (rows, N)), jnp.int32)


def test_logits_and_loss_match_the_reference(cfg, pair):
    mdl, variables, ref = pair
    tokens = _tokens()
    np.testing.assert_allclose(
        mdl.apply(variables, tokens), mellum_ref.logits_fn(ref, cfg, tokens), atol=2e-5)
    np.testing.assert_allclose(
        mdl.apply(variables, tokens, return_loss=True),
        mellum_ref.loss_fn(ref, cfg, tokens), rtol=1e-6)


def test_every_gradient_leaf_matches_the_reference(cfg, pair):
    mdl, variables, ref = pair
    tokens = _tokens(seed=1)
    got = jax.grad(lambda p: mdl.apply({"params": p}, tokens, return_loss=True))(
        variables["params"])
    got = build_lm.from_program(got, mdl.depth)
    want = jax.grad(lambda p: mellum_ref.loss_fn(p, cfg, tokens))(ref)
    assert set(got) == set(want) == set(mellum_ref.param_shapes(cfg))
    for name, w in want.items():
        assert float(jnp.max(jnp.abs(w))) > 0, name  # no leaf is idle
        np.testing.assert_allclose(got[name], w, atol=2e-5 * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)


@pytest.mark.parametrize("warmup_steps", [0, 2, 200])
def test_three_optimizer_steps_match_the_reference(cfg, pair, warmup_steps):
    """Through the trainer's own pieces: remat, clip, Adam (at its rate, and
    inside and past a linear warm-up), the step's routing counts; the
    reference follows with its own Adam."""
    mdl, variables, _ = pair
    opt = dict(OPT, warmup_steps=warmup_steps)
    batches = [np.asarray(_tokens(seed=10 + i)) for i in range(3)]
    state = TrainState.create(
        apply_fn=mdl.apply, params=variables["params"],
        tx=make_optimizer(OPT["learning_rate"], clip_grad_norm=OPT["clip_grad_norm"],
                          warmup_steps=warmup_steps))
    step = jax.jit(make_lm_train_step(mdl))
    losses = []
    for i, tokens in enumerate(batches):
        state, metrics = step(state, {"tokens": jnp.asarray(tokens)}, jax.random.PRNGKey(i))
        losses.append(float(metrics["loss"]))
        assert metrics["moe_load"].shape == (4, 4) and int(metrics["moe_dropped"].sum()) == 0
        assert np.array_equal(metrics["moe_load"].sum(-1), metrics["moe_rows"])
    want = mellum_ref.train_steps(cfg, SEED, batches, opt)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    change = jax.tree.map(lambda a, b: a - b, state.params, variables["params"])
    got = jax.device_get(build_lm.leaf_norms_of(change, mdl.depth))
    for name, w in want["change_norms"].items():
        np.testing.assert_allclose(got[name], w, rtol=2e-3, err_msg=name)


def test_the_step_carries_what_the_routed_layers_counted(cfg, pair):
    """`moe_load`, `moe_rows`, `moe_dropped` to the integer, as the layer
    counted them before its moves followed the rows present (this seed's
    numbers, read off the parent commit), and `moe_moved` beside them: the
    rows present rounded up to the chunk, at most the buffer."""
    from dalle_pytorch_tpu.models import moe

    mdl, variables, _ = pair
    state = TrainState.create(apply_fn=mdl.apply, params=variables["params"],
                              tx=make_optimizer(OPT["learning_rate"]))
    _, metrics = jax.jit(make_lm_train_step(mdl))(
        state, {"tokens": _tokens(seed=10)}, jax.random.PRNGKey(0))
    got = {k: np.asarray(v).tolist() for k, v in metrics.items() if k.startswith("moe_")}
    assert got["moe_load"] == [[7, 4, 4, 9], [4, 11, 37, 16], [14, 10, 14, 4], [21, 6, 27, 24]]
    assert got["moe_rows"] == [24, 68, 42, 78] and got["moe_dropped"] == [0, 0, 0, 0]
    buffer_rows = min(mdl.trunk["moe_buffer_rows"], 2 * N * cfg["num_experts_per_tok"])
    chunk = moe._chunk(buffer_rows)
    assert got["moe_moved"] == [min(-(-rows // chunk) * chunk, buffer_rows)
                                for rows in got["moe_rows"]]
    assert all(r <= m <= buffer_rows for r, m in zip(got["moe_rows"], got["moe_moved"]))


def test_first_layer_choices_match_the_reference(cfg, pair):
    mdl, variables, ref = pair
    tokens = _tokens(seed=2)
    got = np.asarray(mdl.apply(variables, tokens, method=CausalLM.route_choices))
    want = mellum_ref.route_choices(ref, cfg, np.asarray(tokens))
    assert got.shape == want.shape == (2, N, 2)
    assert np.array_equal(np.sort(got, -1), np.sort(want, -1))


def test_the_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer(cfg):
    """Expert parallelism's contract (model-configs guide, section 4): each
    of the chips that share a layer computes its own experts' part; the
    parts, with the residual counted once, are the uncut reference's layer.
    Here 8 experts in four shares of 2, through the PROGRAM's routed layer."""
    from dalle_pytorch_tpu.models.moe import RoutedExperts

    whole = dict(cfg, num_experts=8)  # the reference holds every expert
    d = mellum_ref.dims(whole)
    ref = mellum_ref.init_params(whole, SEED)
    lp = {k: ref[k][0] for k in mellum_ref.LAYER_LEAVES}
    y = jax.random.normal(jax.random.PRNGKey(3), (N, d["dim"]))
    h2 = mellum_ref._rms(y, lp["norm_ff_g"], d["eps"])
    weights, _ = mellum_ref.route(h2, lp["router_w"], d)
    uncut = y + mellum_ref._experts(h2, weights, lp, d, None, held=(0, 8))
    parts = []
    for first in (0, 2, 4, 6):
        layer = RoutedExperts(dim=d["dim"], expert_dim=d["expert_dim"], experts_total=8,
                              experts_per_token=2, experts_held=(first, 2), buffer_rows=2 * N)
        params = {"router": lp["router_w"], "w_gate": lp["gate_w"][first:first + 2],
                  "w_up": lp["up_w"][first:first + 2], "w_out": lp["down_w"][first:first + 2]}
        parts.append(layer.apply({"params": params}, h2[None])[0])
    np.testing.assert_allclose(y + sum(parts), uncut, atol=2e-5)
    # and one share alone is what the cut reference computes
    cut = y + mellum_ref._experts(h2, weights, {k: v[:2] for k, v in lp.items() if v.ndim == 3},
                                  d, None, held=(0, 2))
    np.testing.assert_allclose(y + parts[0], cut, atol=2e-5)


def test_an_assignment_past_the_buffer_is_counted_as_dropped(cfg):
    mdl = CausalLM.from_config(cfg, N, dtype="float32", moe_buffer_rows=8)
    variables = build_lm.seeded_variables(cfg, mdl, SEED, check=False)
    _, aux = mdl.apply(variables, _tokens(), return_loss=True, mutable=["stats"])
    for layer in aux["stats"]["transformer"].values():
        assert int(layer["moe_dropped"]) == int(layer["moe_rows"]) - 8 > 0


def test_dense_and_flash_attention_agree_on_the_new_block(cfg, pair):
    _, variables, _ = pair
    tokens = _tokens(seed=4)
    out = [CausalLM.from_config(cfg, N, dtype="float32", attn_impl=impl).apply(variables, tokens)
           for impl in ("dense", "flash")]
    np.testing.assert_allclose(out[0], out[1], atol=2e-5)


def test_generate_says_what_is_missing_and_grouped_heads_decode_through_a_cache(cfg, pair):
    mdl, variables, _ = pair
    with pytest.raises(NotImplementedError, match="Queue 2 B"):
        mdl.apply(variables, method=CausalLM.generate)
    trunk = Transformer(dim=64, depth=1, seq_len=N, heads=4, dim_head=16, kv_heads=2,
                        rotary_emb=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, N, 64))
    params = trunk.init(jax.random.PRNGKey(0), x)
    # K/V heads shared by query heads: a prefill, then a step at the row's own index
    cache = trunk.init_cache(1, N + 1)
    assert cache["layer_0"]["attn"]["k"].shape == (1, 2, N + 1, 16)
    assert cache["layer_0"]["attn"]["index"].shape == (1,)
    with pytest.raises(NotImplementedError, match="has to start the rows' sequences"):
        trunk.apply(params, x[:, :N - 1], cache=cache)  # the start is said, never guessed
    _, cache = trunk.apply(params, x[:, :N - 1], cache=cache, start=True)
    out, cache = trunk.apply(params, x[:, N - 1:], cache=cache)
    np.testing.assert_allclose(out[:, 0], trunk.apply(params, x)[:, -1], atol=2e-5)
    assert int(cache["layer_0"]["attn"]["index"][0]) == N


def test_the_scan_executor_refuses_the_new_options():
    trunk = Transformer(dim=64, depth=2, seq_len=N, heads=4, dim_head=16, kv_heads=2,
                        rotary_emb=False, executor="scan")
    with pytest.raises(ValueError, match='executor="scan" does not support the block option'):
        trunk.init(jax.random.PRNGKey(0), jnp.zeros((1, N, 64)))


def test_a_window_layer_without_a_window_length_is_refused():
    """Also where every other block option is the DALL-E block's: such a
    trunk must not quietly build full causal attention."""
    trunk = Transformer(dim=64, depth=2, seq_len=N, heads=4, dim_head=16, rotary_emb=False,
                        attn_types=("window", "full"))
    with pytest.raises(AssertionError, match="no window length"):
        trunk.init(jax.random.PRNGKey(0), jnp.zeros((1, N, 64)))


@pytest.mark.parametrize("name,leaves,digest", [
    ("dalle-flagship", 162, "2ef544e1db6836a7"),
    ("dalle-paper64", 19, "35d5a93cd7103b2e"),
    ("mellum2-12b-ep4", 43, "69dcdcd7311ddf51"),
])
def test_the_dalle_configurations_build_the_parameter_trees_they_built(name, leaves, digest):
    """No default of an accepted configuration moved: paths, shapes and
    dtypes of the benchmark configurations' trees, the DALL-E ones as the
    commit before the block options built them, the language model's as the
    commit before the generation options did."""
    import hashlib

    from benchmark import build

    with open(ROOT / "benchmark" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    if "model" in cfg:
        mdl = build.model(cfg)
        tokens = (jnp.zeros((1, mdl.text_seq_len), jnp.int32),
                  jnp.zeros((1, mdl.image_seq_len), jnp.int32))
    else:
        mdl = CausalLM.from_config(cfg, 8192)
        tokens = (jnp.zeros((1, mdl.seq_len), jnp.int32),)
    shapes = jax.eval_shape(mdl.init, jax.random.PRNGKey(0), *tokens)["params"]
    flat = sorted(
        ("/".join(str(getattr(k, "key", k)) for k in path), tuple(x.shape), str(x.dtype))
        for path, x in jax.tree_util.tree_leaves_with_path(shapes))
    assert len(flat) == leaves
    assert hashlib.sha256(repr(flat).encode()).hexdigest()[:16] == digest


def test_buffer_rows_that_hold_no_assignment_are_never_read(cfg, monkeypatch):
    """The grouped product leaves the rows past its groups unwritten; the
    routed layer must select around them, never multiply them by 0: with NaN
    put there the layer's output and every gradient are what they were."""
    from dalle_pytorch_tpu.models import moe

    d = mellum_ref.dims(cfg)
    layer = moe.RoutedExperts(
        dim=d["dim"], expert_dim=d["expert_dim"], experts_total=d["experts_total"],
        experts_per_token=d["per_token"], experts_held=(0, d["experts_held"]), buffer_rows=2 * N)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, N, d["dim"]))
    params = {"params": layer.init(jax.random.PRNGKey(6), x)["params"]}
    loss = lambda p, x: jnp.sum(layer.apply(p, x) ** 2)
    want = jax.value_and_grad(loss, (0, 1))(params, x)
    real = moe.grouped_matmul

    def poisoned(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes)
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    got = jax.value_and_grad(loss, (0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_the_warm_up_raises_the_rate_linearly_and_then_holds_it():
    tx = make_optimizer(3e-4, clip_grad_norm=0.5, warmup_steps=4)
    params = {"w": jnp.ones(3)}
    state, rates = tx.init(params), []
    for _ in range(6):
        _, state = tx.update({"w": jnp.asarray([1.0, -2.0, 3.0])}, state, params)
        rates.append(float(state.hyperparams["learning_rate"]))
    np.testing.assert_allclose(rates, [0.75e-4, 1.5e-4, 2.25e-4, 3e-4, 3e-4, 3e-4], rtol=1e-6)
    plain = make_optimizer(3e-4, clip_grad_norm=0.5)  # no warm-up: the rate as given
    assert float(plain.init(params).hyperparams["learning_rate"]) == pytest.approx(3e-4)


def test_the_trainer_builds_a_published_config_as_the_benchmark_does(cfg):
    """`CausalLM.from_config` is the one reading of the published keys: the
    benchmark's loop calls it, and the trainer's `--config` path builds the
    same module."""
    import train_lm

    args = train_lm.parse_args([
        "--tokens", "seeded:1.0", "--seq_len", str(N), "--batch_size", "2",
        "--config", str(ROOT / "benchmark" / "configs" / "_tiny-mellum.json")])
    mdl, _ = train_lm.build_model(args)
    assert mdl == CausalLM.from_config(cfg, N, reversible=True)
    assert mdl.trunk["experts_total"] == 8 and mdl.trunk["experts_held"] == (0, 4)
    assert mdl.trunk["attn_types"] == ("window", "window", "window", "full")


def test_the_trainer_script_trains_and_needs_nothing_of_the_benchmark(tmp_path):
    """`train_lm.py` end to end at a toy size (DEFAULT_CONFIG with `--set`),
    a warm-up included; the program's entry point does not import the
    yardstick package."""
    import os
    import subprocess
    import sys

    code = (
        "import sys, train_lm\n"
        "train_lm.main(['--tokens', 'seeded:1.0', '--steps', '3', '--log_every', '1',"
        " '--seq_len', '32', '--batch_size', '2', '--warmup_steps', '2', '--debug', '--set',"
        " 'hidden_size=32', 'num_attention_heads=2', 'head_dim=16', 'num_key_value_heads=1',"
        " 'sliding_window=8', 'num_experts=4', 'moe_intermediate_size=16', 'vocab_size=64',"
        " 'num_hidden_layers=2', 'dtype=float32'])\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'benchmark'], 'benchmark imported'\n"
    )
    done = subprocess.run(  # in a directory of its own: the logger writes under `logs/`
        [sys.executable, "-c", code], cwd=tmp_path, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)})
    assert done.returncode == 0, done.stderr[-2000:]
    steps = [line for line in done.stdout.splitlines() if line.startswith("step ")]
    assert len(steps) == 3 and all("moe_dropped 0" in line for line in steps)
    assert all(" moe_moved " in line for line in steps)
