"""What a remat layer keeps across remat: `flash_attention`'s forward rule
NAMES what it hands its backward (`pallas_attention.RESIDUAL_NAMES`), and
`FeedForward` names its two products, the first as the GEGLU reads it
(`transformer.FF_RESIDUAL_NAMES`). The policy `flash_residuals`
(`models/transformer.py:resolve_remat_policy`) keeps exactly the kernels'
five, so a remat layer's backward runs the forward kernel, the projection and
the rotary before it ONCE where "save nothing" runs them twice;
`layer_residuals` keeps the feed-forward's products beside them, so those run
once too. `DALLE` and `TrainConfig` take `layer_residuals` by default;
`CausalLM` keeps none.

CPU, kernels interpreted, tiny widths. Counted in the gradient's jaxpr (what
XLA is handed), a scan's body times its length."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.models.lm import CausalLM
from dalle_pytorch_tpu.models.transformer import (
    FF_RESIDUAL_NAMES,
    FLASH_RESIDUALS,
    LAYER_RESIDUALS,
    FeedForward,
    Transformer,
    resolve_remat_policy,
)
from dalle_pytorch_tpu.ops import pallas_attention
from dalle_pytorch_tpu.ops.pallas_attention import flash_attention
from dalle_pytorch_tpu.training.config import TrainConfig, load_config
from dalle_pytorch_tpu.training.pipeline import REMAT_POLICIES, dalle_from_config

ROOT = Path(__file__).resolve().parent.parent
DEPTH, FMAP, FF_MULT = 2, 3, 4
SEQ = 7 + FMAP * FMAP  # 16 rows: one tile

# heads x dim_head: a 64-wide head pairs into a 128-lane block (token-major,
# the flagship's path), an 8-wide one goes to the kernels head-major
LAYOUTS = {"token_major": (2, 64), "head_major": (2, 8)}


def equations(jaxpr, times: int = 1, remat: bool = False):
    """(equation, how often it runs, whether inside a checkpoint's recompute)
    over a jaxpr and its sub-jaxprs, a scan's body once for each of its steps."""
    for eqn in jaxpr.eqns:
        yield eqn, times, remat
        inner_times = times * (eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1)
        inner_remat = remat or eqn.primitive.name in ("checkpoint", "remat2")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub, inner_times, inner_remat)


def kernel_calls(jaxpr, name: str) -> int:
    """Calls of the Pallas kernel `name` that a jaxpr makes."""
    return sum(
        times for eqn, times, _ in equations(jaxpr)
        if eqn.primitive.name == "pallas_call"
        and str(eqn.params.get("name") or getattr(
            eqn.params.get("name_and_src_info"), "name", "")).startswith(name))


def ff_products(jaxpr, x) -> tuple:
    """Runs of the feed-forward's two products a jaxpr makes, (first, second):
    the `dot_general`s that take x's rows from dim to 2 x `ff_mult` x dim
    columns and from `ff_mult` x dim back to dim (no gradient has those
    widths: a weight's has no batch, an operand's goes the other way)."""
    dim = x.shape[-1]
    runs = {(dim, 2 * FF_MULT * dim): 0, (FF_MULT * dim, dim): 0}
    for eqn, times, _ in equations(jaxpr):
        if eqn.primitive.name == "dot_general":
            widths = (eqn.invars[0].aval.shape[-1], eqn.outvars[0].aval.shape[-1])
            if widths in runs and eqn.outvars[0].aval.shape[:-1] == x.shape[:-1]:
                runs[widths] += times
    return tuple(runs.values())


def trunk(layout: str, executor: str, policy, reversible=True) -> Transformer:
    heads, dim_head = LAYOUTS[layout]
    return Transformer(
        dim=heads * dim_head, depth=DEPTH, seq_len=SEQ, heads=heads, dim_head=dim_head,
        image_fmap_size=FMAP, rotary_emb=True, shift_tokens=True, attn_impl="flash",
        executor=executor, reversible=reversible, remat_policy=policy)


def trunk_io(layout: str, executor: str):
    heads, dim_head = LAYOUTS[layout]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, heads * dim_head))
    params = trunk(layout, executor, None).init(jax.random.PRNGKey(1), x)["params"]
    return params, x


def loss_of(tfm: Transformer, x):
    return lambda p: jnp.sum(jnp.tanh(tfm.apply({"params": p}, x)))


def test_the_policy_name_resolves_to_the_five_names_and_nothing_else_moves():
    assert pallas_attention.RESIDUAL_NAMES == (
        "flash_q", "flash_k", "flash_v", "flash_out", "flash_lse")
    assert FLASH_RESIDUALS == "flash_residuals" and FLASH_RESIDUALS in REMAT_POLICIES
    assert LAYER_RESIDUALS == "layer_residuals" and LAYER_RESIDUALS in REMAT_POLICIES
    assert FF_RESIDUAL_NAMES == ("ff_hidden", "ff_out")
    assert not set(FF_RESIDUAL_NAMES) & set(pallas_attention.RESIDUAL_NAMES)
    for name in (FLASH_RESIDUALS, LAYER_RESIDUALS):
        # ONE object a name, whoever asks: jax caches a jitted emitter's
        # partial evaluation by the policy's identity
        assert callable(resolve_remat_policy(name))
        assert resolve_remat_policy(name) is resolve_remat_policy(name)
    assert resolve_remat_policy(FLASH_RESIDUALS) is not resolve_remat_policy(LAYER_RESIDUALS)
    assert resolve_remat_policy(None) is None
    assert resolve_remat_policy("nothing_saveable") is jax.checkpoint_policies.nothing_saveable
    assert resolve_remat_policy("dots_saveable") is jax.checkpoint_policies.dots_saveable


# per layer, forward + under remat: (the flash forward kernel, the
# feed-forward's first product, its second)
RUNS = {
    LAYER_RESIDUALS: (1, 1, 1),
    FLASH_RESIDUALS: (1, 2, 2),
    "nothing_saveable": (2, 2, 2),
    None: (2, 2, 2),
}


@pytest.mark.parametrize("policy", list(RUNS), ids=str)
@pytest.mark.parametrize("executor", ["unrolled", "scan"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_remat_layer_runs_once_what_its_policy_keeps_and_twice_what_it_does_not(
        layout, executor, policy):
    params, x = trunk_io(layout, executor)
    grad = jax.make_jaxpr(jax.grad(loss_of(trunk(layout, executor, policy), x)))(params)
    kernel, *products = RUNS[policy]
    assert {k: kernel_calls(grad.jaxpr, k) for k in ("fwd_flash", "dq_flash", "dkv_flash")} \
        == {"fwd_flash": kernel * DEPTH, "dq_flash": DEPTH, "dkv_flash": DEPTH}
    assert ff_products(grad.jaxpr, x) == tuple(n * DEPTH for n in products)


def test_without_remat_everything_runs_once_whatever_the_policy():
    params, x = trunk_io("token_major", "unrolled")
    tfm = trunk("token_major", "unrolled", LAYER_RESIDUALS, reversible=False)
    grad = jax.make_jaxpr(jax.grad(loss_of(tfm, x)))(params)
    assert kernel_calls(grad.jaxpr, "fwd_flash") == DEPTH
    assert ff_products(grad.jaxpr, x) == (DEPTH, DEPTH)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_names_reach_the_kernel_under_the_trainers_mesh(layout):
    """`Attention._flash` / `_flash_columns` under a multi-device `train_mesh`
    call the same `flash_attention` inside a `shard_map`: the policy sees the
    names there as well."""
    from dalle_pytorch_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(devices=jax.devices()[:4], dp=2, tp=2)
    params, x = trunk_io(layout, "unrolled")
    calls = {}
    for policy in (LAYER_RESIDUALS, FLASH_RESIDUALS, "nothing_saveable"):
        tfm = trunk(layout, "unrolled", policy).clone(train_mesh=mesh)
        grad = jax.make_jaxpr(jax.grad(loss_of(tfm, x)))(params)
        assert "shard_map" in str(grad)
        calls[policy] = (kernel_calls(grad.jaxpr, "fwd_flash"), *ff_products(grad.jaxpr, x))
    assert calls == {policy: tuple(n * DEPTH for n in RUNS[policy]) for policy in calls}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_projection_and_the_rotary_before_the_kernel_are_not_built_again(layout):
    """q, k and v are kept too: the backward's recompute holds no product of
    `to_qkv`'s width (3 x inner columns) under `flash_residuals`, nor under
    `layer_residuals`, which keeps them as well."""
    params, x = trunk_io(layout, "unrolled")
    inner = x.shape[-1]

    def qkv_products(policy):
        grad = jax.make_jaxpr(jax.grad(loss_of(trunk(layout, "unrolled", policy), x)))(params)
        return sum(
            remat and eqn.primitive.name == "dot_general"
            and eqn.outvars[0].aval.shape[-1] == 3 * inner
            for eqn, _, remat in equations(grad.jaxpr))

    assert qkv_products("nothing_saveable") == DEPTH
    assert qkv_products(FLASH_RESIDUALS) == qkv_products(LAYER_RESIDUALS) == 0


@pytest.mark.parametrize("executor", ["unrolled", "scan"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_loss_and_every_gradient_leaf_are_bitwise_equal_under_the_four(layout, executor):
    """Op by op, without `jit`: each primitive then rounds as itself in all
    four, where XLA's CPU fusions of four different programs do not."""
    params, x = trunk_io(layout, executor)
    results = {}
    for label, policy, reversible in (("layer", LAYER_RESIDUALS, True),
                                      ("kept", FLASH_RESIDUALS, True),
                                      ("nothing", "nothing_saveable", True),
                                      ("no remat", None, False)):
        tfm = trunk(layout, executor, policy, reversible)
        results[label] = jax.value_and_grad(loss_of(tfm, x))(params)
    want_loss, want_grads = results["no remat"]
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(want_grads))
    for label in ("layer", "kept", "nothing"):
        loss, grads = results[label]
        np.testing.assert_array_equal(loss, want_loss, err_msg=label)
        for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads),
                                     jax.tree.leaves(want_grads)):
            np.testing.assert_array_equal(
                got, want, err_msg=f"{label} {jax.tree_util.keystr(path)}")


def test_outside_a_checkpoint_the_names_change_nothing():
    """A name is the identity: the kernel's result and its gradients with the
    names are those of the same kernels called without the custom rule's
    names, and the plain forward's jaxpr holds no `name` at all."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 16, 8)) for i in range(3))
    f = lambda *qkv: jnp.sum(jnp.tanh(flash_attention(*qkv)))
    assert "name[" not in str(jax.make_jaxpr(f)(q, k, v))
    grad = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    assert kernel_calls(grad.jaxpr, "fwd_flash") == 1
    for name in pallas_attention.RESIDUAL_NAMES:
        assert str(grad).count(f"name={name}]") == 1, name


@pytest.mark.parametrize("program", ["forward", "gradient"])
def test_outside_a_checkpoint_the_feed_forwards_name_lowers_to_nothing(program, monkeypatch):
    """`FeedForward` names its products wherever it runs, a sampler's step
    included: the jaxpr holds each name once and the lowered program is, byte
    for byte, that of the same module with no name in it."""
    from dalle_pytorch_tpu.models import transformer

    ff = FeedForward(dim=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    params = ff.init(jax.random.PRNGKey(1), x)

    def program_of():  # a function of its own each time: jax keeps traces by function
        f = lambda p, x: jnp.sum(jnp.tanh(ff.apply(p, x)))
        return jax.grad(f) if program == "gradient" else f

    for name in FF_RESIDUAL_NAMES:
        assert str(jax.make_jaxpr(program_of())(params, x)).count(f"name={name}]") == 1
    named = jax.jit(program_of()).lower(params, x).as_text()
    monkeypatch.setattr(transformer, "checkpoint_name", lambda value, name: value)
    assert "name[" not in str(jax.make_jaxpr(program_of())(params, x))
    assert jax.jit(program_of()).lower(params, x).as_text() == named


def test_dalle_and_the_train_config_keep_the_residuals_by_default_and_causal_lm_does_not():
    assert DALLE(dim=16, depth=1, num_image_tokens=8, image_fmap_size=2).remat_policy \
        == LAYER_RESIDUALS
    assert TrainConfig().model.remat_policy == LAYER_RESIDUALS
    assert load_config().model.remat_policy == LAYER_RESIDUALS
    assert Transformer(dim=16, depth=1, seq_len=8).remat_policy is None
    with open(ROOT / "benchmark" / "configs" / "_tiny-mellum.json") as f:
        lm = CausalLM.from_config(json.load(f), 32, dtype="float32", reversible=True)
    assert lm.remat_policy is None
    assert dalle_from_config(load_config(), 32, 4, 100).remat_policy == LAYER_RESIDUALS


@pytest.mark.parametrize("asked", [None, FLASH_RESIDUALS, LAYER_RESIDUALS], ids=str)
def test_a_causal_lm_remat_layer_runs_the_forward_kernel_twice_unless_asked(asked):
    """`CausalLM.remat_policy` is `None` and its layers run the kernel twice;
    the kernels' names reach it, so either policy engages where asked for,
    and no layer of it is a `FeedForward`: the new names are nowhere in it."""
    with open(ROOT / "benchmark" / "configs" / "_tiny-mellum.json") as f:
        cfg = json.load(f)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 32)), jnp.int32)

    lm = CausalLM.from_config(cfg, 32, dtype="float32", reversible=True, attn_impl="flash")
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0), tokens)["params"]
    assert lm.remat_policy is None
    grad = jax.make_jaxpr(jax.grad(
        lambda p: lm.clone(remat_policy=asked).apply({"params": p}, tokens, return_loss=True)
    ))(params)
    assert kernel_calls(grad.jaxpr, "fwd_flash") == (2 if asked is None else 1) * lm.depth
    assert not [name for name in FF_RESIDUAL_NAMES if name in str(grad)]


@pytest.mark.parametrize("name, accepted", [
    (LAYER_RESIDUALS, True),
    (FLASH_RESIDUALS, True),
    ("nothing_saveable", True),
    ("dots_with_no_batch_dims_saveable", True),
    ("null", True),
    ("bogus_policy", False),
    ("save_only_these_names", False),  # a FACTORY: as a policy it saves everything
    ("save_any_names_but_these", False),
])
def test_dalle_from_config_takes_the_new_name_and_refuses_unknown_ones_and_factories(
        name, accepted):
    cfg = load_config(overrides=[f"model.remat_policy={name}", "model.reversible=true"])
    if not accepted:
        with pytest.raises(ValueError, match="unknown model.remat_policy"):
            dalle_from_config(cfg, 32, 4, 100)
        return
    model = dalle_from_config(cfg, 32, 4, 100)
    assert model.remat_policy == (None if name == "null" else name)
    resolve_remat_policy(model.remat_policy)  # and every accepted name resolves
