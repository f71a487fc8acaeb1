"""A Mamba-2 layer's two forms and the ungated experts, alone, at a small
size on the CPU: the chunked prefill form (`models/attention.py:ssm_chunked`)
and the token step's kernel (`ops/ssm_step.py`, interpreted) against the
reference's recurrence (`benchmark/reference/nemotron_h_ref.py`), and a routed
layer of relu2 experts cut in two against the reference's uncut layer."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h_ref as ref
from dalle_pytorch_tpu.models import decode_cache
from dalle_pytorch_tpu.models.attention import ssm_chunked
from dalle_pytorch_tpu.models.moe import RoutedExperts
from dalle_pytorch_tpu.ops.ssm_step import ssm_step, ssm_step_operands, ssm_step_reference

ROOT = Path(__file__).resolve().parent.parent
N, SEED = 150, 7  # 150 tokens: one whole chunk of 128 and a tail of 22


@pytest.fixture(scope="module")
def cfg():
    with open(ROOT / "benchmark" / "configs" / "_tiny-nemotron-h.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def inputs(cfg):
    """A Mamba-2 layer's (x, dt, b, c) on two sequences, from the reference,
    and its (A, D): [2, N, H, P], [2, N, H], [2, N, G, S] twice; [H] twice."""
    d = ref.dims(cfg)
    lp = ref.init_layer(cfg, SEED, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, N, d["dim"]))
    _, xs, dt, b, c = jax.vmap(lambda row: ref.ssm_inputs(row, lp, d))(x)
    return (xs, dt, b, c), (-jnp.exp(lp["a_log"]), lp["skip_g"])


@pytest.mark.parametrize("chunk", [128, 16])
def test_the_chunked_form_is_the_recurrence(inputs, chunk):
    """At 150 tokens, no multiple of 128 (nor of 16): outputs and the state it leaves."""
    (x, dt, b, c), (a, skip) = inputs
    y, state = ssm_chunked(x, dt, a, b, c, skip, chunk=chunk)
    want_y, want_s = jax.vmap(lambda *t: ref.recurrence(*t[:2], a, *t[2:], skip))(x, dt, b, c)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(state, want_s, rtol=1e-4, atol=2e-5)


def test_the_kernel_steps_as_the_recurrence_does(inputs):
    """`ssm_step` (interpreted) a token at a time against the reference's scan."""
    (x, dt, b, c), (a, skip) = inputs
    x, dt, b, c = (t[0] for t in (x, dt, b, c))  # one sequence
    want_y, want_s = ref.recurrence(x[:24], dt[:24], a, b[:24], c[:24], skip)
    heads, p = x.shape[1:]
    state = jnp.zeros((1, b.shape[-1], heads * p))
    outs = []
    for t in range(24):
        y, state = ssm_step(state, *ssm_step_operands(x[t][None], dt[t][None], a, skip),
                            b[t][None], c[t][None], block=1)
        outs.append(y[0].reshape(heads, p))
    np.testing.assert_allclose(np.stack(outs), want_y, atol=1e-6)
    got = decode_cache.running_state({"layer_0": {"attn": {"state": state}}}, 0, heads)
    np.testing.assert_allclose(got, want_s[None], atol=1e-6)


@pytest.mark.parametrize("block", [1, 2, None])
def test_ssm_step_matches_its_equations_at_every_group_block(block):
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    rows, h, p, g, n = 3, 8, 8, 2, 16
    state = jax.random.normal(ks[0], (rows, h, n, p))
    x, dt = jax.random.normal(ks[1], (rows, h, p)), jax.random.uniform(ks[2], (rows, h))
    b, c = jax.random.normal(ks[3], (rows, g, n)), jax.random.normal(ks[4], (rows, g, n))
    a, skip = -jax.random.uniform(ks[5], (h,), minval=1.0, maxval=16.0), jax.random.normal(ks[6], (h,))
    want_y, want_s = ssm_step_reference(state, x, dt, a, b, c, skip)
    y, new = ssm_step(decode_cache.pack_state(state), *ssm_step_operands(x, dt, a, skip), b, c,
                      block=block)
    np.testing.assert_allclose(y.reshape(rows, h, p), want_y, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new).reshape(rows, n, h, p).transpose(0, 2, 1, 3), want_s, atol=1e-5)


def test_the_two_halves_of_the_experts_and_the_shared_one_once_are_the_whole_layer(cfg):
    """A routed layer of ungated experts, cut in two as expert parallelism
    cuts it: chip 0's half plus chip 1's half, the shared expert counted once,
    is the reference's layer with every expert held."""
    d = ref.dims(dict(cfg, n_routed_experts=8))
    lp = ref._make(jax.random.PRNGKey(5), ref.layer_shapes(dict(cfg, n_routed_experts=8), "routed"),
                   "float32")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, d["dim"]))
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: ref.routed_mixer(row, lp, dict(d, experts_held=8))[0])(x)
        shared = jax.vmap(lambda row: ref._relu2(row, lp["sh_up_w"], lp["sh_down_w"], None))(x)

    def half(first):
        layer = RoutedExperts(
            dim=d["dim"], expert_dim=d["expert_dim"], experts_total=8, experts_per_token=2,
            experts_held=(first, 4), buffer_rows=64, score="sigmoid", routed_scale=2.5,
            shared_dim=d["shared_dim"], score_bias=True, act="relu2")
        params = {"router": lp["router_w"], "router_bias": lp["router_b"],
                  "w_up": lp["up_w"][first:first + 4], "w_out": lp["down_w"][first:first + 4],
                  "shared_up": lp["sh_up_w"], "shared_out": lp["sh_down_w"]}
        out, stats = layer.apply({"params": params}, x, mutable=["stats"])
        return out, int(stats["stats"]["moe_rows"])

    (low, rows_low), (high, rows_high) = half(0), half(4)
    assert rows_low + rows_high == 2 * 9 * 2 and min(rows_low, rows_high) > 0
    np.testing.assert_allclose(low + high - shared, want, atol=2e-5)
    assert "w_gate" not in jax.eval_shape(
        lambda: RoutedExperts(dim=8, expert_dim=4, experts_total=2, experts_per_token=1,
                              experts_held=(0, 2), buffer_rows=8, shared_dim=4, act="relu2").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 8))))["params"]
