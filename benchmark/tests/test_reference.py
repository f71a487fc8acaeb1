"""The plain reference against the program at a tiny size, on the CPU, in
float32: every attention pattern, both layer executors, logits and loss."""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build, traffic
from benchmark.reference import dalle_ref

BENCH = Path(__file__).resolve().parents[1]


def tiny(pattern, executor):
    cfg = json.loads((BENCH / "configs" / "_tiny.json").read_text())
    cfg["model"].update(attn_types=[pattern, "full"], executor=executor, dtype="float32",
                        rotary_angle_dtype="float32", attn_impl="dense")
    return cfg


@pytest.mark.parametrize("executor", ["unrolled", "scan"])
@pytest.mark.parametrize("pattern", dalle_ref.PATTERNS)
def test_logits_and_loss_agree(pattern, executor):
    cfg = tiny(pattern, executor)
    d = dalle_ref.dims(cfg)
    mdl = build.model(cfg)
    variables = build.seeded_variables(cfg, mdl, seed=3)
    b = traffic.token_batch(3, 0, 2, {"prompt_length": {"dist": "lognormal", "median": 4,
                                                        "sigma": 0.5}}, d)
    text, image = jnp.asarray(b["text"]), jnp.asarray(b["image_tokens"])
    params = dalle_ref.init_params(cfg, 3)
    with jax.default_matmul_precision("highest"):
        want = dalle_ref.logits_fn(params, cfg, text, image)
        got = mdl.apply(variables, text, image)
        want_loss = dalle_ref.loss_fn(params, cfg, text, image)
        got_loss, _ = mdl.apply(variables, text, image, return_loss=True)
    live = np.asarray(want) > dalle_ref.NEG / 2
    assert (np.asarray(got) > dalle_ref.NEG / 2).tolist() == live.tolist()
    # float32 on both sides: what is left is the order of summation
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-4)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5


@pytest.mark.parametrize("pattern", dalle_ref.PATTERNS)
def test_masks_are_the_programs(pattern):
    """Re-derived from the definition, equal to what the program builds."""
    from dalle_pytorch_tpu.models.transformer import _build_static_mask

    cfg = tiny(pattern, "unrolled")
    d = dalle_ref.dims(cfg)
    mine = dalle_ref.pattern_mask(pattern, d)
    theirs = _build_static_mask(pattern, d["seq"], d["fmap"], 0)
    causal = np.tril(np.ones((d["seq"], d["seq"]), bool))
    theirs = causal if theirs is None else np.asarray(theirs)[: d["seq"], : d["seq"]] & causal
    assert (mine == theirs).all()


def test_cached_decode_serves_the_references_greedy_tokens():
    """Prefill + cached steps (the sampler) against one full forward of the
    reference: a greedy token lies at or within rounding of the reference's
    best at every position."""
    from dalle_pytorch_tpu.models.dalle import generate_images_cached

    cfg = tiny("axial_row", "scan")
    cfg["model"].update(attn_types=list(dalle_ref.PATTERNS))
    d = dalle_ref.dims(cfg)
    mdl = build.model(cfg)
    variables = build.seeded_variables(cfg, mdl, seed=5)
    text = traffic.prompts(5, 0, 3, {"dist": "fixed", "value": 8}, d["text_seq"],
                           d["base_text_vocab"])
    with jax.default_matmul_precision("highest"):
        toks = generate_images_cached(mdl, variables, jax.random.PRNGKey(0),
                                      jnp.asarray(text), filter_thres=1.0)
    gaps = dalle_ref.greedy_gaps(cfg, dalle_ref.init_params(cfg, 5), text, np.asarray(toks))
    assert gaps.max() < 1e-3
    # and a wrong token is seen: the check is not blind
    wrong = np.asarray(toks).copy()
    wrong[0, 3] = (wrong[0, 3] + 1) % d["image_vocab"]
    assert dalle_ref.greedy_gaps(cfg, dalle_ref.init_params(cfg, 5), text, wrong).max() > 1e-2


@pytest.mark.parametrize("impl", ["flash", "dense"])
def test_gradients_agree_in_float32(impl):
    """The trainer's step (flash kernels in interpret mode, remat, clip, Adam)
    against the reference's, both in float32: every number `correct` compares
    is at rounding level, so what a chip run shows is precision, not a
    difference in the mathematics."""
    from benchmark.loops import train

    cfg = tiny("full", "unrolled")
    cfg["model"].update(attn_types=["full"], attn_impl=impl)
    wl = json.loads((BENCH / "workloads" / "_tiny.train.json").read_text())
    with jax.default_matmul_precision("highest"):
        prog = train.Program(cfg, wl["job"])
        state, feed, rng = prog.begin(9)
        try:
            got, state, rng = prog.follow(9, state, feed, rng)
        finally:
            feed.close()
        want = prog.reference(9)
    for name, (value, where) in train.numbers(got, want).items():
        assert value < 2e-3, (name, value, where)
