"""The samplers' top-k filter finds the k-th largest logit by counting
(`ops/sampling.py:kth_largest`): held bit for bit to the definition it
replaced, `kth` = the k-th entry of a descending sort and the same `where`,
alone and through the cached samplers of the tiny configurations."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import build, build_olmo, build_pangu
from dalle_pytorch_tpu.models import dalle as dalle_mod, lm as lm_mod
from dalle_pytorch_tpu.models.dalle import NEG_MASK_VALUE, generate_images_cached
from dalle_pytorch_tpu.models.lm import CausalLM, generate_tokens_cached, prefill_cached
from dalle_pytorch_tpu.ops import sampling
from dalle_pytorch_tpu.ops.sampling import top_k_filter, top_k_filter_per_row

ROOT = Path(__file__).resolve().parent.parent


def keep_of(thres: float, vocab: int) -> int:
    return max(int((1.0 - thres) * vocab), 1)


def sorted_filter(logits, thres=0.5):
    """The definition: what `top_k_filter` was before it counted."""
    k = keep_of(thres, logits.shape[-1])
    kth = -jnp.sort(-logits, axis=-1)[..., k - 1:k]
    return jnp.where(logits < kth, -jnp.inf, logits)


def sorted_filter_per_row(logits, keep_k):
    sorted_desc = -jnp.sort(-logits.astype(jnp.float32), axis=-1)
    idx = jnp.clip(keep_k - 1, 0, logits.shape[-1] - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    return jnp.where(logits < kth, -jnp.inf, logits)


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


def _normal(rows, vocab, dtype=jnp.float32, seed=0, scale=4.0):
    return (scale * jax.random.normal(jax.random.PRNGKey(seed), (rows, vocab))).astype(dtype)


def _ties(rows, vocab):
    """Few distinct values, so the k-th place lies inside a run of equals."""
    return jnp.round(_normal(rows, vocab, seed=1, scale=1.5))


def _blocked(rows, vocab, value):
    """The leading two thirds of the ids masked, as the image samplers mask the text's."""
    return jnp.where(jnp.arange(vocab)[None] < 2 * vocab // 3, value, _normal(rows, vocab, seed=2))


def _zeros(rows, vocab):
    """+0.0 and -0.0 around the k-th place, a few values either side."""
    signs = jnp.where(jnp.arange(vocab)[None] % 2 == 0, 0.0, -0.0)
    x = _normal(rows, vocab, seed=3)
    return jnp.where(jnp.abs(x) < 2.0, signs, x)


ROWS = {
    "normal_f32": lambda: _normal(3, 1000),
    "normal_bf16": lambda: _normal(3, 1000, jnp.bfloat16),
    "tiny_and_huge": lambda: _normal(2, 512, seed=4) * jnp.exp(_normal(2, 512, seed=5, scale=20.0)),
    "ties_at_the_kth": lambda: _ties(3, 1000),
    "all_equal": lambda: jnp.full((2, 640), 1.25, jnp.float32),
    "minus_inf_block": lambda: _blocked(2, 900, -jnp.inf),
    "neg_mask_block": lambda: _blocked(2, 900, NEG_MASK_VALUE),
    "signed_zeros": lambda: _zeros(2, 1000),
    "leading_axes": lambda: _normal(6, 257, seed=6).reshape(2, 3, 257),
    "vocab_19200": lambda: _normal(2, 19200, seed=7),
    "vocab_41216_masked": lambda: jnp.where(
        jnp.arange(41216)[None] < 33024, NEG_MASK_VALUE, _normal(2, 41216, seed=8)),
    "vocab_100352": lambda: _normal(2, 100352, seed=9),
}
# thres 0.0 keeps all V, 1.0 keeps one: k = V and k = 1 beside the cells' 0.9
CASES = [(name, thres) for name in ROWS for thres in (0.9, 0.5, 1.0, 0.0)
         if not name.startswith("vocab_") or thres in (0.9, 1.0)]


@pytest.mark.parametrize("name,thres", CASES)
def test_filter_is_bit_equal_to_the_sort_definition(name, thres):
    logits = ROWS[name]()
    got = jax.jit(lambda x: top_k_filter(x, thres=thres))(logits)
    want = sorted_filter(logits, thres)
    assert got.dtype == want.dtype == logits.dtype
    assert np.array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("name", [n for n in ROWS if n != "leading_axes"])
def test_per_row_filter_is_bit_equal_with_a_k_a_row(name):
    logits = ROWS[name]()
    rows, vocab = logits.shape
    keep = np.resize(np.asarray([1, vocab, keep_of(0.9, vocab), vocab // 2, 0, vocab + 7]), rows)
    keep = jnp.asarray(np.roll(keep, len(name) % rows), jnp.int32)  # 0 and V + 7 are clipped
    got = jax.jit(top_k_filter_per_row)(logits, keep)
    want = sorted_filter_per_row(logits, keep)
    assert got.dtype == want.dtype
    assert np.array_equal(_bits(got), _bits(want))


def test_both_filters_agree_on_the_same_k():
    """`serving/engine.py:_keep_k`'s promise: one selection serves both."""
    logits = _ties(4, 777)
    for thres in (0.9, 0.5, 1.0):
        keep = jnp.full((4,), keep_of(thres, 777), jnp.int32)
        assert np.array_equal(_bits(top_k_filter(logits, thres)),
                              _bits(top_k_filter_per_row(logits, keep)))


# ------------------------------------------------------------- the counter

def test_the_record_names_the_path_and_the_passes_of_each_shape():
    sampling.forget()
    shapes = {(48, 100352): 10035, (64, 19200): 1919, (2, 41216): 4121}
    for (rows, vocab), k in shapes.items():
        x = jax.ShapeDtypeStruct((rows, vocab), jnp.float32)
        jax.eval_shape(lambda x: top_k_filter(x, thres=0.9), x)
        jax.eval_shape(lambda x: top_k_filter(x, thres=1.0), x)
        assert sampling.selections[(rows, vocab, k)] == ("count", 32)
        assert sampling.selections[(rows, vocab, 1)] == ("max", 0)
    jax.eval_shape(top_k_filter_per_row, jax.ShapeDtypeStruct((8, 41216), jnp.float32),
                   jax.ShapeDtypeStruct((8,), jnp.int32))
    assert sampling.selections[(8, 41216, None)] == ("count", 32)
    assert len(sampling.selections) == 7
    sampling.forget()
    assert sampling.selections == {}


def test_no_sort_is_lowered_at_the_cells_shape():
    x = jax.ShapeDtypeStruct((48, 100352), jnp.float32)
    text = jax.jit(lambda x: top_k_filter(x, thres=0.9)).lower(x).as_text()
    assert "sort" not in text and "top_k" not in text and "while" in text
    greedy = jax.jit(lambda x: top_k_filter(x, thres=1.0)).lower(x).as_text()
    assert "sort" not in greedy and "top_k" not in greedy and "while" not in greedy
    keep = jax.ShapeDtypeStruct((48,), jnp.int32)
    per_row = jax.jit(top_k_filter_per_row).lower(x, keep).as_text()
    assert "sort" not in per_row and "while" in per_row


# ------------------------------------------------------------ the samplers

def _config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


@pytest.fixture
def sort_defined(monkeypatch):
    """Call it to put the sort definition in the samplers' place; the compiled
    samplers are dropped on both sides of it."""
    def patch():
        dalle_mod._jitted_sampler.cache_clear()
        monkeypatch.setattr(lm_mod, "top_k_filter", sorted_filter)
        monkeypatch.setattr(dalle_mod, "top_k_filter", sorted_filter)
    yield patch
    dalle_mod._jitted_sampler.cache_clear()


@pytest.mark.parametrize("name,builder,prompt", [
    ("_tiny-olmo", build_olmo, 70), ("_tiny-pangu", build_pangu, 24)])
def test_sampled_tokens_are_those_of_the_sort_definition(name, builder, prompt, sort_defined):
    cfg = _config(name)
    steps, vocab = 12, cfg["vocab_size"]
    mdl = CausalLM.from_config(cfg, prompt + steps)
    variables = builder.seeded_variables(cfg, mdl, 7)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, vocab, (2, prompt + 2)), jnp.int32)

    def turn():
        cache, _ = prefill_cached(mdl, variables, tokens[:, :prompt], mdl.init_cache(2))
        toks, logits, _, _ = generate_tokens_cached(
            mdl, variables, jax.random.PRNGKey(5), cache, tokens[:, prompt:], steps,
            filter_thres=0.9, logit_rows=2, start=prompt)
        return np.asarray(toks), np.asarray(logits)

    sampling.forget()
    got = turn()
    assert sampling.selections == {(2, vocab, keep_of(0.9, vocab)): ("count", 32)}
    sort_defined()
    sampling.forget()
    want = turn()
    assert sampling.selections == {}
    assert np.array_equal(got[0], want[0]) and np.array_equal(_bits(got[1]), _bits(want[1]))
    assert len(np.unique(got[0])) > 2  # sampled, not one token repeated


def test_sampled_image_tokens_are_those_of_the_sort_definition(sort_defined):
    cfg = _config("_tiny-scan")
    mdl = build.model(cfg)
    variables = build.seeded_variables(cfg, mdl, 7)
    text = jnp.asarray(np.random.default_rng(1).integers(1, 96, (2, mdl.text_seq_len)), jnp.int32)
    sample = lambda: np.asarray(generate_images_cached(
        mdl, variables, jax.random.PRNGKey(3), text, filter_thres=0.9))
    sampling.forget()
    got = sample()
    assert sampling.selections == {
        (2, mdl.total_tokens, keep_of(0.9, mdl.total_tokens)): ("count", 32)}
    sort_defined()
    want = sample()
    assert got.shape == (2, mdl.image_seq_len) and np.array_equal(got, want)
