"""int8 KV cache (`--kv_dtype int8`): quantization primitives, cache
layout (scale sidecars beside int8 payloads; default layout untouched),
the >=1.8x pool-capacity win over bf16, engine plumbing, partition rules
for the scale leaves, and the quality floor of a quantized decode
against the full-precision reference.

The default path carries the strongest pin: with `kv_dtype` unset the
state tree has NO scale leaves, K/V stay at the historical cache dtype,
and the continuous engine's tokens remain bit-identical to the
micro-batch engine's (PR 2's composition-invariance contract) — the
quantization plumbing must be invisible until opted into.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dalle_pytorch_tpu.models.attention import _kv_dequantize, _kv_quantize
from dalle_pytorch_tpu.models.dalle import (
    DALLE,
    init_paged_slot_state,
    init_slot_state,
)
from dalle_pytorch_tpu.parallel.serving_partition import decode_state_shardings
from dalle_pytorch_tpu.serving.engine import (
    ContinuousEngine,
    GenerationEngine,
    PagedContinuousEngine,
    SampleSpec,
)
from dalle_pytorch_tpu.serving.sharded import build_serving_mesh
from dalle_pytorch_tpu.training.metrics import MetricsRegistry

TEXT_SEQ = 8
FMAP = 4
IMG_SEQ = FMAP * FMAP


def _model(**kw):
    base = dict(
        dim=32, depth=2, heads=2, dim_head=8,
        num_image_tokens=32, image_fmap_size=FMAP,
        num_text_tokens=64, text_seq_len=TEXT_SEQ,
        shift_tokens=True, rotary_emb=True,
    )
    base.update(kw)
    return DALLE(**base)


def _params(model):
    text = jnp.zeros((1, TEXT_SEQ), jnp.int32)
    toks = jnp.zeros((1, model.image_seq_len), jnp.int32)
    return jax.jit(model.init)(jax.random.PRNGKey(42), text, toks)


def spec(seed, temperature=1.0, top_k=0.9):
    ids = np.zeros(TEXT_SEQ, np.int32)
    ids[:3] = (5, 6, 7)
    return SampleSpec(ids, seed=seed, temperature=temperature, top_k=top_k)


def _drain(eng, max_chunks=32):
    for _ in range(max_chunks):
        pos, act = eng.step_chunk()
        if (pos[act] >= eng.image_seq_len).all():
            return pos, act
    raise AssertionError("decode never finished")


def _attn(state):
    return state["cache"]["layer_0"]["attn"]


# ---------------------------------------------------------- primitives


class TestQuantPrimitives:
    def test_roundtrip_error_bounded_by_half_scale(self):
        """Symmetric rounding: every element round-trips within scale/2
        — the tolerance the quantized decode path inherits."""
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 64)) * 4.0
        q, scale = _kv_quantize(x)
        assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
        err = np.abs(np.asarray(x, np.float32) - np.asarray(
            _kv_dequantize(q, scale)
        ))
        bound = 0.5 * np.asarray(scale)[..., None] + 1e-6
        assert (err <= bound).all()

    def test_zero_rows_round_trip_to_zero(self):
        """The eps clip keeps an all-zero (position, head) finite: zeros
        in, zeros out, no NaN from a 0/0 scale."""
        q, scale = _kv_quantize(jnp.zeros((1, 2, 3, 8)))
        dq = np.asarray(_kv_dequantize(q, scale))
        assert np.isfinite(dq).all() and (dq == 0).all()

    def test_extremes_use_the_full_int8_range(self):
        x = jnp.asarray([[[[-3.0, 0.0, 1.5, 3.0]]]])
        q, _ = _kv_quantize(x)
        qn = np.asarray(q)
        assert qn[..., 0] == -127 and qn[..., 3] == 127


# -------------------------------------------------------- cache layout


class TestCacheLayout:
    def test_default_layout_has_no_scale_leaves(self):
        model = _model()
        for state in (
            init_slot_state(model, 2),
            init_paged_slot_state(model, 2, n_pages=8, page_size=4),
        ):
            attn = _attn(state)
            assert "k_scale" not in attn and "v_scale" not in attn
            assert attn["k"].dtype != jnp.int8

    def test_int8_layout_pairs_payload_with_scales(self):
        model = _model().clone(kv_dtype="int8")
        slot = _attn(init_slot_state(model, 2))
        assert slot["k"].dtype == jnp.int8
        assert slot["k_scale"].dtype == jnp.float32
        assert slot["k_scale"].shape == slot["k"].shape[:-1]  # [B, H, S]
        paged = _attn(init_paged_slot_state(model, 2, n_pages=8, page_size=4))
        assert paged["k"].dtype == jnp.int8
        assert paged["v_scale"].shape == paged["v"].shape[:-1]  # [P, H, page]

    def test_capacity_ratio_vs_bf16_at_least_1p8(self):
        """The HBM win the ISSUE promises: at head-dim 64 an int8 page
        position costs D + 4 bytes (payload + fp32 scale) against bf16's
        2D — 2D/(D+4) = 1.88x rows in the same page budget. Derived from
        the REAL paged layout's leaf shapes/itemsizes, not re-stated
        constants."""
        model = _model(dim=128, heads=2, dim_head=64).clone(kv_dtype="int8")
        attn = _attn(init_paged_slot_state(model, 2, n_pages=4, page_size=4))
        d = attn["k"].shape[-1]
        int8_bytes = attn["k"].dtype.itemsize * d + attn["k_scale"].dtype.itemsize
        bf16_bytes = 2 * d  # the accelerator cache dtype's cost per position
        assert bf16_bytes / int8_bytes >= 1.8


# ----------------------------------------------------- partition rules


class TestScalePartitionRules:
    def test_scales_follow_their_payloads_head_split(self):
        """k_scale/v_scale shard exactly like k/v: head axis over tp,
        page/batch axes whole — a scale on a different device than its
        payload would force a collective inside the decode kernel."""
        mesh = build_serving_mesh({"tp": 2})
        model = _model().clone(kv_dtype="int8")

        def flat(state):
            return {
                "/".join(str(getattr(p, "key", p)) for p in path): s.spec
                for path, s in jax.tree_util.tree_flatten_with_path(
                    decode_state_shardings(state, mesh)
                )[0]
            }

        slot = flat(init_slot_state(model, 4))
        assert next(
            v for p, v in slot.items() if p.endswith("attn/k_scale")
        ) == P(None, "tp")  # [B, H, S]
        paged = flat(init_paged_slot_state(model, 4, n_pages=8, page_size=4))
        assert next(
            v for p, v in paged.items() if p.endswith("attn/v_scale")
        ) == P(None, "tp")  # [P, H, page]: page axis stays whole


# ----------------------------------------------------- engine plumbing


@pytest.fixture(scope="module")
def toy():
    model = _model()
    return model, _params(model)


class TestEnginePlumbing:
    def test_engine_clones_model_and_reports_dtype(self, toy):
        model, params = toy
        eng = PagedContinuousEngine(
            model=model, variables=params, max_batch=2, chunk_tokens=8,
            page_size=4, kv_dtype="int8", registry=MetricsRegistry(),
        )
        assert eng.model.kv_dtype == "int8"
        det = eng.kv_detail()
        assert det["dtype"] == "int8"
        assert det["bytes_per_page"] == eng.kv_page_bytes()
        assert "k_scale" in _attn(eng._state)
        assert eng.registry.get(
            "dalle_serving_kv_bytes_per_slot"
        ).value == eng.kv_bytes_per_slot() > 0

    def test_default_engine_unchanged(self, toy):
        model, params = toy
        eng = ContinuousEngine(
            model=model, variables=params, max_batch=2, chunk_tokens=8,
            registry=MetricsRegistry(),
        )
        assert getattr(eng.model, "kv_dtype", None) is None
        assert "k_scale" not in _attn(eng._state)


# ------------------------------------------------------------- quality


class TestDecodeQuality:
    def test_default_path_bit_identical_to_micro(self, toy):
        """The bf16/default pin: with kv_dtype unset, the continuous
        engine's tokens stay BIT-IDENTICAL to the micro-batch engine's
        (composition invariance) — the int8 plumbing changed nothing it
        wasn't asked to."""
        model, params = toy
        micro = GenerationEngine(
            model=model, variables=params, batch_shapes=(2,),
            registry=MetricsRegistry(),
        )
        cont = ContinuousEngine(
            model=model, variables=params, max_batch=2, chunk_tokens=8,
            registry=MetricsRegistry(),
        )
        specs = [spec(51, 0.9, 0.9), spec(53, 1.1, 0.85)]
        ref, _ = micro.generate(specs)
        for i, s in enumerate(specs):
            cont.prefill_slot(i, s)
        _drain(cont)
        got = cont.harvest([0, 1])
        cont.release([0, 1])
        np.testing.assert_array_equal(np.asarray(ref), got)

    def test_int8_tokens_track_the_reference(self, toy):
        """Tolerance pin for the quantized path ONLY: int8 decode is NOT
        bit-identical by design (scale/2 rounding in every attention
        read) — but on the toy model the token stream must stay close to
        the full-precision decode. The bound is deliberately loose;
        quality at full size (CLIP on the chip) is not measured: no
        cell serves int8 K/V yet (PERF.md section 7, cell 5)."""
        model, params = toy
        ref_eng = ContinuousEngine(
            model=model, variables=params, max_batch=2, chunk_tokens=8,
            registry=MetricsRegistry(),
        )
        q_eng = ContinuousEngine(
            model=model, variables=params, max_batch=2, chunk_tokens=8,
            kv_dtype="int8", registry=MetricsRegistry(),
        )
        specs = [spec(61, 0.9, 0.9), spec(63, 1.1, 0.85)]
        outs = []
        for eng in (ref_eng, q_eng):
            for i, s in enumerate(specs):
                eng.prefill_slot(i, s)
            _drain(eng)
            outs.append(eng.harvest([0, 1]))
            eng.release([0, 1])
        agreement = float(np.mean(outs[0] == outs[1]))
        assert agreement >= 0.75, f"token agreement {agreement:.3f}"
