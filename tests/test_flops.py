"""Analytic FLOPs/MFU model (utils/flops.py)."""

import pytest

from dalle_pytorch_tpu.utils.flops import (
    dalle_train_flops_per_sample,
    mfu,
    peak_flops_per_chip,
    transformer_train_flops,
)


class TestFlops:
    def test_peak_lookup(self):
        assert peak_flops_per_chip("TPU v5 lite") == 197e12
        assert peak_flops_per_chip("TPU v4") == 275e12
        with pytest.raises(KeyError, match="no published peak"):
            peak_flops_per_chip("cpu")
        with pytest.raises(KeyError, match="mystery accelerator"):
            peak_flops_per_chip("mystery accelerator")

    def test_flagship_magnitude(self):
        # dim1024/depth12/seq1280: ~1.8e12 matmul FLOPs per sample
        f = transformer_train_flops(1024, 12, 16, 64, 1280)
        assert 1e12 < f < 3e12

    def test_model_accessor_matches_direct(self):
        from dalle_pytorch_tpu.models.dalle import DALLE

        m = DALLE(dim=64, depth=2, heads=4, dim_head=16, num_image_tokens=32,
                  image_fmap_size=4, num_text_tokens=60, text_seq_len=12)
        assert dalle_train_flops_per_sample(m) == transformer_train_flops(
            64, 2, 4, 16, m.total_seq_len, vocab=m.total_tokens
        )
        # the logits head is counted (standard MFU includes the LM head)
        assert dalle_train_flops_per_sample(m) > transformer_train_flops(
            64, 2, 4, 16, m.total_seq_len
        )

    def test_mode_aware_passes(self):
        # dual-objective modes run the transformer twice per sample
        # (training/steps.py loss_fn), so the MFU numerator doubles
        from dalle_pytorch_tpu.models.dalle import DALLE
        from dalle_pytorch_tpu.training.steps import MODES
        from dalle_pytorch_tpu.utils.flops import OBJECTIVE_PASSES

        assert set(OBJECTIVE_PASSES) == set(MODES)
        m = DALLE(dim=64, depth=2, heads=4, dim_head=16, num_image_tokens=32,
                  image_fmap_size=4, num_text_tokens=60, text_seq_len=12)
        base = dalle_train_flops_per_sample(m, mode="forward_only")
        assert dalle_train_flops_per_sample(m, mode="reverse_only") == base
        assert dalle_train_flops_per_sample(m, mode="forward_forward") == 2 * base
        assert (
            dalle_train_flops_per_sample(m, mode="forward_reverse_partial")
            == 2 * base
        )
        with pytest.raises(KeyError):
            dalle_train_flops_per_sample(m, mode="nonsense")

    def test_mfu(self):
        # 1 sample/s at exactly peak-flops-per-sample == MFU 1.0
        assert mfu(1.0, 197e12, "TPU v5e") == pytest.approx(1.0)
        assert mfu(0.5, 197e12, "TPU v5e", n_chips=1) == pytest.approx(0.5)
