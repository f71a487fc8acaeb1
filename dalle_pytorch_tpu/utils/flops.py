"""Analytic FLOPs model for throughput/MFU accounting.

Counts matmul FLOPs only (the MXU-relevant work) for the DALLE
transformer; elementwise/softmax/embedding work is excluded by
convention, matching how MFU is normally quoted. Used by the trainer's
live MFU log (the reference logs only `sample_per_sec`,
`/root/reference/train_dalle.py:578-581`).
"""

from __future__ import annotations

# published per-chip peaks, keyed by substrings of jax.Device.device_kind
# (lowercased; first match wins, so "v5 lite"/"v5e" precede "v5"):
# (bf16 FLOP/s, HBM bytes/s). Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s). A device that is not listed is an error, not a default.
PEAKS = {
    "v4": (275e12, 1228e9),
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5": (459e12, 2765e9),  # v5p
    "v6": (918e12, 1640e9),
}


def lookup_peaks(device_kind: str):
    """(bf16 FLOP/s, HBM bytes/s) of a listed device kind, else None."""
    kind = device_kind.lower()
    for key, peaks in PEAKS.items():
        if key in kind:
            return peaks
    return None


def peak_flops_per_chip(device_kind: str) -> float:
    peaks = lookup_peaks(device_kind)
    if peaks is None:
        raise KeyError(
            f"no published peak for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); utilization is only defined "
            "against a listed chip"
        )
    return peaks[0]


def transformer_train_flops(
    dim: int, depth: int, heads: int, dim_head: int, seq: int, ff_mult: int = 4,
    vocab: int = 0,
) -> float:
    """Matmul FLOPs per sample for one fwd+bwd training step.

    `vocab` adds the logits-head projection (standard MFU accounting
    includes the LM head; ~6% of the flagship's matmul FLOPs). Remat
    recompute is deliberately NOT counted — MFU quotes useful FLOPs.
    """
    inner = heads * dim_head
    per_layer = (
        2 * seq * dim * 3 * inner            # qkv proj
        + 2 * seq * seq * inner * 2          # qk^T and attn@v
        + 2 * seq * inner * dim              # out proj
        + 2 * seq * dim * dim * ff_mult * 2  # ff up (GEGLU: 2x width)
        + 2 * seq * dim * ff_mult * dim      # ff down
    )
    fwd = depth * per_layer + 2 * seq * dim * vocab
    return 3 * fwd  # fwd + 2x bwd


# objective mode (training/steps.py MODES) -> number of full fwd+bwd
# transformer passes per sample. forward_forward / forward_reverse_partial
# run the model twice (forward objective + inverse objective, steps.py
# `loss_fn`), so their useful work is 2x a single-objective step.
OBJECTIVE_PASSES = {
    "forward_only": 1,
    "reverse_only": 1,
    "forward_forward": 2,
    "forward_reverse_partial": 2,
}


def dalle_train_flops_per_sample(model, mode: str = "forward_only") -> float:
    """FLOPs/sample for a DALLE model instance under an objective mode.

    Counts `OBJECTIVE_PASSES[mode]` full fwd+bwd passes; in-step dVAE
    encoding (when images rather than tokens are fed) is excluded — it is
    frozen forward-only conv work, small next to the transformer.
    Gradient accumulation does not change FLOPs/sample: `_accumulate`
    scan-splits the same global batch into microbatches, so per-sample
    work is identical and `sample_per_sec * flops_per_sample` stays the
    correct MFU numerator.
    """
    passes = OBJECTIVE_PASSES[mode]
    return passes * transformer_train_flops(
        model.dim, model.depth, model.heads, model.dim_head,
        model.total_seq_len, vocab=model.total_tokens,
    )


def mfu(samples_per_sec: float, flops_per_sample: float, device_kind: str,
        n_chips: int = 1) -> float:
    return samples_per_sec * flops_per_sample / (
        peak_flops_per_chip(device_kind) * n_chips
    )
