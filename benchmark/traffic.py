"""The one traffic generator: token batches, prompts and arrival times.

Everything is drawn on the host from (`seed`, a stream name, an index), so a
seed fixes the inputs whatever order they are asked for in, and a cell's
traffic is the parameters in its workload file, never code. Every seed of a
cell gets the same SET of prompt lengths and of gaps between arrivals (the
distribution's quantiles), in another order: the seed moves the work about,
it does not change how much there is.
"""

from __future__ import annotations

import zlib
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: str, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(stream.encode()), int(index)])


def prompt_lengths(rng, n: int, spec: dict, text_seq: int) -> np.ndarray:
    """`{"dist": "fixed", "value": v}` or `{"dist": "lognormal", "median": m,
    "sigma": s}`; clipped to [1, text_seq]."""
    if spec["dist"] == "fixed":
        lengths = np.full(n, spec["value"])
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        lengths = np.rint(np.exp(np.log(spec["median"]) + spec["sigma"] * rng.permutation(z)))
    else:
        raise ValueError(f"unknown prompt length distribution {spec['dist']!r}")
    return np.clip(lengths, 1, text_seq).astype(np.int64)


def prompts(seed: int, index: int, n: int, spec: dict, text_seq: int, vocab: int):
    """[n, text_seq] int32: ids uniform over [1, vocab), padded with 0 as the
    tokenizer pads."""
    rng = _rng(seed, "prompts", index)
    lengths = prompt_lengths(rng, n, spec, text_seq)
    ids = rng.integers(1, vocab, (n, text_seq), dtype=np.int32)
    ids[np.arange(text_seq)[None, :] >= lengths[:, None]] = 0
    return ids


def token_batch(seed: int, index: int, batch: int, spec: dict, d: dict) -> dict:
    """One training batch: prompts plus image codes uniform over the codebook."""
    rng = _rng(seed, "image_tokens", index)
    return {
        "text": prompts(seed, index, batch, spec["prompt_length"], d["text_seq"],
                        d["base_text_vocab"]),
        "image_tokens": rng.integers(
            0, d["image_vocab"], (batch, d["image_seq"]), dtype=np.int32
        ),
    }


def arrivals(seed: int, rate: float, horizon: float) -> np.ndarray:
    """Arrival times (seconds from 0) of a Poisson process at `rate` per
    second over `horizon` seconds: round(rate x horizon) gaps, the
    exponential's quantiles in an order drawn from the seed, so that every
    seed offers the same load."""
    n = max(1, int(round(rate * horizon)))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    return np.cumsum(_rng(seed, "arrivals").permutation(gaps))


def sample(seed: int, stream: str, n: int, k: int) -> np.ndarray:
    """k distinct indices of range(n), drawn from the seed, sorted."""
    return np.sort(_rng(seed, stream).choice(n, size=min(k, n), replace=False))
