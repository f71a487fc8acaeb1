"""Token sequences for the language-model cells, from the one generator's
seeded streams (`traffic.py:_rng`): a cell's traffic is the parameters in
its workload file, never code.

`{"dist": "zipf", "exponent": s}`: ids drawn with probability proportional
to rank^-s over the vocabulary held, as text is, so that routing is uneven.
Which id has which rank is a permutation fixed by the vocabulary's size, not
by the seed: every seed offers the same skew over the same ids, in another
order.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic import _rng


def rank_to_id(vocab: int) -> np.ndarray:
    """The permutation of range(vocab) that says which id has rank r."""
    return _rng(vocab, "zipf_rank_to_id").permutation(vocab)


def zipf_cdf(vocab: int, exponent: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    return np.cumsum(p / p.sum())


def token_batch(seed: int, index: int, rows: int, length: int, spec: dict, vocab: int,
                tables=None) -> dict:
    """One training batch {"tokens": [rows, length] int32}: each row one
    sequence, no packing. `tables`: (cdf, rank_to_id), to make them once."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    cdf, ids = tables or (zipf_cdf(vocab, spec["exponent"]), rank_to_id(vocab))
    u = _rng(seed, "lm_tokens", index).random((rows, length))
    ranks = np.minimum(np.searchsorted(cdf, u), vocab - 1)
    return {"tokens": ids[ranks].astype(np.int32)}
