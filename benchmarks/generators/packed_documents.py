"""Packed-document traffic for the decoder configuration: documents as graphs
(a copy of ``hydragnn_tpu/data/synthetic.py`` ``packed_documents_dataset``:
the same lengths and ids). Token = node: id in ``z`` (int32) and ``x``
(float32 column, which the reference reads); ``pos = [index in document,
document number, 0]``, unique per document (``compare.match_records`` finds a
graph by its positions); chain edges ``t-1 -> t``; ``energy`` and ``forces``
are zeros that no head reads."""

from __future__ import annotations

from typing import List

import numpy as np

import datagen


def generate(number_configurations, median_tokens, sigma, min_tokens, max_tokens,
             vocab_size, zipf_exponent, seed) -> List[datagen.Record]:
    """Lognormal lengths (median, sigma) clipped to [min, max]; ids Zipf over
    the vocabulary slice, p(rank) ~ rank ** -exponent, so repeated ids route
    alike and expert load is uneven (at exponent 1.1 the first id alone is 14%
    of the tokens)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(zipf_exponent))
    cdf /= cdf[-1]
    out: List[datagen.Record] = []
    for doc in range(int(number_configurations)):
        n = int(np.clip(rng.lognormal(np.log(median_tokens), sigma), min_tokens, max_tokens))
        ids = np.searchsorted(cdf, rng.random(n)).astype(np.int32)
        idx = np.arange(n, dtype=np.float32)
        out.append({
            "x": ids[:, None].astype(np.float32),
            "pos": np.stack([idx, np.full(n, doc, np.float32), np.zeros(n, np.float32)], axis=1),
            "senders": np.arange(0, n - 1, dtype=np.int32),
            "receivers": np.arange(1, n, dtype=np.int32),
            "energy": np.zeros((1,), np.float32),
            "forces": np.zeros((n, 3), np.float32), "z": ids,
        })
    return out
