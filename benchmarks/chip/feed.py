"""The traffic: next-token batches of Zipf-distributed token ids.

A copy of the program's ``data/pipeline.py`` ``SyntheticLM`` law, kept
here so that no change to the program moves the yardstick: token ids of
rank r are drawn with probability proportional to r^-zipf over the whole
vocabulary, the natural-language frequency law that makes embedding
gradients row-sparse and skewed.  Every step draws a fresh global batch
from (seed, step), so any seed gives the same sizes and a different
order, and every row differs.
"""
from __future__ import annotations

import numpy as np


class ZipfFeed:
    def __init__(self, vocab: int, seq_len: int, global_batch: int,
                 zipf: float, seed: int):
        w = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf)
        self.cdf = np.cumsum(w / w.sum())
        self.vocab, self.seq_len, self.batch, self.seed = (
            vocab, seq_len, global_batch, seed)

    def host_batch(self, step: int) -> dict:
        """{tokens, labels}: int32 [global_batch, seq_len], labels the
        tokens shifted by one."""
        rng = np.random.default_rng((self.seed, step))
        u = rng.random((self.batch, self.seq_len + 1))
        toks = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          self.vocab - 1).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
