"""Training batches: documents packed into fixed-length sequences.

Documents of log-normal length are drawn from a Zipf unigram distribution
over the vocabulary (so that there is something to learn and the loss can
fall), joined with a separator id and cut into ``seq_len``-token rows;
attention is plainly causal across document boundaries, as in most
pre-training set-ups.  Every step gets a fresh batch, a pure function of
``(seed, step)``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import numpy as np


class Batches:
    def __init__(self, traffic: Dict[str, Any], seed: int, vocab_size: int,
                 rows: int, seq_len: int):
        self.traffic, self.seed = traffic, int(seed)
        self.rows, self.seq_len = int(rows), int(seq_len)
        self.sep = int(traffic["separator_id"])
        ranks = np.arange(1, vocab_size, dtype=np.float64)
        p = ranks ** -float(traffic["zipf_exponent"])
        self._cdf = np.cumsum(p / p.sum())
        doc = traffic["document_tokens"]
        self._mu, self._sigma = math.log(doc["median"]), doc["sigma"]
        self._lo, self._hi = doc["min"], doc["max"]

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng([self.seed, 0xD0C, int(step)])
        need = self.rows * self.seq_len
        # token ids 1..V-1 by Zipf rank (0 is left to the separator)
        ids = 1 + np.searchsorted(self._cdf, rng.random(need))
        ids = np.minimum(ids, len(self._cdf)).astype(np.int32)
        at = 0
        while at < need:
            n = int(np.clip(rng.lognormal(self._mu, self._sigma),
                            self._lo, self._hi))
            at += n
            if at < need:
                ids[at] = self.sep
                at += 1
        return {"input_ids": ids.reshape(self.rows, self.seq_len)}


def make(traffic: Dict[str, Any], seed: int, vocab_size: int, rows: int,
         seq_len: int) -> Batches:
    return Batches(traffic, seed, vocab_size, rows, seq_len)
