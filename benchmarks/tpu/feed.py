"""Token batches for the training jobs, a pure function of (seed, step).

Copied from ``repro.data.pipeline.SyntheticLM`` so that the benchmark's
inputs cannot change with the program: Zipfian unigrams (exponent
``zipf_a``) clipped to the vocabulary, and every odd position continues a
fixed permutation chain of the token before it.  Rows of a batch, and
batches of different steps, are independent draws.
"""
from __future__ import annotations

import numpy as np


class LMFeed:
    """Batches of ``[batch, seq]`` int32 token ids."""

    def __init__(self, *, vocab: int, batch: int, seq: int, seed: int,
                 zipf_a: float):
        self.vocab, self.batch, self.seq = vocab, batch, seq
        self.seed, self.zipf_a = seed, zipf_a
        self._perm = np.random.default_rng(
            np.random.SeedSequence([seed, 1])).permutation(vocab)

    def batch_at(self, step: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 0,
                                                            step]))
        toks = rng.zipf(self.zipf_a, size=(self.batch, self.seq))
        toks = np.minimum(toks - 1, self.vocab - 1)
        odd = toks[:, 1::2].shape[1]
        toks[:, 1::2] = self._perm[toks[:, 0::2][:, :odd]]
        return toks.astype(np.int32)
