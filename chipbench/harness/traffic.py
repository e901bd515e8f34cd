"""The one traffic generator: language-model training batches from a seed.

A mix file gives ``seq_len``, ``ranks``, ``seqs_per_rank`` and the token
law. ``"zipf"`` draws token ranks with probability proportional to
``rank ** -exponent`` and maps them through a permutation of the
vocabulary drawn from the seed, as text does; ``"uniform"`` draws ids
uniformly. Step ``k``'s batch depends on (seed, k) alone, so any step
can be made again, in any order; every seed gives the same shapes.
Rows are ``(ranks * seqs_per_rank, seq_len)``, rank-major, labels the
next tokens.
"""

from __future__ import annotations

import numpy as np

TAG_PERM, TAG_STEP = 0x7E4A, 0x5EB5


class LMTraffic:
    def __init__(self, mix, vocab, seed):
        self.seq_len = mix["seq_len"]
        self.rows = mix["ranks"] * mix["seqs_per_rank"]
        self.seed = int(seed)
        law = mix["tokens"]
        self.law = law["law"]
        if self.law == "zipf":
            w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(law["exponent"])
            self.cdf = np.cumsum(w / w.sum())
            self.cdf[-1] = 1.0
            rng = np.random.default_rng(np.random.SeedSequence([self.seed, TAG_PERM]))
            self.perm = rng.permutation(vocab).astype(np.int32)
        elif self.law == "uniform":
            self.vocab = vocab
        else:
            raise ValueError(f"unknown token law {self.law!r}")

    @property
    def tokens_per_step(self):
        return self.rows * self.seq_len

    def batch(self, step):
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, TAG_STEP, step]))
        shape = (self.rows, self.seq_len + 1)
        if self.law == "zipf":
            ranks = np.searchsorted(self.cdf, rng.random(shape), side="right")
            toks = self.perm[np.minimum(ranks, len(self.perm) - 1)]
        else:
            toks = rng.integers(0, self.vocab, shape, dtype=np.int32)
        return {"tokens": np.ascontiguousarray(toks[:, :-1]),
                "labels": np.ascontiguousarray(toks[:, 1:])}
