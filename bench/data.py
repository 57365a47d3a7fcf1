"""Token batches of a cell, made from the seed.

Row ``r`` of step ``s`` holds ``seq + 1`` ids drawn uniformly from the
vocabulary by ``numpy.random.default_rng((seed, s))``; the first ``seq``
are the inputs and the last ``seq`` the labels.  Every row of every step
differs.  The same recipe as the program's ``repro.data.SyntheticLM``, kept
here so that the reference and the program are fed by the benchmark.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Tokens:
    vocab_size: int
    global_batch: int
    seq_len: int
    seed: int

    def batch_at(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, step))
        toks = rng.integers(0, self.vocab_size,
                            (self.global_batch, self.seq_len + 1),
                            dtype=np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
