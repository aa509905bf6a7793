"""Inputs generated from ``--seed``: the one general generator every cell's
traffic file parameterises.

``markov2`` is the training stream of ``repro.launch.train``
(``synthetic_stream``) copied here so that a change to the program cannot
change the benchmark's data: an order-2 Markov chain over the first
``vocab_cap`` token ids, each context with ``branch`` random successors.
It is generated for a whole pool of batches at once, vectorised over the
rows, so that set-up stays short.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *stream) -> np.random.Generator:
    """A generator keyed by the seed (any size of integer) and a label."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def markov2_pool(spec: dict, vocab: int, batch: int, seq: int,
                 seed: int) -> np.ndarray:
    """(pool, batch, seq) int32 token ids; every row of the pool differs."""
    cap = min(vocab, int(spec.get("vocab_cap", 1024)))
    branch = int(spec.get("branch", 4))
    pool = int(spec["pool"])
    r = rng(seed, 1)
    succ = r.integers(0, cap, size=(cap, cap, branch), dtype=np.int32)
    rows = pool * batch
    out = np.empty((rows, seq), np.int32)
    out[:, 0] = r.integers(0, cap, rows)
    out[:, 1] = r.integers(0, cap, rows)
    pick = r.integers(0, branch, size=(rows, seq), dtype=np.int32)
    for t in range(2, seq):
        out[:, t] = succ[out[:, t - 2], out[:, t - 1], pick[:, t]]
    return out.reshape(pool, batch, seq)
