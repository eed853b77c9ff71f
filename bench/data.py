"""Token batches for the training cells, made from ``--seed``.

The arithmetic is that of the repository's Markov corpus
(``repro.data.synthetic.markov_corpus``), copied here so that no later
change to the program can move the yardstick: each token of the vocabulary
prefers one of ``branching`` successors with Dirichlet(0.5) weights, and
``explore`` of the positions draw a token uniformly instead.  Every row is
its own chain, started at a uniform token, and the chains advance together,
one position at a time across all rows.  Row ``i`` holds ``seq_len + 1``
tokens: the first ``seq_len`` are the inputs and the last ``seq_len`` the
labels.
"""
from __future__ import annotations

import numpy as np


def markov_rows(seed: int, rows: int, length: int, vocab: int, *,
                branching: int = 4, explore: float = 0.05) -> np.ndarray:
    """``(rows, length)`` int32 token ids below ``vocab``."""
    rng = np.random.default_rng(seed)
    succ = rng.integers(0, vocab, size=(vocab, branching), dtype=np.int32)
    probs = rng.dirichlet(np.ones(branching) * 0.5, size=vocab)
    cum = np.cumsum(probs, axis=1)
    u = rng.random((length, rows))
    wild = rng.random((length, rows)) < explore
    uniform = rng.integers(0, vocab, size=(length, rows), dtype=np.int32)
    out = np.empty((length, rows), np.int32)
    t = rng.integers(0, vocab, size=rows, dtype=np.int32)
    for i in range(length):
        j = np.minimum((cum[t] < u[i][:, None]).sum(axis=1), branching - 1)
        t = np.where(wild[i], uniform[i], succ[t, j])
        out[i] = t
    return out.T.copy()


def ring(seed: int, *, batches: int, global_batch: int, seq_len: int,
         vocab: int, **corpus) -> list[dict]:
    """``batches`` distinct global batches of ``tokens`` and ``labels``."""
    rows = markov_rows(seed, batches * global_batch, seq_len + 1, vocab,
                       **corpus)
    rows = rows.reshape(batches, global_batch, seq_len + 1)
    return [{"tokens": r[:, :-1].copy(), "labels": r[:, 1:].copy()}
            for r in rows]
