"""Token traffic for the training cells, made from ``--seed`` on the host.

A copy of the program's ``data/synthetic.TokenPipeline.batch`` stream
(Zipf unigrams with an order-2 Markov mix: with probability 1/2 token t
is ``(t-1 + t-2) % V``), kept here so that the benchmark's inputs cannot
change when the program's generator does.  A batch is a pure function of
``(seed, step, shard)``: every seed gives the same sizes, only the
tokens differ.
"""
from __future__ import annotations

import numpy as np


def unigram_probs(vocab: int) -> np.ndarray:
    probs = 1.0 / np.arange(1, vocab + 1)
    return probs / probs.sum()


def token_batch(seed: int, step: int, rows: int, seq_len: int, vocab: int,
                shard: int = 0) -> np.ndarray:
    """(rows, seq_len) int32 tokens of one batch shard."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, shard]))
    base = rng.choice(vocab, size=(rows, seq_len), p=unigram_probs(vocab))
    mix = rng.random((rows, seq_len)) < 0.5
    for t in range(2, seq_len):
        base[:, t] = np.where(mix[:, t],
                              (base[:, t - 1] + base[:, t - 2]) % vocab,
                              base[:, t])
    return base.astype(np.int32)


def global_batch(seed: int, step: int, workers: int, global_rows: int,
                 seq_len: int, vocab: int) -> np.ndarray:
    """The step's global batch: worker w's rows are its own shard stream,
    stacked in worker order (the order the data axis splits them)."""
    rows = global_rows // workers
    return np.concatenate([token_batch(seed, step, rows, seq_len, vocab, w)
                           for w in range(workers)])
