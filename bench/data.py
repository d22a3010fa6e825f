"""Synthetic token batches from the seed.

A copy of the program's synthetic source (documents are noisy walks over a
per-document Markov chain; ``repro.data.pipeline.SyntheticLM``) kept with
the benchmark, so that a change to the program's data code cannot change
what the benchmark feeds.  Batch ``i`` is a pure function of ``(seed, i)``
and of the traffic file's parameters; seeds of any size are taken whole.
Every batch has the same shape, so every seed gives the same work.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed) & (2**64 - 1), *stream])))


class MarkovTokens:
    """``batch(i) -> {"tokens": (B, S) int32, "labels": (B, S) int32}``,
    the labels the tokens shifted by one."""

    def __init__(self, seed: int, vocab: int, seq: int, batch: int, *,
                 n_chains: int = 64, order_vocab: int = 512,
                 noise: float = 0.05):
        self.seed, self.seq_len, self.batch_size = seed, seq, batch
        self.noise = noise
        width = min(vocab, order_vocab)
        self._next = _rng(seed, 0).integers(
            0, width, size=(n_chains, width, 4), dtype=np.int32)

    def batch(self, index: int) -> Dict[str, np.ndarray]:
        rng = _rng(self.seed, 1, index)
        B, S = self.batch_size, self.seq_len
        n_chains, width = self._next.shape[:2]
        chains = rng.integers(0, n_chains, size=B)
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, width, size=B)
        noise = rng.random((B, S)) < self.noise
        branch = rng.integers(0, 4, size=(B, S))
        rand_tok = rng.integers(0, width, size=(B, S), dtype=np.int32)
        for t in range(S):
            nxt = self._next[chains, toks[:, t], branch[:, t]]
            toks[:, t + 1] = np.where(noise[:, t], rand_tok[:, t], nxt)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_source(seed: int, vocab: int, traffic: dict) -> MarkovTokens:
    gen = dict(traffic["data"])
    kind = gen.pop("kind")
    if kind != "markov":
        raise ValueError(f"unknown token generator {kind!r}")
    return MarkovTokens(seed, vocab, traffic["seq"], traffic["batch"], **gen)
