"""Data pipeline: deterministic synthetic LM corpora + per-worker sharding.

The paper pre-trains on OpenWebText; offline we provide two corpora with
real sequential structure (so optimizers separate, unlike iid noise):

  * ``MarkovCorpus`` — an order-2 token-level Markov chain whose sparse
    transition kernel is a hash of the seed, so it costs no memory per
    context at any vocabulary size.  Entropy is controlled, and 100-step
    training curves already separate optimizers.
  * ``TextCorpus``   — byte-level corpus from any file (self-hosting: we
    ship our own source tree as the default corpus).

Batches are yielded in the DSM layout (W, tau, accum, B_micro, S):
worker i always consumes stream shard i (the paper's D_i), giving the
data-heterogeneity the theory's delta^2 term describes.
"""

from __future__ import annotations

import glob
import os
from typing import Iterator

import numpy as np


_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_CDF_BANK = 4096  # Dirichlet CDFs that the contexts' hashes choose among


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: a fixed bijective hash of uint64 arrays."""
    x = x + _GOLDEN
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


class MarkovCorpus:
    """Order-2 Markov chain over ``vocab`` tokens with ``branch`` choices.

    The transition of context ``(a, b)`` is computed, not stored: a hash of
    ``(seed, a, b)`` picks one of a fixed bank of Dirichlet(0.5) CDFs over
    the ``branch`` choices, and a hash of ``(seed, a, b, choice)`` names the
    next token.  Memory is O(bank * branch) at any vocabulary size (GPT-2's
    50,257 included), where a dense table would be O(vocab^2 * branch).
    """

    def __init__(self, vocab: int, branch: int = 8, seed: int = 0):
        self.vocab = vocab
        rng = np.random.default_rng(seed)
        self._salt = np.uint64(rng.integers(0, 2**63))
        cdf = np.cumsum(rng.dirichlet(np.ones(branch) * 0.5, size=_CDF_BANK),
                        axis=-1)
        cdf[:, -1] = 1.0  # rounding must never push u past the last choice
        self._cdf = cdf

    def transition(self, a: np.ndarray, b: np.ndarray, u: np.ndarray) -> np.ndarray:
        """Next tokens after contexts ``(a, b)`` for uniforms ``u`` in [0, 1)."""
        ctx = _mix64((a.astype(np.uint64) * np.uint64(self.vocab)
                      + b.astype(np.uint64)) ^ self._salt)
        cdf = self._cdf[(ctx % np.uint64(len(self._cdf))).astype(np.int64)]
        choice = (u[:, None] > cdf).sum(axis=-1).astype(np.uint64)
        nxt = _mix64(ctx + (choice + np.uint64(1)) * _GOLDEN)
        return (nxt % np.uint64(self.vocab)).astype(np.int32)

    def sample(self, rng: np.random.Generator, batch: int, seq: int) -> np.ndarray:
        out = np.empty((batch, seq), dtype=np.int32)
        out[:, :2] = rng.integers(0, self.vocab, size=(batch, 2))
        u = rng.random(size=(batch, seq))
        for t in range(2, seq):
            out[:, t] = self.transition(out[:, t - 2], out[:, t - 1], u[:, t])
        return out


class TextCorpus:
    """Byte-level corpus over a directory of text files (vocab 256)."""

    def __init__(self, root: str = ".", pattern: str = "**/*.py", max_bytes: int = 8_000_000):
        files = sorted(glob.glob(os.path.join(root, pattern), recursive=True))
        buf = []
        total = 0
        for f in files:
            try:
                b = open(f, "rb").read()
            except OSError:
                continue
            buf.append(b)
            total += len(b)
            if total >= max_bytes:
                break
        data = b"\n".join(buf)
        if len(data) < 65536:
            raise ValueError(f"corpus too small: {len(data)} bytes from {root}/{pattern}")
        self.data = np.frombuffer(data, dtype=np.uint8).astype(np.int32)
        self.vocab = 256

    def sample(self, rng: np.random.Generator, batch: int, seq: int) -> np.ndarray:
        starts = rng.integers(0, len(self.data) - seq - 1, size=batch)
        return np.stack([self.data[s : s + seq] for s in starts])


def dsm_batches(
    corpus,
    n_workers: int,
    tau: int,
    accum: int,
    b_micro: int,
    seq: int,
    seed: int = 0,
    heterogeneous: bool = True,
) -> Iterator[dict]:
    """Yield DSM outer-step batches {tokens: (W, tau, accum, B_micro, S)}.

    ``heterogeneous``: each worker draws from its own stream (paper's D_i);
    otherwise all workers share one stream (iid split).
    """
    rngs = [np.random.default_rng(seed + (i if heterogeneous else 0) * 1009 + 1)
            for i in range(n_workers)]
    while True:
        tokens = np.stack([
            corpus.sample(rngs[i], tau * accum * b_micro, seq)
            .reshape(tau, accum, b_micro, seq)
            for i in range(n_workers)
        ])
        yield {"tokens": tokens}


def eval_batch(corpus, batch: int, seq: int, seed: int = 10_000) -> dict:
    rng = np.random.default_rng(seed)
    return {"tokens": corpus.sample(rng, batch, seq)}
