"""Synthetic token pipeline for the LM-scale architectures: deterministic
per-shard streams with a Zipfian unigram mixture + local n-gram structure
so losses actually decrease during smoke training."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator

import numpy as np


@dataclass
class TokenStreamConfig:
    vocab_size: int
    seq_len: int
    batch_size: int
    seed: int = 0
    zipf_a: float = 1.2


def _zipf_probs(vocab: int, a: float) -> np.ndarray:
    w = 1.0 / np.power(np.arange(1, vocab + 1), a)
    return w / w.sum()


class TokenStream:
    """Infinite deterministic stream of (tokens, labels) batches."""

    def __init__(self, cfg: TokenStreamConfig, shard: int = 0,
                 num_shards: int = 1):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed * 9973 + shard)
        self.probs = _zipf_probs(min(cfg.vocab_size, 50_000), cfg.zipf_a)
        self.vocab_eff = self.probs.shape[0]

    def next_batch(self) -> Dict[str, np.ndarray]:
        c = self.cfg
        base = self.rng.choice(self.vocab_eff, (c.batch_size, c.seq_len + 1),
                               p=self.probs)
        # inject copy structure: second half repeats the first half shifted
        half = (c.seq_len + 1) // 2
        base[:, half:2 * half] = base[:, :half]
        tokens = base[:, :-1].astype(np.int32)
        labels = base[:, 1:].astype(np.int32)
        return {"tokens": tokens, "labels": labels}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next_batch()


class ClientTokenStreams:
    """One token stream per FL client, for continual federated training
    of a language model.  Client ``c``'s ids are drawn i.i.d. from a
    Zipf(``zipf_a``) law over ``vocab_size`` ranks, each rank mapped to an
    id by the client's own permutation (numpy ``default_rng([seed, c])``),
    so clients differ in which ids are common.  The stream is cut into
    windows: window ``r`` holds ``n`` sequences of ``seq_len + 1`` ids
    whose ranks are the inverse CDF of ``default_rng([seed, c, r])``'s
    uniforms, so any round's window is made without the ones before it."""

    def __init__(self, vocab_size: int, seq_len: int, seed: int = 0,
                 zipf_a: float = 1.2):
        self.vocab_size, self.seq_len = vocab_size, seq_len
        self.seed, self.zipf_a = int(seed), zipf_a
        self.cdf = np.cumsum(_zipf_probs(vocab_size, zipf_a))

    def window(self, client: int, round_idx: int,
               sequences: int) -> np.ndarray:
        """Window ``round_idx`` of ``client``'s stream: (sequences,
        seq_len + 1) int32 ids."""
        perm = np.random.default_rng([self.seed, client]).permutation(
            self.vocab_size)
        u = np.random.default_rng([self.seed, client, round_idx]).random(
            (sequences, self.seq_len + 1))
        ranks = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                           self.vocab_size - 1)
        return perm[ranks].astype(np.int32)

    def round_tokens(self, clients, round_idx: int, steps: int,
                     batch: int):
        """A round's data for every client: training batches (C, steps,
        batch, seq_len + 1) from the first ``steps * batch`` sequences of
        its window, and a held-out batch (C, batch, seq_len + 1) from the
        next ``batch``."""
        w = np.stack([self.window(int(c), round_idx, (steps + 1) * batch)
                      for c in clients])
        C = w.shape[0]
        train = w[:, :steps * batch].reshape(C, steps, batch, -1)
        return train, w[:, steps * batch:]
