"""The seeded source corpus and the order a traffic mix reads it in.

Regenerated from the traffic file's rules alone, independently of the stripe
store: the benchmark compares what the loader delivered with this.
"""

from __future__ import annotations

import numpy as np


class Corpus:
    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.seq = traffic["seq_len"]
        self.batch = traffic["batch"]
        self.ipc = traffic["items_per_chunk"]
        self.n_items = self.ipc * traffic["n_chunks"]
        self.vocab = vocab
        self.seed = seed
        self._chunks: dict[int, np.ndarray] = {}
        self._orders: dict[int, np.ndarray] = {}

    def chunk(self, c: int) -> np.ndarray:
        if c not in self._chunks:
            rng = np.random.default_rng((self.seed, c))
            self._chunks[c] = rng.integers(0, self.vocab, (self.ipc, self.seq), dtype=np.int32)
        return self._chunks[c]

    def order(self, epoch: int) -> np.ndarray:
        if epoch not in self._orders:
            self._orders[epoch] = np.random.default_rng((self.seed, epoch)).permutation(self.n_items)
        return self._orders[epoch]

    def items(self, step: int) -> np.ndarray:
        """Item ids of the ``step``-th batch since the start (0-based)."""
        per_epoch = self.n_items // self.batch
        epoch, s = divmod(step, per_epoch)
        return self.order(epoch)[s * self.batch:(s + 1) * self.batch]

    def batch_at(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        tokens = np.stack([self.chunk(i // self.ipc)[i % self.ipc] for i in self.items(step)])
        labels = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        return tokens, labels

    def rows_wrong(self, step: int, tokens: np.ndarray, labels: np.ndarray) -> int:
        """Rows of a delivered batch whose tokens or labels differ from the source."""
        want_t, want_l = self.batch_at(step)
        if tokens.shape != want_t.shape or labels.shape != want_l.shape:
            return self.batch
        bad = np.any(tokens != want_t, axis=1) | np.any(labels != want_l, axis=1)
        return int(bad.sum())
