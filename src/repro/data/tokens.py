"""Token data pipeline: Hoard-cached real-bytes datasets -> jnp batches.

Bridges ``repro.core`` (the paper's cache) to JAX training: a synthetic token
corpus is materialised as real chunk files striped across node directories,
and ``TokenLoader`` reads items through the stripe store (CRC-verified,
closest replica) into device-ready (tokens, labels) batches.  The training
loop sees a plain iterator — Requirement 4's transparency — and per-epoch
order is a seeded permutation with resumable state (epoch, step), which the
checkpoint manager persists for deterministic restart.  Each batch is one
``loader.batch`` span of :mod:`repro.core.hostspans`, over its item reads.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np

from ..core import CacheManager, DatasetSpec, Node, StripeStore, hostspans
from ..train.checkpoint import SamplerState


@dataclass
class TokenDatasetSpec:
    dataset_id: str
    n_sequences: int
    seq_len: int
    vocab: int
    seed: int = 0

    @property
    def item_bytes(self) -> int:
        return self.seq_len * 4              # int32 tokens


def materialize_token_dataset(
    store: StripeStore,
    cache: CacheManager,
    spec: TokenDatasetSpec,
    nodes: list[Node],
    *,
    items_per_chunk: int = 64,
    replication: int = 1,
):
    """Generate + stripe a synthetic corpus as real chunk files."""

    def payload(chunk_idx: int) -> bytes:
        rng = np.random.default_rng((spec.seed, chunk_idx))
        toks = rng.integers(
            0, spec.vocab, (items_per_chunk, spec.seq_len), dtype=np.int32
        )
        return toks.tobytes()

    dspec = DatasetSpec(
        spec.dataset_id, f"synthetic://{spec.dataset_id}", spec.n_sequences, spec.item_bytes
    )
    if spec.dataset_id not in cache.entries:
        cache.register(dspec)
    cache.admit(
        spec.dataset_id, nodes, materialize=True, payload=payload,
        items_per_chunk=items_per_chunk,
    )
    cache.mark_filled(spec.dataset_id)
    return dspec


class TokenLoader:
    """Iterates (tokens, labels) batches from striped chunks; resumable."""

    def __init__(
        self,
        store: StripeStore,
        spec: TokenDatasetSpec,
        reader: Node,
        *,
        batch: int,
        state: Optional[SamplerState] = None,
    ):
        self.store = store
        self.spec = spec
        self.reader = reader
        self.batch = batch
        self.state = state or SamplerState(seed=spec.seed)

    def _order(self, epoch: int) -> np.ndarray:
        rng = np.random.default_rng((self.spec.seed, epoch))
        return rng.permutation(self.spec.n_sequences)

    def __iter__(self) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        while True:
            order = self._order(self.state.epoch)
            steps = len(order) // self.batch
            while self.state.step_in_epoch < steps:
                s = self.state.step_in_epoch
                ids = order[s * self.batch : (s + 1) * self.batch]
                # closed before the yield: the consumer's time is not the batch's
                with hostspans.batch():
                    toks = np.stack([self._read_item(i) for i in ids])
                    self.state.step_in_epoch += 1
                    labels = np.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
                yield toks, labels
            self.state.epoch += 1
            self.state.step_in_epoch = 0

    def _read_item(self, item: int) -> np.ndarray:
        raw = self.store.read_item(self.spec.dataset_id, int(item), self.reader)
        return np.frombuffer(raw, np.int32).reshape(self.spec.seq_len).copy()
