"""Deterministic, resumable synthetic data pipelines.

DLRMQueryStream — dense + categorical features with per-table hotness
(paper §V datasets; heterogeneous mixes per Table VII).

Determinism contract: state is (seed, step). `state_dict()`/`load_state_dict`
round-trip exactly; a restored stream reproduces the same batches — this is
what checkpoint/restart tests assert.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Sequence

import numpy as np

from repro.core.access_patterns import make_pattern

# paper Table VII heterogeneous mixtures (counts per hotness level)
HETERO_MIXES = {
    "mix1": {"high_hot": 100, "med_hot": 75, "low_hot": 50, "random": 25},
    "mix2": {"high_hot": 62, "med_hot": 63, "low_hot": 63, "random": 62},
    "mix3": {"high_hot": 25, "med_hot": 50, "low_hot": 75, "random": 100},
}


@dataclasses.dataclass
class DLRMBatch:
    dense: np.ndarray      # [B, F] float32
    indices: np.ndarray    # [B, T, L] int32
    labels: np.ndarray     # [B] float32


class DLRMQueryStream:
    def __init__(self, *, num_tables: int, rows: int, pooling: int,
                 batch_size: int, dense_features: int = 13,
                 hotness: str | Sequence[str] = "med_hot", seed: int = 0):
        if isinstance(hotness, str):
            hotness = [hotness] * num_tables
        assert len(hotness) == num_tables
        self.patterns = [make_pattern(h, rows, seed=seed + t)
                         for t, h in enumerate(hotness)]
        self.num_tables = num_tables
        self.rows = rows
        self.batch_size = batch_size
        self.pooling = pooling
        self.dense_features = dense_features
        self.seed = seed
        self.step = 0

    @classmethod
    def heterogeneous(cls, mix: str, rows: int, pooling: int,
                      batch_size: int, seed: int = 0) -> "DLRMQueryStream":
        hotness = []
        for h, n in HETERO_MIXES[mix].items():
            hotness += [h] * n
        return cls(num_tables=len(hotness), rows=rows, pooling=pooling,
                   batch_size=batch_size, hotness=hotness, seed=seed)

    def next_batch(self) -> DLRMBatch:
        rng = np.random.default_rng((self.seed << 20) ^ self.step)
        b = self.batch_size
        idx = np.stack(
            [p.sample(b, self.pooling, seed=self.step * 1000 + t)
             for t, p in enumerate(self.patterns)], axis=1)
        batch = DLRMBatch(
            dense=rng.standard_normal((b, self.dense_features),
                                      dtype=np.float32),
            indices=idx.astype(np.int32),
            labels=(rng.random(b) < 0.2).astype(np.float32),
        )
        self.step += 1
        return batch

    def sample_trace(self, num_batches: int = 4,
                     peek: bool = False) -> np.ndarray:
        """The next `num_batches` batches' indices as one planning trace
        [num_batches * B, T, L] — offline profiling input for hot-tier
        planning (paper §IV-C) and the tiered parameter server's initial
        plans. By default the profiled batches are CONSUMED (they are the
        profiling window's traffic; serving continues on fresh batches —
        planning and evaluation windows must not coincide). `peek=True`
        restores the stream position instead."""
        step0 = self.step
        try:
            return np.concatenate(
                [self.next_batch().indices for _ in range(num_batches)],
                axis=0)
        finally:
            if peek:
                self.step = step0

    def __iter__(self) -> Iterator[DLRMBatch]:
        while True:
            yield self.next_batch()

    # -- resume -------------------------------------------------------------
    def state_dict(self) -> dict:
        return {"seed": self.seed, "step": self.step}

    def load_state_dict(self, st: dict) -> None:
        assert st["seed"] == self.seed, "stream seed mismatch on restore"
        self.step = int(st["step"])

