"""Inputs made from ``--seed`` that do not depend on the model:
per-purpose seeds, the partition of labelled rows over clients, and each
client's batch indices.  A model kind makes its rows
(``chipbench/kinds/<kind>.py``).

The partitioners follow ``repro.data.partition``.  The benchmark keeps
its own copies so that the reference never depends on the program's
code.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Seeds for each input stream, all below 2**31."""

    data: int
    partition: int
    init: int
    channel: int
    clients: int

    @classmethod
    def from_seed(cls, seed: int) -> "Seeds":
        words = np.random.SeedSequence(int(seed)).generate_state(5)
        return cls(*(int(w) & 0x7FFFFFFF for w in words))

    def client(self, i: int) -> int:
        """Client ``i``'s batch-sampling seed."""
        return self.clients + 997 * i


def partition(labels: np.ndarray, n_clients: int, spec: dict,
              seed: int) -> List[np.ndarray]:
    """Sample indices per client: ``{"kind": "iid"}`` or
    ``{"kind": "sort_and_partition", "s": s}`` (each client holds ``s``
    label-sorted shards, the paper's non-IID split)."""
    rng = np.random.default_rng(seed)
    if spec["kind"] == "iid":
        perm = rng.permutation(len(labels))
        return [np.sort(p) for p in np.array_split(perm, n_clients)]
    if spec["kind"] == "sort_and_partition":
        s = int(spec["s"])
        order = np.argsort(labels, kind="stable")
        shards = np.array_split(order, n_clients * s)
        ids = rng.permutation(n_clients * s)
        return [np.sort(np.concatenate([shards[t] for t in ids[c * s:(c + 1) * s]]))
                for c in range(n_clients)]
    raise ValueError(f"unknown partition kind {spec['kind']!r}")


def batch_indices(seed: int, n_rows: int, steps: int, batch: int) -> np.ndarray:
    """The first ``steps`` minibatches a client with ``seed`` samples:
    ``(steps, batch)`` row indices, uniform with replacement."""
    return np.random.default_rng(seed).integers(0, n_rows, size=(steps, batch))
