"""Inputs made from ``--seed``: per-purpose seeds, synthetic CIFAR-shaped
images, the partition over clients, and each client's batch indices.

The generator follows ``repro.data.synthetic_cifar`` (a smooth random
template per class plus per-pixel noise) but draws the noise in float32
from a pool of noise fields, so that set-up stays short; the
partitioners follow
``repro.data.partition``.  The benchmark keeps its own copies so that
the reference never depends on the program's code.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

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


def synthetic_images(n: int, seed: int, *, n_classes: int = 10,
                     image_size: int = 32, channels: int = 3,
                     noise: float = 0.6, pool: int = 8192):
    """``(images (n, H, W, C) float32, labels (n,) int32)``: a smooth
    random template per class plus Gaussian pixel noise.  The noise fields
    are drawn from a pool of ``pool``, which makes 50,000 images in about
    a second instead of three."""
    rng = np.random.default_rng(seed)
    freq = 4
    base = rng.normal(size=(n_classes, freq, freq, channels)).astype(np.float32)
    rep = image_size // freq
    templates = np.repeat(np.repeat(base, rep, axis=1), rep, axis=2)
    labels = rng.integers(0, n_classes, size=n).astype(np.int32)
    fields = rng.standard_normal((min(pool, n), image_size, image_size, channels),
                                 dtype=np.float32)
    fields *= np.float32(noise)
    images = fields[rng.integers(0, len(fields), size=n)]
    images += templates[labels]
    return images, labels


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


def client_arrays(images, labels, parts) -> List[Dict[str, np.ndarray]]:
    return [{"images": images[idx], "labels": labels[idx]} for idx in parts]


def batch_indices(seed: int, n_rows: int, steps: int, batch: int) -> np.ndarray:
    """The first ``steps`` minibatches a client with ``seed`` samples:
    ``(steps, batch)`` row indices, uniform with replacement."""
    return np.random.default_rng(seed).integers(0, n_rows, size=(steps, batch))
