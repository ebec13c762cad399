"""A toy model kind (``"kind": "toy"``), added as a new file only: a
two-layer ReLU classifier on seeded random vectors.  The program's zoo
has no such model, so ``program_model`` is written here too; the
federated path around it (local SGD, relaying, aggregation, the server
step) is the program's."""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data

HIGHEST = jax.lax.Precision.HIGHEST


def _init(key, model: dict, dtype):
    k1, k2 = jax.random.split(key)
    f, h, c = model["features"], model["hidden"], model["n_classes"]
    return {"w1": (jax.random.normal(k1, (f, h)) * (2.0 / f) ** 0.5).astype(dtype),
            "b1": jnp.zeros((h,), dtype),
            "w2": (jax.random.normal(k2, (h, c)) * 0.1).astype(dtype),
            "b2": jnp.zeros((c,), dtype)}


def _logits(params, x, precision=None):
    h = jax.nn.relu(jnp.dot(x, params["w1"], precision=precision) + params["b1"])
    return jnp.dot(h, params["w2"], precision=precision) + params["b2"]


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@dataclasses.dataclass(frozen=True)
class Bundle:
    """What the trainer takes of a model: ``init`` and ``loss_fn``."""

    model: dict
    dtype: str

    def init(self, key):
        return _init(key, self.model, jnp.dtype(self.dtype))

    def loss_fn(self, params, batch):
        loss = _cross_entropy(_logits(params, batch["x"]), batch["labels"])
        return loss, {"ce": loss}


def program_model(model: dict, dtype: Optional[str] = None) -> Bundle:
    return Bundle(model, dtype or model["dtype"])


def init_params(model: dict, seed: int, dtype: Optional[str] = None):
    fn = jax.jit(functools.partial(_init, model=model,
                                   dtype=jnp.dtype(dtype or model["dtype"])))
    return fn(jax.random.PRNGKey(seed))


def client_arrays(model: dict, traffic: dict,
                  seeds: data.Seeds) -> List[Dict[str, np.ndarray]]:
    """A random template per class plus Gaussian noise, partitioned by
    the traffic's ``partition``."""
    rng = np.random.default_rng(seeds.data)
    n, c = int(traffic["data_size"]), model["n_classes"]
    templates = rng.normal(size=(c, model["features"])).astype(np.float32)
    labels = rng.integers(0, c, size=n).astype(np.int32)
    x = templates[labels] + rng.normal(size=(n, model["features"])).astype(np.float32)
    parts = data.partition(labels, int(traffic["n_clients"]), traffic["partition"],
                           seeds.partition)
    return [{"x": x[idx], "labels": labels[idx]} for idx in parts]


def loss(model: dict, params, batch):
    return _cross_entropy(_logits(params, batch["x"], HIGHEST), batch["labels"])
