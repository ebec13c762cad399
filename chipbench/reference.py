"""Plain float32 reference of the federated rounds the timed path runs.

Written from the published descriptions, not from the program.  A round
of ColRel (arXiv:2202.11850 Alg. 1-2): every client runs T steps of SGD
with L2 weight decay from the server model on its own minibatches; the
server receives ``(1/n) sum_i tau_i sum_j alpha_ij tau_ji Delta_j`` and
applies heavy-ball momentum with unit step (``m <- beta m - delta;
x <- x - m``).  The model's loss is the configuration kind's
(``chipbench/kinds/<kind>.py``).

Clients run one after another at ``Precision.HIGHEST``, so on a TPU the
reference is float32 throughout.  The server's state (parameters,
momentum and the aggregate) lives on the host; the round's collapsed
weights are known before the clients train, so each client's weighted
update is added to the aggregate in client order and dropped before the
next client trains.  The device holds one copy of the round's parameters
and one client's training at a time.
"""

from __future__ import annotations

import functools
import json
from types import ModuleType
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("loss", "model_key"))
def _client_update(params, batches, lr, wd, *, loss, model_key):
    model = json.loads(model_key)

    def step(p, batch):
        value, g = jax.value_and_grad(lambda q: loss(model, q, batch))(p)
        p = jax.tree.map(lambda w, gw: w - lr * (gw + wd * w), p, g)
        return p, value

    final, losses = jax.lax.scan(step, params, batches)
    delta = jax.tree.map(lambda a, b: a - b, final, params)
    return delta, jnp.mean(losses)


def collapsed_weights(tau_up, tau_dd, A) -> np.ndarray:
    """``w_j = (1/n) sum_i tau_i alpha_ij tau_ji`` in float64."""
    tau_up, tau_dd, A = (np.asarray(a, np.float64) for a in (tau_up, tau_dd, A))
    return tau_up @ (A * tau_dd.T) / tau_up.shape[0]


def run_rounds(kind: ModuleType, model: dict, hyper: dict, params0,
               clients: List[Dict[str, np.ndarray]], batch_idx: List[np.ndarray],
               tau_up, tau_dd, A, rounds: int, first_block: int,
               fault: Optional[str] = None) -> dict:
    """``rounds`` rounds from ``params0``, with ``kind.loss`` as the
    model's loss.

    ``batch_idx[i]`` holds client ``i``'s ``(rounds * T, B)`` row
    indices into each of its arrays; ``tau_up (rounds, n)``, ``tau_dd
    (rounds, n, n)`` and ``A (n, n)`` are the round's connectivity and
    relay weights.  Returns the per-round mean client loss and norm of
    the aggregate, the server momentum after ``first_block`` rounds, and
    the parameters after ``rounds``.

    ``fault`` plants a fault for the control readings:
    ``"half_batch"`` trains on the first half of every minibatch (the
    mean taken over it); ``"double_client"`` counts the update of the
    round's most heavily weighted client twice in the aggregate.
    """
    with jax.default_matmul_precision("highest"):
        return _run_rounds(kind, model, hyper, params0, clients, batch_idx,
                           tau_up, tau_dd, A, rounds, first_block, fault)


def _train_client(kind, key, params, data, idx, lr, wd):
    """One client's T steps from ``params`` on its rows ``idx (T, B)``:
    its update, on the host, and its mean loss."""
    batches = {k: jnp.asarray(v[idx]) for k, v in data.items()}
    delta, value = _client_update(params, batches, lr, wd, loss=kind.loss,
                                  model_key=key)
    return jax.device_get(delta), float(value)


def _add_weighted(agg, delta, wj):
    """``agg + wj * delta``, in place where ``agg`` exists."""
    if agg is None:
        return jax.tree.map(lambda d: wj * d, delta)
    for a, d in zip(jax.tree.leaves(agg), jax.tree.leaves(delta)):
        a += wj * d
    return agg


def _run_rounds(kind, model, hyper, params0, clients, batch_idx, tau_up, tau_dd,
                A, rounds, first_block, fault):
    T = int(hyper["local_steps"])
    lr, wd = np.float32(hyper["lr"]), np.float32(hyper["weight_decay"])
    beta = np.float32(hyper["server_momentum"])
    key = json.dumps(model, sort_keys=True)
    params = jax.tree.map(lambda x: np.asarray(x, np.float32), params0)
    momentum = jax.tree.map(np.zeros_like, params)
    losses, delta_norms, momentum_first = [], [], None
    for r in range(rounds):
        w = collapsed_weights(tau_up[r], tau_dd[r], A).astype(np.float32)
        if fault == "double_client":
            w[np.argmax(w)] *= 2
        server = jax.device_put(params)
        agg, client_losses = None, []
        for i, data in enumerate(clients):
            idx = batch_idx[i][r * T:(r + 1) * T]
            if fault == "half_batch":
                idx = idx[:, : idx.shape[1] // 2]
            delta, value = _train_client(kind, key, server, data, idx, lr, wd)
            agg = _add_weighted(agg, delta, w[i])
            del delta
            client_losses.append(value)
        del server
        momentum = jax.tree.map(lambda m, d: beta * m - d, momentum, agg)
        params = jax.tree.map(lambda p, m: p - m, params, momentum)
        losses.append(float(np.mean(client_losses)))
        delta_norms.append(float(np.sqrt(sum(
            float(jnp.sum(x * x)) for x in map(jnp.asarray, jax.tree.leaves(agg))))))
        del agg
        if r + 1 == first_block:
            momentum_first = momentum
    return {"losses": losses, "delta_norms": delta_norms,
            "momentum_first": momentum_first, "params": params}
