"""Plain float32 reference of the federated rounds the timed path runs.

Written from the published descriptions, not from the program:

- the CIFAR ResNet (He et al., arXiv:1512.03385 Sec. 4.2): a 3x3 stem,
  stages of basic blocks (two 3x3 convolutions, the first of a stage at
  stride 2 after the first stage), GroupNorm after each convolution
  (Wu & He, arXiv:1803.08494) in place of BatchNorm, a 1x1 projection
  without a norm where the shape changes, global average pooling and a
  dense classifier; softmax cross-entropy;
- a round of ColRel (arXiv:2202.11850 Alg. 1-2): every client runs T
  steps of SGD with L2 weight decay from the server model on its own
  minibatches; the server receives ``(1/n) sum_i tau_i sum_j
  alpha_ij tau_ji Delta_j`` and applies heavy-ball momentum with unit
  step (``m <- beta m - delta; x <- x - m``).

Clients run one after another and every convolution and product runs at
``Precision.HIGHEST``, so on a TPU the reference is float32 throughout.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import cnn_blocks

HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _group_norm(x, p, groups, eps=1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return y * p["scale"] + p["bias"]


def forward(model: dict, params, images):
    groups = model["groups"]
    x = jax.nn.relu(_group_norm(_conv(images, params["stem"]["w"], 1),
                                params["stem"]["gn"], groups))
    blocks = [blk for stage in params["stages"] for blk in stage]
    for blk, (_, _, _, stride) in zip(blocks, cnn_blocks(model)):
        h = jax.nn.relu(_group_norm(_conv(x, blk["w1"], stride), blk["gn1"], groups))
        h = _group_norm(_conv(h, blk["w2"], 1), blk["gn2"], groups)
        shortcut = _conv(x, blk["wproj"], stride) if "wproj" in blk else x
        x = jax.nn.relu(h + shortcut)
    pooled = x.mean(axis=(1, 2))
    return jnp.dot(pooled, params["fc"]["w"], precision=HIGHEST) + params["fc"]["b"]


def loss(model: dict, params, images, labels):
    logp = jax.nn.log_softmax(forward(model, params, images))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("model_key",))
def _client_update(params, images, labels, lr, wd, *, model_key):
    model = dict(model_key)
    model["widths"] = list(model["widths"])

    def step(p, batch):
        x, y = batch
        value, g = jax.value_and_grad(lambda q: loss(model, q, x, y))(p)
        p = jax.tree.map(lambda w, gw: w - lr * (gw + wd * w), p, g)
        return p, value

    final, losses = jax.lax.scan(step, params, (images, labels))
    delta = jax.tree.map(lambda a, b: a - b, final, params)
    return delta, jnp.mean(losses)


def _model_key(model: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def collapsed_weights(tau_up, tau_dd, A) -> np.ndarray:
    """``w_j = (1/n) sum_i tau_i alpha_ij tau_ji`` in float64."""
    tau_up, tau_dd, A = (np.asarray(a, np.float64) for a in (tau_up, tau_dd, A))
    return tau_up @ (A * tau_dd.T) / tau_up.shape[0]


def run_rounds(model: dict, hyper: dict, params0, clients: List[Dict[str, np.ndarray]],
               batch_idx: List[np.ndarray], tau_up, tau_dd, A, rounds: int,
               first_block: int, fault: Optional[str] = None) -> dict:
    """``rounds`` rounds from ``params0``.

    ``batch_idx[i]`` holds client ``i``'s ``(rounds * T, B)`` row
    indices; ``tau_up (rounds, n)``, ``tau_dd (rounds, n, n)`` and ``A
    (n, n)`` are the round's connectivity and relay weights.  Returns the
    per-round mean client loss and norm of the aggregate, the server
    momentum after ``first_block`` rounds, and the parameters after
    ``rounds``.

    ``fault`` plants a fault for the control readings:
    ``"half_batch"`` trains on the first half of every minibatch (the
    mean taken over it); ``"double_client"`` counts the update of the
    round's most heavily weighted client twice in the aggregate.
    """
    with jax.default_matmul_precision("highest"):
        return _run_rounds(model, hyper, params0, clients, batch_idx,
                           tau_up, tau_dd, A, rounds, first_block, fault)


def _run_rounds(model, hyper, params0, clients, batch_idx, tau_up, tau_dd, A,
                rounds, first_block, fault):
    T = int(hyper["local_steps"])
    lr, wd = np.float32(hyper["lr"]), np.float32(hyper["weight_decay"])
    beta = np.float32(hyper["server_momentum"])
    key = _model_key(model)
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params0)
    momentum = jax.tree.map(jnp.zeros_like, params)
    losses, delta_norms, momentum_first = [], [], None
    for r in range(rounds):
        deltas, client_losses = [], []
        for i, data in enumerate(clients):
            idx = batch_idx[i][r * T:(r + 1) * T]
            if fault == "half_batch":
                idx = idx[:, : idx.shape[1] // 2]
            delta, value = _client_update(
                params, jnp.asarray(data["images"][idx]),
                jnp.asarray(data["labels"][idx]), lr, wd, model_key=key)
            deltas.append(delta)
            client_losses.append(value)
        w = collapsed_weights(tau_up[r], tau_dd[r], A).astype(np.float32)
        if fault == "double_client":
            w[np.argmax(w)] *= 2
        agg = jax.tree.map(lambda *ds: sum(wj * d for wj, d in zip(w, ds)),
                           *deltas)
        momentum = jax.tree.map(lambda m, d: beta * m - d, momentum, agg)
        params = jax.tree.map(lambda p, m: p - m, params, momentum)
        losses.append(float(np.mean([float(v) for v in client_losses])))
        delta_norms.append(float(np.sqrt(sum(
            float(jnp.sum(x * x)) for x in jax.tree.leaves(agg)))))
        if r + 1 == first_block:
            momentum_first = jax.device_get(momentum)
    return {"losses": losses, "delta_norms": delta_norms,
            "momentum_first": momentum_first, "params": jax.device_get(params)}
