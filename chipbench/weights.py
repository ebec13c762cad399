"""Initial weights made on the device from the seed, in one jitted call,
in the layout the program's CNN reads (``stem``, ``stages``, ``fc``)."""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp


def cnn_blocks(model: dict) -> List[Tuple[int, int, int, int]]:
    """``(stage, cin, cout, stride)`` of every basic block, in order."""
    out, cin = [], model["widths"][0]
    for s, cout in enumerate(model["widths"]):
        for b in range(model["blocks_per_stage"]):
            out.append((s, cin, cout, 2 if (s > 0 and b == 0) else 1))
            cin = cout
    return out


def _cnn_init(key, model: dict, dtype):
    widths, nb = model["widths"], model["blocks_per_stage"]
    n_blocks = len(widths) * nb
    keys = iter(jax.random.split(key, 2 + 3 * n_blocks))

    def conv(k, cin, cout):
        std = (2.0 / (k * k * cin)) ** 0.5
        return (jax.random.normal(next(keys), (k, k, cin, cout), jnp.float32)
                * std).astype(dtype)

    def gn(c):
        return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}

    params = {"stem": {"w": conv(3, model["channels"], widths[0]),
                       "gn": gn(widths[0])},
              "stages": [[] for _ in widths]}
    for s, cin, cout, stride in cnn_blocks(model):
        blk = {"w1": conv(3, cin, cout), "gn1": gn(cout),
               "w2": conv(3, cout, cout), "gn2": gn(cout)}
        if stride != 1 or cin != cout:
            blk["wproj"] = conv(1, cin, cout)
        params["stages"][s].append(blk)
    fc = jax.random.normal(next(keys), (widths[-1], model["n_classes"]),
                           jnp.float32) * 0.01
    params["fc"] = {"w": fc.astype(dtype),
                    "b": jnp.zeros((model["n_classes"],), dtype)}
    return params


def make_params(model: dict, seed: int, dtype=jnp.float32):
    """The CNN's initial parameters for ``seed``: He-normal convolutions,
    unit GroupNorm scales, a 0.01-scaled classifier, zero biases."""
    fn = jax.jit(functools.partial(_cnn_init, model=model, dtype=dtype))
    return fn(jax.random.PRNGKey(seed))
