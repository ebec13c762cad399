"""The CIFAR ResNet kind (``"kind": "cnn"``): what the benchmark needs of
a model that the rest of the harness does not know.

- ``program_model``: the program's model for the file's ``model`` dict;
- ``init_params``: initial weights made on the device from the seed, in
  one jitted call, in the layout the program's CNN reads (``stem``,
  ``stages``, ``fc``);
- ``client_arrays``: each client's host arrays (``images``, ``labels``),
  synthetic CIFAR-shaped images split over the clients;
- ``loss``: the plain float32 loss of one minibatch, for the reference.

The plain forward is written from the published descriptions, not from
the program: the CIFAR ResNet (He et al., arXiv:1512.03385 Sec. 4.2): a
3x3 stem, stages of basic blocks (two 3x3 convolutions, the first of a
stage at stride 2 after the first stage), GroupNorm after each
convolution (Wu & He, arXiv:1803.08494) in place of BatchNorm, a 1x1
projection without a norm where the shape changes, global average
pooling and a dense classifier; softmax cross-entropy.  Every
convolution and product runs at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data

HIGHEST = jax.lax.Precision.HIGHEST


def program_model(model: dict, dtype: Optional[str] = None):
    """``repro.models.build`` of the program's ``CNNConfig`` for
    ``model``; ``dtype`` overrides the model's."""
    from repro.models import build
    from repro.models.cnn import CNNConfig

    fields = {f.name for f in dataclasses.fields(CNNConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in model.items() if k in fields}
    if dtype is not None:
        kw["dtype"] = dtype
    return build(CNNConfig(**kw))


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


def init_params(model: dict, seed: int, dtype: Optional[str] = None):
    """The CNN's initial parameters for ``seed``, in the program model's
    dtype: He-normal convolutions, unit GroupNorm scales, a 0.01-scaled
    classifier, zero biases."""
    dtype = jnp.dtype(program_model(model, dtype).cfg.dtype)
    fn = jax.jit(functools.partial(_cnn_init, model=model, dtype=dtype))
    return fn(jax.random.PRNGKey(seed))


def synthetic_images(n: int, seed: int, *, n_classes: int = 10,
                     image_size: int = 32, channels: int = 3,
                     noise: float = 0.6, pool: int = 8192):
    """``(images (n, H, W, C) float32, labels (n,) int32)``: a smooth
    random template per class plus Gaussian pixel noise, as
    ``repro.data.synthetic_cifar`` makes them, but with the noise drawn
    in float32 from a pool of ``pool`` noise fields, which makes 50,000
    images in about a second instead of three."""
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


def client_arrays(model: dict, traffic: dict,
                  seeds: data.Seeds) -> List[Dict[str, np.ndarray]]:
    """The traffic's ``data_size`` synthetic images, partitioned over its
    ``n_clients`` by its ``partition``."""
    images, labels = synthetic_images(
        int(traffic["data_size"]), seeds.data, n_classes=model["n_classes"],
        image_size=model["image_size"], channels=model["channels"])
    parts = data.partition(labels, int(traffic["n_clients"]),
                           traffic["partition"], seeds.partition)
    return [{"images": images[idx], "labels": labels[idx]} for idx in parts]


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


def loss(model: dict, params, batch):
    """Mean softmax cross-entropy of ``batch["images"]`` against
    ``batch["labels"]``."""
    logp = jax.nn.log_softmax(forward(model, params, batch["images"]))
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], axis=1))
