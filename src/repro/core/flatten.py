"""Flatten-once plumbing between pytree model updates and the (n, d) stack.

The ColRel hot path (relay mix + blind PS sum) is pure memory-bound
streaming over the stacked client updates.  Executing it per-leaf costs
one XLA op pair *per pytree leaf* (hundreds for the production archs) and
re-reads the (n, d) stack from HBM leaf by leaf.  Instead, the round
ravels the whole per-client update pytree into a single contiguous
``(n_clients, d)`` buffer **once per round**, streams that buffer through
the fused aggregation kernel exactly once, and unravels the resulting
``(d,)`` PS delta back to the model pytree.

Two ravel executions (DESIGN.md §14):

* **Segmented fill** (:func:`ravel` / :func:`ravel_stacked`) — the
  ``(n, d)`` buffer is pre-allocated once and filled leaf-by-leaf with
  ``dynamic_update_slice``.  Each write is the single consumer of the
  previous buffer value, so XLA updates it in place: the stack is
  materialized exactly once, and any dtype cast happens *per leaf inside
  the fill* (fused into the slice write) instead of materializing a
  second full-size casted copy first.
* **Segment streaming** (:func:`ravel_stacked_segments`) — at large d
  the stack itself is the memory bottleneck; this returns the per-leaf
  ``(n, d_i)`` column segments (reshape + cast only, no buffer at all)
  so the fused kernels can consume leaf buffers directly and the
  monolithic stack never exists.

:func:`ravel_stacked_concat` keeps the pre-segmentation ``concatenate``
implementation as the oracle/baseline (bitwise-identical values) for
``benchmarks/larged_bench.py`` and the segmented-path tests.

``FlatSpec`` is hashable static metadata (leaf shapes + treedef), so the
same spec can key jit caches and be rebuilt for free under tracing.

Every ravel and unravel runs under the ``fl.flatten`` scope
(:mod:`repro.telemetry.spans`), which names its compiled instructions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry.spans import FLATTEN

Params = Any

__all__ = [
    "FlatSpec",
    "flat_spec",
    "ravel",
    "ravel_stacked",
    "ravel_stacked_concat",
    "ravel_stacked_segments",
    "unravel",
    "unravel_stacked",
]


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static layout of a flattened pytree: where each leaf lives in (d,)."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(np.prod(s, dtype=np.int64)) for s in self.shapes)

    @property
    def offsets(self) -> Tuple[int, ...]:
        return tuple(int(o) for o in np.cumsum((0,) + self.sizes[:-1]))

    @property
    def d(self) -> int:
        return sum(self.sizes)


def flat_spec(tree: Params, *, stacked: bool = False) -> FlatSpec:
    """Layout spec for ``tree``.  With ``stacked=True`` the leaves carry a
    leading client axis ``(n, *shape)`` that is excluded from the layout."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(
        tuple(leaf.shape[1:] if stacked else leaf.shape) for leaf in leaves
    )
    return FlatSpec(treedef, shapes)


def _scoped(fn):
    """Run ``fn`` under the ``fl.flatten`` scope."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.named_scope(FLATTEN):
            return fn(*args, **kwargs)

    return wrapped


def _cast(part: jax.Array, dtype) -> jax.Array:
    # per-leaf cast, fused into the segment write by XLA — never a full
    # (n, d) casted intermediate
    return part if dtype is None else part.astype(dtype)


@_scoped
def ravel(tree: Params, *, dtype=None) -> jax.Array:
    """Pytree -> contiguous (d,) buffer (leaf order = jax.tree.flatten).

    Segmented fill: the output buffer is allocated once and each leaf is
    written into its column range with ``dynamic_update_slice`` (cast
    folded per leaf), so the flat buffer is materialized exactly once.
    """
    leaves = jax.tree.leaves(tree)
    if len(leaves) == 1:
        return _cast(leaves[0].reshape(-1), dtype)
    parts = [_cast(leaf.reshape(-1), dtype) for leaf in leaves]
    out_dtype = parts[0].dtype
    d = sum(p.shape[0] for p in parts)
    out = jnp.zeros((d,), out_dtype)
    offset = 0
    for p in parts:
        out = jax.lax.dynamic_update_slice(out, p, (offset,))
        offset += p.shape[0]
    return out


@_scoped
def ravel_stacked(tree: Params, *, dtype=None) -> jax.Array:
    """Stacked pytree (leaves ``(n, *shape)``) -> contiguous ``(n, d)``.

    This is the flatten-*once* step of the fused aggregation engine: the
    only materialization of the round's update stack — a segmented
    ``dynamic_update_slice`` fill of one pre-allocated buffer, with any
    dtype cast folded into each leaf's write.
    """
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    if len(leaves) == 1:
        return _cast(leaves[0].reshape(n, -1), dtype)
    parts = [_cast(leaf.reshape(n, -1), dtype) for leaf in leaves]
    out_dtype = parts[0].dtype
    d = sum(p.shape[1] for p in parts)
    out = jnp.zeros((n, d), out_dtype)
    offset = 0
    for p in parts:
        out = jax.lax.dynamic_update_slice(out, p, (0, offset))
        offset += p.shape[1]
    return out


@_scoped
def ravel_stacked_concat(tree: Params, *, dtype=None) -> jax.Array:
    """The pre-segmentation ``concatenate`` ravel (seed path), kept as the
    oracle/baseline: same values bit-for-bit as :func:`ravel_stacked`, but
    the full-size casted parts materialize before the concat — the extra
    copy ``benchmarks/larged_bench.py`` measures against."""
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    parts = [leaf.reshape(n, -1) for leaf in leaves]
    if dtype is not None:
        parts = [p.astype(dtype) for p in parts]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


@_scoped
def ravel_stacked_segments(tree: Params, *, dtype=None) -> List[jax.Array]:
    """Stacked pytree -> per-leaf ``(n, d_i)`` column segments, in spec
    order.  Layout-only (reshape + per-leaf cast); the monolithic stack is
    never built — ``jnp.concatenate(segments, axis=1)`` would reproduce
    :func:`ravel_stacked` bitwise.  This is what the segment-streaming
    kernel paths (DESIGN.md §14) consume."""
    leaves = jax.tree.leaves(tree)
    n = leaves[0].shape[0]
    return [_cast(leaf.reshape(n, -1), dtype) for leaf in leaves]


@_scoped
def unravel(spec: FlatSpec, flat: jax.Array, *, dtype: Optional[Any] = None) -> Params:
    """(d,) buffer -> pytree with ``spec``'s structure and leaf shapes."""
    if flat.shape != (spec.d,):
        raise ValueError(f"flat buffer {flat.shape} != spec total ({spec.d},)")
    if dtype is not None:
        flat = flat.astype(dtype)
    leaves = [
        jax.lax.slice(flat, (o,), (o + s,)).reshape(shape)
        for o, s, shape in zip(spec.offsets, spec.sizes, spec.shapes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)


@_scoped
def unravel_stacked(
    spec: FlatSpec, stack: jax.Array, *, dtype: Optional[Any] = None
) -> Params:
    """``(n, d)`` stack -> stacked pytree (leaves ``(n, *shape)``).

    Exact inverse of :func:`ravel_stacked` for a spec built with
    ``stacked=True`` — column slices are layout-only, so a ravel/unravel
    round trip at matching dtype is bitwise."""
    if stack.ndim != 2 or stack.shape[1] != spec.d:
        raise ValueError(f"stack {stack.shape} != (n, {spec.d})")
    n = stack.shape[0]
    if dtype is not None:
        stack = stack.astype(dtype)
    leaves = [
        jax.lax.slice(stack, (0, o), (n, o + s)).reshape((n,) + shape)
        for o, s, shape in zip(spec.offsets, spec.sizes, spec.shapes)
    ]
    return jax.tree.unflatten(spec.treedef, leaves)
