"""In-graph sharding constraints (the hooks models and the FL round call).

``constrain_act`` pins the residual stream to ``cfg.act_spec`` — a
PartitionSpec template for the trailing ``(batch, seq, d_model)`` dims set
by the launch layer (see ``repro.launch.steps``).  Without it the
partitioner tends to drift activations (and therefore every backward
intermediate) to replicated layouts on the large meshes.

The active mesh is the one installed with ``jax.set_mesh(mesh)``.  All
helpers are total no-ops when no mesh is active (smoke tests, single
device) and silently drop any axis that is absent from the mesh or does
not divide the corresponding dim, so one spec template serves every
(arch x mesh) combination.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxisEntry = Union[None, str, Tuple[str, ...]]
Params = Any


def current_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh installed by the enclosing ``jax.set_mesh(mesh)``, if any."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _entry_axes(entry: AxisEntry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def _fit_spec(spec: Sequence[AxisEntry], shape: Tuple[int, ...], mesh) -> Optional[P]:
    """Align ``spec`` to the trailing dims of ``shape``, dropping any entry
    whose mesh axes are missing or whose product does not divide the dim."""
    ndim = len(shape)
    if len(spec) > ndim:
        return None
    out: list = [None] * ndim
    off = ndim - len(spec)
    nontrivial = False
    for i, entry in enumerate(spec):
        axes = tuple(
            a for a in _entry_axes(entry)
            if a in mesh.axis_names and mesh.shape[a] > 1
        )
        if not axes:
            continue
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if shape[off + i] % size != 0:
            continue
        out[off + i] = axes if len(axes) > 1 else axes[0]
        nontrivial = True
    return P(*out) if nontrivial else None


def constrain(x: jax.Array, spec: Sequence[AxisEntry]) -> jax.Array:
    """Constrain ``x`` to ``spec`` (trailing-dim aligned) under the active
    mesh; identity outside a mesh context."""
    mesh = current_mesh()
    if mesh is None:
        return x
    p = _fit_spec(tuple(spec), x.shape, mesh)
    if p is None:
        return x
    return jax.lax.with_sharding_constraint(x, p)


def constrain_act(cfg, x: jax.Array) -> jax.Array:
    """Residual-stream hook: pin ``x`` to ``cfg.act_spec`` when set."""
    spec = getattr(cfg, "act_spec", None)
    if not spec:
        return x
    return constrain(x, spec)


def constrain_grads(grads: Params, grad_shardings: Optional[Params]) -> Params:
    """Pin a gradient pytree to the params' sharded layout (ZeRO/FSDP modes);
    identity when no shardings were provided."""
    if grad_shardings is None:
        return grads
    return jax.lax.with_sharding_constraint(grads, grad_shardings)


def spmd_axis_name(spmd_axes: Optional[Tuple[str, ...]]):
    """Normalize a RoundConfig.spmd_axes tuple into the form
    ``jax.vmap(spmd_axis_name=...)`` expects (None / name / tuple)."""
    if not spmd_axes:
        return None
    return spmd_axes if len(spmd_axes) > 1 else spmd_axes[0]
