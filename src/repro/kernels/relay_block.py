"""Pallas TPU kernels for block-sparse clustered relaying.

Under clustering the (n, n) mixing matrix is block-diagonal: only the C
diagonal ``(m, m)`` blocks carry weight (``core/blocks.py``).  The dense
kernels (``relay_mix.py`` / ``fused_aggregate.py``) would stream an
(n, n) operand that is ``1/C`` nonzero — at n = 2^14, C = 256 that is a
2 GiB mask of which 8 MiB matters.  These kernels index the ``(C, m, m)``
block tensor directly, so per grid step only one cluster's ``(m, m)``
weights and its ``(m, block_d)`` update slab touch VMEM; the dense mask
never exists anywhere, and flops drop from O(n²·d) to O(n·m·d).

Grid layout: ``(cdiv(d, block_d), C)`` with the cluster axis innermost.
For ``block_relay_mix`` every (c, d-tile) pair is independent.  For
``block_fused_aggregate`` the output tile ``(1, block_d)`` is *shared*
across the C cluster steps of one d-tile: cluster partials accumulate
into it in place, which is why the cluster axis must be minormost —
revisits to the same output block are then consecutive, so on TPU the
accumulator stays resident in VMEM across the whole cluster sweep and is
written back to HBM once per d-tile.

Alignment: ``m`` need not be a multiple of the 8-sublane / 128-lane
boundary — Mosaic masks sub-tile operands internally, and the per-column
argument from the dense kernels (each output column depends only on its
own input column; out-of-range writes are masked) carries over
unchanged, so tile-unaligned cluster sizes (m = 5, 48, ...) are exact,
just marginally less efficient.  ``tests/test_clustered.py`` pins them
against the dense oracle.

Like the dense kernels: small operands pinned in VMEM, fp32 accumulation
via ``preferred_element_type``, no host-side padding of the stack.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _block_relay_mix_kernel(a_ref, tau_t_ref, x_ref, o_ref):
    # One cluster's realized mixing block, recomputed in VMEM: M_c = A_c * tau_c^T
    m = a_ref[0] * tau_t_ref[0]  # (m, m)
    o_ref[...] = jax.lax.dot(
        m, x_ref[...],
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def block_relay_mix_pallas(
    Ab: jax.Array,     # (C, m, m) float32 per-cluster relay weights
    tau_b: jax.Array,  # (C, m, m) per-cluster D2D indicators
    updates: jax.Array,  # (n, d) = (C*m, d) flattened update stack
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Blocked consensus ``Dx~_c = (A_c * tau_c^T) @ Dx_c``: (n, d) ->
    (n, d) without materializing the dense (n, n) mask."""
    C, m, _ = Ab.shape
    n, d = updates.shape
    if n != C * m:
        raise ValueError(f"updates rows {n} != C*m = {C * m}")
    a = Ab.astype(jnp.float32)
    tbt = jnp.swapaxes(tau_b, 1, 2).astype(jnp.float32)
    bd = min(block_d, d)

    return pl.pallas_call(
        _block_relay_mix_kernel,
        grid=(pl.cdiv(d, bd), C),
        in_specs=[
            pl.BlockSpec((1, m, m), lambda i, c: (c, 0, 0)),  # cluster weights
            pl.BlockSpec((1, m, m), lambda i, c: (c, 0, 0)),  # cluster tau^T
            pl.BlockSpec((m, bd), lambda i, c: (c, i)),       # cluster slab
        ],
        out_specs=pl.BlockSpec((m, bd), lambda i, c: (c, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), updates.dtype),
        interpret=interpret,
        name="block_relay_mix_pallas",
    )(a, tbt, updates)


def _block_fused_aggregate_kernel(a_ref, tau_t_ref, tau_up_ref, x_ref, o_ref,
                                  *, inv_n):
    c = pl.program_id(1)  # cluster axis is innermost
    m = a_ref[0] * tau_t_ref[0]
    # collapsed cluster weight row: w_c = (1/n) tau_up_c @ M_c, (1, m)
    w = jax.lax.dot(
        tau_up_ref[0], m,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ) * inv_n
    partial = jax.lax.dot(
        w, x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    # The (1, bd) output tile is shared by this d-tile's C cluster steps:
    # initialize on the first cluster, accumulate on the rest.
    @pl.when(c == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(c > 0)
    def _accum():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def block_fused_aggregate_pallas(
    Ab: jax.Array,      # (C, m, m) float32 per-cluster relay weights
    tau_up: jax.Array,  # (n,) uplink arrival indicators
    tau_b: jax.Array,   # (C, m, m) per-cluster D2D indicators
    updates: jax.Array,  # (n, d) flattened update stack, f32 or bf16
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """One-pass blocked ColRel PS delta ``(1/n) sum_c tau_c @ (M_c @ Dx_c)``.

    Returns the (d,) fp32 global delta; the stack crosses HBM once and
    neither the dense mask nor a second (n, d) intermediate is ever
    written.
    """
    C, m, _ = Ab.shape
    n, d = updates.shape
    if n != C * m:
        raise ValueError(f"updates rows {n} != C*m = {C * m}")
    a = Ab.astype(jnp.float32)
    tbt = jnp.swapaxes(tau_b, 1, 2).astype(jnp.float32)
    tu = tau_up.astype(jnp.float32).reshape(C, 1, m)
    bd = min(block_d, d)

    out = pl.pallas_call(
        functools.partial(_block_fused_aggregate_kernel, inv_n=1.0 / n),
        grid=(pl.cdiv(d, bd), C),
        in_specs=[
            pl.BlockSpec((1, m, m), lambda i, c: (c, 0, 0)),
            pl.BlockSpec((1, m, m), lambda i, c: (c, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda i, c: (c, 0, 0)),
            pl.BlockSpec((m, bd), lambda i, c: (c, i)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="block_fused_aggregate_pallas",
    )(a, tbt, tu, updates)
    return out.reshape(d)


def _block_row_stream_kernel(w_ref, x_ref, o_ref):
    c = pl.program_id(1)  # cluster axis is innermost
    partial = jax.lax.dot(
        w_ref[0], x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(c == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(c > 0)
    def _accum():
        o_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def block_row_stream_pallas(
    w: jax.Array,        # (C, m) f32 collapsed cluster weight rows
    segment: jax.Array,  # (n, d_i) = (C*m, d_i) one leaf's update segment
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Segment-streaming blocked delta (DESIGN.md §14): the per-cluster
    collapsed rows arrive precomputed (carried across segments) and each
    cluster's slab of this segment accumulates into the shared output
    tile — the columns ``block_fused_aggregate_pallas`` would produce
    for the same leaf, without the monolithic stack."""
    C, m = w.shape
    n, d = segment.shape
    if n != C * m:
        raise ValueError(f"segment rows {n} != C*m = {C * m}")
    wr = w.astype(jnp.float32).reshape(C, 1, m)
    bd = min(block_d, d)

    out = pl.pallas_call(
        _block_row_stream_kernel,
        grid=(pl.cdiv(d, bd), C),
        in_specs=[
            pl.BlockSpec((1, 1, m), lambda i, c: (c, 0, 0)),  # cluster row
            pl.BlockSpec((m, bd), lambda i, c: (c, i)),       # cluster slab
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="block_row_stream_pallas",
    )(wr, segment)
    return out.reshape(d)
