"""Pallas TPU kernel fusing the whole ColRel aggregation into one HBM pass.

Fuses three stages that the faithful path executes as separate ops —

  1. mixing-matrix mask       ``M = A * tau_dd^T``          (Eq. (3) mask)
  2. relay mix                ``Dx~ = M @ Dx``              (Eq. (3))
  3. tau-weighted blind PS sum ``(1/n) tau_up @ Dx~``       (Alg. 2 line 5)

— into a single grid pass over the flattened update stack ``Dx (n, d)``.
Because stages 2+3 compose to ``((1/n) tau_up @ M) @ Dx``, each grid step
reduces its ``(n, block_d)`` tile straight to ``(1, block_d)`` with fp32
accumulation: the update stack crosses HBM **exactly once** and the
kernel's output is the ``(d,)`` PS delta instead of a second (n, d)
intermediate (an n-fold write saving over relay_mix + a separate sum).

The tiny (n, n) / (1, n) connectivity operands stay pinned in VMEM across
the grid; the mask and the collapsed weight row are recomputed per step
(O(n^2) flops — free next to the (n x block_d) stream).

Tail handling: the d grid is ``cdiv(d, block_d)`` with **no host-side
padding of the update stack** — out-of-range lanes of the last tile read
garbage, but every output column is a function of its own input column
only, and Pallas masks out-of-range writes, so the garbage never lands.
bf16 updates are supported (fp32 accumulation via preferred_element_type);
the output is always fp32.

``row_stream_pallas`` is the segment-streaming twin (DESIGN.md §14): the
collapsed weight row is computed **once per round** by the caller and
each per-leaf ``(n, d_i)`` segment streams through independently — the
monolithic ``(n, d)`` stack never materializes.  Every output column is
a function of its own input column only, so the per-segment outputs are
exactly the corresponding column ranges of the monolithic pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_aggregate_kernel(a_ref, tau_dd_t_ref, tau_up_ref, x_ref, o_ref, *, inv_n):
    # Stage 1: realized mixing mask, recomputed in VMEM each grid step.
    m = a_ref[...] * tau_dd_t_ref[...]  # (n, n) = A * tau_dd^T
    # Stages 2+3 collapsed: w = (1/n) tau_up @ M, one (1, n) row vector.
    w = jax.lax.dot(
        tau_up_ref[...], m,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ) * inv_n
    # Stream the (n, block_d) tile once; reduce straight to (1, block_d).
    o_ref[...] = jax.lax.dot(
        w, x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_aggregate_pallas(
    A: jax.Array,        # (n, n) float32 relay weights alpha
    tau_up: jax.Array,   # (n,)  uplink arrival indicators
    tau_dd: jax.Array,   # (n, n) D2D arrival indicators (tau_dd[j, i]: j -> i)
    updates: jax.Array,  # (n, d) flattened client update stack, f32 or bf16
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """One-pass ColRel PS delta: ``(1/n) tau_up @ ((A * tau_dd^T) @ updates)``.

    Returns the ``(d,)`` fp32 global delta.
    """
    n, d = updates.shape
    a = A.astype(jnp.float32)
    tdt = tau_dd.astype(jnp.float32).T  # (n, n), tiny — layout for the mask
    tu = tau_up.astype(jnp.float32).reshape(1, n)
    bd = min(block_d, d)

    out = pl.pallas_call(
        functools.partial(_fused_aggregate_kernel, inv_n=1.0 / n),
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # A pinned in VMEM
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # tau_dd^T pinned
            pl.BlockSpec((1, n), lambda i: (0, 0)),   # tau_up pinned
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # the streamed stack
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="fused_aggregate_pallas",
    )(a, tdt, tu, updates)
    return out.reshape(d)


def _row_stream_kernel(w_ref, x_ref, o_ref):
    # The weight row arrives precomputed (carried across segments); each
    # grid step streams its (n, block_d) tile straight to (1, block_d).
    o_ref[...] = jax.lax.dot(
        w_ref[...], x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def row_stream_pallas(
    w: jax.Array,        # (n,) f32 collapsed weight row (caller computes once)
    segment: jax.Array,  # (n, d_i) one leaf's update segment, f32/bf16/int8
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """Segment-streaming delta: ``w @ segment`` with fp32 accumulation.

    Returns the ``(d_i,)`` fp32 partial delta for this segment — the
    columns the monolithic :func:`fused_aggregate_pallas` would have
    produced for the same leaf, without ever building the (n, d) stack.
    """
    n, d = segment.shape
    wr = w.astype(jnp.float32).reshape(1, n)
    bd = min(block_d, d)

    out = pl.pallas_call(
        _row_stream_kernel,
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),   # weight row pinned
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # the streamed segment
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="row_stream_pallas",
    )(wr, segment)
    return out.reshape(d)
