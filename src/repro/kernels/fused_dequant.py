"""Pallas TPU kernel fusing dequantize + relay mix + PS accumulate.

The ``quantized`` strategy receives the update stack in the int8 affine
wire format ``(q int8 (n, d), s f32 (n, 1))`` with ``x = q · s`` per
client row.  The naive PS pipeline dequantizes to a full f32 ``(n, d)``
stack (4x the HBM traffic of the wire payload, plus an (n, d) write)
and then runs the ColRel aggregation over it.  But the whole ColRel
collapse is linear in the per-client rows:

    delta = (1/n) tau_up @ ((A * tau_dd^T) @ (q · s))
          = ((1/n) tau_up @ (A * tau_dd^T) · s^T) @ q

so the per-client dequant scales fold straight into the collapsed
weight row, and the kernel streams the **int8** stack through HBM
exactly once — a 4x traffic saving over the dequantize-then-aggregate
oracle on top of the flatten-once wins of ``fused_aggregate``
(DESIGN.md §4/§8).  The dequantized f32 stack is never materialized
anywhere.

Grid layout matches ``fused_aggregate``: the tiny (n, n) / (1, n)
connectivity and scale operands stay pinned in VMEM across the
``cdiv(d, block_d)`` grid; each step reduces its ``(n, block_d)`` int8
tile straight to ``(1, block_d)`` f32.  Tail tiles rely on the same
no-padding argument: every output column is a function of its own
input column only, and Pallas masks out-of-range writes.

The per-leaf / dense dequant path (``codec.decode`` then the inner
strategy's aggregation) is the correctness oracle —
``tests/test_wire.py`` and ``benchmarks/quant_bench.py`` assert
agreement within fp32 contraction-order tolerance.

``dequant_row_stream_pallas`` is the segment-streaming twin
(DESIGN.md §14): the caller folds the per-client scales (and bias
correction) into the collapsed weight row once with
:func:`fold_dequant_scales`, then streams each per-leaf int8 segment
independently — neither the monolithic int8 stack nor any f32 stack
ever materializes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_dequant_kernel(a_ref, tau_dd_t_ref, tau_up_ref, scale_ref, q_ref,
                          o_ref, *, inv_n):
    # Realized mixing mask + scalar collapse, recomputed in VMEM each step.
    m = a_ref[...] * tau_dd_t_ref[...]  # (n, n) = A * tau_dd^T
    w = jax.lax.dot(
        tau_up_ref[...], m,
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    ) * inv_n
    # Fold the per-client dequant scales into the weight row: the int8
    # tile is consumed directly, no f32 stack ever exists.
    ws = w * scale_ref[...]  # (1, n)
    o_ref[...] = jax.lax.dot(
        ws, q_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_dequant_aggregate_pallas(
    A: jax.Array,        # (n, n) float32 relay weights alpha
    tau_up: jax.Array,   # (n,)  uplink arrival indicators
    tau_dd: jax.Array,   # (n, n) D2D arrival indicators (tau_dd[j, i]: j -> i)
    q: jax.Array,        # (n, d) int8 quantized update stack
    scale: jax.Array,    # (n,) or (n, 1) per-client dequant scales
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """One-pass quantized ColRel PS delta:
    ``(1/n) tau_up @ ((A * tau_dd^T) @ (q * scale))`` computed as
    ``((1/n) tau_up @ (A * tau_dd^T) * scale^T) @ q``.

    Returns the ``(d,)`` fp32 global delta.
    """
    n, d = q.shape
    a = A.astype(jnp.float32)
    tdt = tau_dd.astype(jnp.float32).T  # (n, n), tiny — layout for the mask
    tu = tau_up.astype(jnp.float32).reshape(1, n)
    s = scale.astype(jnp.float32).reshape(1, n)
    bd = min(block_d, d)

    out = pl.pallas_call(
        functools.partial(_fused_dequant_kernel, inv_n=1.0 / n),
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # A pinned in VMEM
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # tau_dd^T pinned
            pl.BlockSpec((1, n), lambda i: (0, 0)),   # tau_up pinned
            pl.BlockSpec((1, n), lambda i: (0, 0)),   # dequant scales pinned
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # the streamed int8 stack
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="fused_dequant_aggregate_pallas",
    )(a, tdt, tu, s, q)
    return out.reshape(d)


def fold_dequant_scales(w: jax.Array, scale: jax.Array) -> jax.Array:
    """Fold the per-client dequant scales into a collapsed weight row:
    ``(w * scale)`` with everything flattened to ``(n,)`` f32.  The same
    fold the monolithic kernel performs in VMEM, hoisted out so the
    segment-streaming path pays it once per round instead of per tile."""
    return w.astype(jnp.float32).reshape(-1) * scale.astype(jnp.float32).reshape(-1)


def dequant_row_stream_pallas(ws: jax.Array, q_segment: jax.Array, *,
                              block_d: int = 2048,
                              interpret: bool = False) -> jax.Array:
    """Stream one int8 segment against the scale-folded weight row.

    ``ws @ q_segment`` with fp32 accumulation — the int8 columns cross
    HBM once and the dequantized f32 form never exists.  Delegates to
    ``row_stream_pallas`` (the kernel upcasts the tile in VMEM)."""
    from repro.kernels.fused_aggregate import row_stream_pallas

    return row_stream_pallas(ws, q_segment, block_d=block_d,
                             interpret=interpret)
