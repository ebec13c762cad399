"""Pallas TPU kernel for the ColRel relay consensus (Eq. (3)).

``Dx~ = M @ Dx`` where ``M = A * tau_dd^T`` is the realized (n x n) mixing
matrix and ``Dx`` is the (n, d) stack of flattened client updates with d up
to ~10^11.  The operation is totally memory-bound (arithmetic intensity
~n flops/byte with n = 16..64), so the kernel's job is to stream the
update matrix through VMEM exactly once at full HBM bandwidth with the tiny
mixing matrix pinned in VMEM, instead of letting XLA materialize masked
intermediates (A * tau^T, broadcasts) in HBM.

Tiling: grid of ``cdiv(d, block_d)`` over the d axis; block = (n, block_d)
with block_d a multiple of the 128-lane boundary.  Each grid step does an
(n x n) @ (n x block_d) MXU matmul — fully independent tiles.

The update stack is **never copied or padded on the host**: a partial
final tile reads garbage in its out-of-range lanes, but every output
column depends only on its own input column and Pallas masks out-of-range
writes, so the garbage never lands.  (The previous version materialized a
zero-padded (n_pad, d_pad) copy of the whole stack — a full second HBM
write+read for a kernel whose entire point is single-pass streaming.)
Sub-tile client counts (n not a multiple of the 8-sublane boundary) are
handled by Mosaic's internal masking; n is tiny so the cost is nil.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _relay_mix_kernel(m_ref, x_ref, o_ref):
    m = m_ref[...]
    x = x_ref[...]
    o_ref[...] = jax.lax.dot(
        m, x, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def relay_mix_pallas(
    mixing: jax.Array,  # (n, n) float32  — A * tau_dd^T, precomputed
    updates: jax.Array,  # (n, d)
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    n, d = updates.shape
    m = mixing.astype(jnp.float32)
    bd = min(block_d, d)

    return pl.pallas_call(
        _relay_mix_kernel,
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # mixing pinned in VMEM
            pl.BlockSpec((n, bd), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), updates.dtype),
        interpret=interpret,
        name="relay_mix_pallas",
    )(m, updates)
