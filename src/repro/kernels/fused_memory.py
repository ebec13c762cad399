"""Pallas TPU kernel fusing the memory strategy's select-accumulate-update.

The ``memory`` strategy's PS recursion (implicit gossip, arXiv:2404.10091)
is three streaming stages over two ``(n, d)`` buffers — the round's
update stack ``x`` and the replay buffer ``B``:

    tilde    = (A * tau_dd^T) @ x            # ColRel D2D consensus
    contrib  = tau_up ⊙ tilde + (1 - tau_up) ⊙ B     # select
    delta    = (1/n) Σ_i contrib_i                   # accumulate
    B'       = contrib                               # update

Executed separately that is two full reads (x, B) plus an (n, d)
``tilde`` intermediate written and re-read, plus the contrib write —
five (n, d) HBM crossings.  Fused, each ``(n, block_d)`` grid step
reads its x and B tiles once, keeps ``tilde``/``contrib`` in VMEM, and
writes exactly the two outputs the recursion needs: the ``(1, block_d)``
delta tile and the ``(n, block_d)`` new-buffer tile — three crossings,
and no ``tilde`` ever touches HBM (the same flatten-once treatment
``fused_aggregate`` gives colrel; ROADMAP "Per-strategy Pallas
kernels").

The (n, n) connectivity operands and the (n, 1) uplink selector stay
pinned in VMEM across the ``cdiv(d, block_d)`` grid.  Tail tiles need
no host-side padding: every output column depends only on its own
input column and Pallas masks out-of-range writes.

``MemoryStrategy.aggregate`` (pure jnp, same contraction order) is the
correctness oracle — asserted in ``tests/test_wire.py``.

``memory_stream_pallas`` is the segment-streaming twin (DESIGN.md §14):
the realized mixing mask ``A * tau_dd^T`` is computed **once per round**
by the caller and each per-leaf ``(n, d_i)`` segment of the update stack
and the replay buffer streams through independently — the monolithic
``(n, d)`` stack never materializes, and the caller writes each
``contrib`` segment back into the (donated) replay buffer in place.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_memory_kernel(a_ref, tau_dd_t_ref, tau_col_ref, x_ref, buf_ref,
                         delta_ref, contrib_ref, *, inv_n):
    # Realized mixing mask, recomputed in VMEM each grid step.
    m = a_ref[...] * tau_dd_t_ref[...]  # (n, n) = A * tau_dd^T
    tilde = jax.lax.dot(
        m, x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    t = tau_col_ref[...]  # (n, 1) uplink selector
    contrib = t * tilde + (1.0 - t) * buf_ref[...].astype(jnp.float32)
    contrib_ref[...] = contrib
    delta_ref[...] = jnp.sum(contrib, axis=0, keepdims=True) * inv_n


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_memory_update_pallas(
    A: jax.Array,        # (n, n) float32 relay weights alpha
    tau_up: jax.Array,   # (n,)  uplink arrival indicators
    tau_dd: jax.Array,   # (n, n) D2D arrival indicators (tau_dd[j, i]: j -> i)
    updates: jax.Array,  # (n, d) flattened client update stack, f32 or bf16
    buffer: jax.Array,   # (n, d) f32 replay buffer (last delivered contribs)
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One-pass memory-strategy round: returns ``(delta (d,), buffer' (n, d))``
    with fp32 accumulation throughout."""
    n, d = updates.shape
    a = A.astype(jnp.float32)
    tdt = tau_dd.astype(jnp.float32).T  # (n, n), tiny — layout for the mask
    tcol = tau_up.astype(jnp.float32).reshape(n, 1)
    bd = min(block_d, d)

    delta, contrib = pl.pallas_call(
        functools.partial(_fused_memory_kernel, inv_n=1.0 / n),
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # A pinned in VMEM
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # tau_dd^T pinned
            pl.BlockSpec((n, 1), lambda i: (0, 0)),   # uplink selector pinned
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # streamed update stack
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # streamed replay buffer
        ],
        out_specs=(
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((n, bd), lambda i: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ),
        interpret=interpret,
        name="fused_memory_update_pallas",
    )(a, tdt, tcol, updates, buffer)
    return delta.reshape(d), contrib


def _memory_stream_kernel(mix_ref, tau_col_ref, x_ref, buf_ref,
                          delta_ref, contrib_ref, *, inv_n):
    # The realized mask arrives precomputed (carried across segments).
    tilde = jax.lax.dot(
        mix_ref[...], x_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )
    t = tau_col_ref[...]  # (n, 1) uplink selector
    contrib = t * tilde + (1.0 - t) * buf_ref[...].astype(jnp.float32)
    contrib_ref[...] = contrib
    delta_ref[...] = jnp.sum(contrib, axis=0, keepdims=True) * inv_n


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def memory_stream_pallas(
    mix: jax.Array,      # (n, n) f32 realized mask A * tau_dd^T (caller-computed)
    tau_up: jax.Array,   # (n,)  uplink arrival indicators
    segment: jax.Array,  # (n, d_i) one leaf's update segment, f32 or bf16
    buf_seg: jax.Array,  # (n, d_i) matching replay-buffer columns, f32
    *,
    block_d: int = 2048,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Segment-streaming memory round: ``(delta_seg (d_i,), contrib_seg
    (n, d_i))`` — the columns :func:`fused_memory_update_pallas` would
    produce for this leaf, without the monolithic stack."""
    n, d = segment.shape
    tcol = tau_up.astype(jnp.float32).reshape(n, 1)
    bd = min(block_d, d)

    delta, contrib = pl.pallas_call(
        functools.partial(_memory_stream_kernel, inv_n=1.0 / n),
        grid=(pl.cdiv(d, bd),),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),   # realized mask pinned
            pl.BlockSpec((n, 1), lambda i: (0, 0)),   # uplink selector pinned
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # streamed segment
            pl.BlockSpec((n, bd), lambda i: (0, i)),  # streamed buffer columns
        ],
        out_specs=(
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((n, bd), lambda i: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
        ),
        interpret=interpret,
        name="memory_stream_pallas",
    )(mix.astype(jnp.float32), tcol, segment, buf_seg)
    return delta.reshape(d), contrib
