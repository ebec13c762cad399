"""Pallas TPU kernels for the intra-chunk term of the one-group SSD
(Mamba-2 with B and C shared by every head), forward and backward,
joined by ``jax.custom_vjp``.

Per (batch, chunk) of ``Q`` tokens, with ``c`` a head's chunk-local
inclusive cumulative log decays:

    CB   = C B^T                                    (Q, Q), once for all heads
    L_h  = exp(c_t - c_s) for s <= t, else 0        (Q, Q), per head
    y_h  = (CB ⊙ L_h) x_h                           (Q, P)

and for the cotangent ``dy``:

    dx_h = (CB ⊙ L_h)^T dy_h
    dCB  = Σ_h (dy_h x_h^T) ⊙ L_h
    dc_h = rowsum(G_h) - colsum(G_h),   G_h = (dy_h x_h^T) ⊙ CB ⊙ L_h

The grid runs over (batch, chunk, head block).  The head-block axis is
innermost and sequential: ``CB`` is formed once per (batch, chunk) in
VMEM scratch, and ``dCB`` accumulates in its output block across head
blocks.  No head's (Q, Q) matrix reaches HBM.  ``dC = dCB B`` and
``dB = dCB^T C`` are two (Q, Q) x (Q, N) products left to XLA.

The blocks hold every array token-minor: x, y and their cotangents as
(H P, Q) (head h is rows ``[h P, (h + 1) P)``), C and B as (N, Q), c and
dc as (H, Q) rows (the column form ``c_t`` is a transpose in VMEM).  That
is the layout XLA gives these activations in the training step, so the
calls add no transpose around them; it also makes the chunk the lane
axis, hence :func:`tiles`.

Every exponent is ``min(c_t - c_s, 0)`` (non-positive, as in
``repro.models.ssm``), and the mask is a select to 0, so the backward
never multiplies by ``exp(-inf)``.

The jnp twin is ``repro.kernels.ops.ssd_intra``'s non-kernel branch (the
algebra of ``ref.ssd_intra_ref``, which ``ssm.ssd_chunked`` shares); the
oracles are ``repro.models.ssm.ssd_chunked`` fed B and C broadcast to
every head, and ``ssm.ssd_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
MAX_HEADS_PER_BLOCK = 8


def _heads_per_block(h: int) -> int:
    """Heads a grid step takes: the largest divisor of ``h`` up to
    :data:`MAX_HEADS_PER_BLOCK`."""
    return max(d for d in range(1, min(h, MAX_HEADS_PER_BLOCK) + 1) if h % d == 0)


def _causal(chunk: int):
    """The (Q, Q) mask ``s <= t``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _decay(ct, cs, causal):
    """``L[t, s] = exp(min(c_t - c_s, 0))`` for ``s <= t``, else 0."""
    return jnp.where(causal, jnp.exp(jnp.minimum(ct - cs, 0.0)), 0.0)


def _cb(c_ref, b_ref):
    """``C B^T`` from the blocks of C^T and B^T, (N, Q) each."""
    return jax.lax.dot_general(c_ref[...], b_ref[...], (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)


def _fwd_kernel(c_ref, b_ref, x_ref, cr_ref, y_ref, cb_scr, *, heads, p, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        cb_scr[...] = _cb(c_ref, b_ref)

    causal = _causal(chunk)
    rows_c = cr_ref[...]
    # the column form c_t, built in VMEM: a (.., Q, 1) array in HBM would be
    # padded 128-fold in lanes
    cols_c = rows_c.T
    for h in range(heads):  # head h is rows [h p, (h + 1) p) of the block
        rows = slice(h * p, (h + 1) * p)
        m = cb_scr[...] * _decay(cols_c[:, h:h + 1], rows_c[h:h + 1, :], causal)
        # y_h^T = x_h^T M^T
        y_ref[rows, :] = jax.lax.dot_general(x_ref[rows, :], m, (((1,), (1,)), ((), ())),
                                             preferred_element_type=F32)


def _bwd_kernel(c_ref, b_ref, x_ref, dy_ref, cr_ref, dx_ref, dcb_ref, dc_ref, cb_scr,
                *, heads, p, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _():
        cb_scr[...] = _cb(c_ref, b_ref)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

    causal = _causal(chunk)
    rows_c = cr_ref[...]
    cols_c = rows_c.T
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 0)
    row_sums = jnp.zeros((chunk, heads), F32)  # head h's rowsum(G_h) in column h
    col_sums = jnp.zeros((heads, chunk), F32)  # head h's colsum(G_h) in row h
    for h in range(heads):
        rows = slice(h * p, (h + 1) * p)
        cb = cb_scr[...]
        lmat = _decay(cols_c[:, h:h + 1], rows_c[h:h + 1, :], causal)
        dy = dy_ref[rows, :]
        # dx_h^T = dy_h^T M;  dM = dy_h x_h^T
        dx_ref[rows, :] = jax.lax.dot(dy, cb * lmat, preferred_element_type=F32)
        dm = jax.lax.dot_general(dy, x_ref[rows, :], (((0,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        dml = dm * lmat
        dcb_ref[...] += dml
        g = dml * cb
        row_sums = jnp.where(lane == h, jnp.sum(g, axis=1, keepdims=True), row_sums)
        col_sums = jnp.where(sub == h, jnp.sum(g, axis=0, keepdims=True), col_sums)
    dc_ref[...] = row_sums.T - col_sums


def tiles(t: int, h: int, p: int, chunk: int) -> bool:
    """Whether the kernels' blocks tile a sequence of ``t`` tokens in
    chunks of ``chunk`` with ``h`` heads of size ``p``: the chunk is the
    blocks' lane axis (a multiple of 128, or the whole sequence), each
    head's ``p`` rows start on a sublane tile (``p`` a multiple of 8), and
    a head block's rows of log decays fill whole sublane tiles (a multiple
    of 8, or every head)."""
    hb = _heads_per_block(h)
    return (chunk % 128 == 0 or chunk == t) and p % 8 == 0 and (hb % 8 == 0 or hb == h)


def _call(kernel, cT, xT, heads, chunk, n_in_x, outs, name, interpret, *args):
    """``pallas_call`` over the (batch, chunk, head block) grid.  Operands:
    C^T and B^T, ``n_in_x`` arrays in x^T's (Bt, H P, T) layout, then c^T
    (Bt, H, T); ``outs`` names each output's layout."""
    bt, n, t = cT.shape
    hp = xT.shape[1]
    p = hp // heads
    nc = t // chunk
    hb = _heads_per_block(heads)
    specs = {
        "cb_in": pl.BlockSpec((None, n, chunk), lambda b, i, j: (b, 0, i)),
        "x": pl.BlockSpec((None, hb * p, chunk), lambda b, i, j: (b, j, i)),
        "c": pl.BlockSpec((None, hb, chunk), lambda b, i, j: (b, j, i)),
        "cb": pl.BlockSpec((None, None, chunk, chunk), lambda b, i, j: (b, i, 0, 0)),
    }
    shapes = {"x": (bt, hp, t), "c": (bt, heads, t), "cb": (bt, nc, chunk, chunk)}
    multi = len(outs) > 1
    out_specs = [specs[o] for o in outs]
    out_shape = [jax.ShapeDtypeStruct(shapes[o], F32) for o in outs]
    return pl.pallas_call(
        functools.partial(kernel, heads=hb, p=p, chunk=chunk),
        grid=(bt, nc, heads // hb),
        in_specs=[specs["cb_in"]] * 2 + [specs["x"]] * n_in_x + [specs["c"]],
        out_specs=out_specs if multi else out_specs[0],
        out_shape=out_shape if multi else out_shape[0],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(*args)


def _transposed(a):
    """(Bt, T, ...) -> (Bt, prod(...), T): the token axis minor, the
    layout XLA gives these arrays in training, so the views are free."""
    return a.reshape(a.shape[0], a.shape[1], -1).transpose(0, 2, 1)


def _forward(C, B, x, c, chunk, interpret):
    h = x.shape[2]
    cT, bT, xT = _transposed(C), _transposed(B), _transposed(x)
    yT = _call(_fwd_kernel, cT, xT, h, chunk, 1, ("x",), "ssd_intra_pallas", interpret,
               cT, bT, xT, _transposed(c))
    return yT.transpose(0, 2, 1).reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_intra_pallas(C, B, x, c, chunk, interpret=False):
    """Intra-chunk SSD output of every head, float32.

    C, B: (Bt, T, N), the one group's; x: (Bt, T, H, P); c: (Bt, T, H),
    inclusive cumulative log decays restarted at each chunk of ``chunk``
    tokens.  Returns y: (Bt, T, H, P).  On a TPU the blocks must tile
    (:func:`tiles`).
    """
    return _forward(C, B, x, c, chunk, interpret)


def _vjp_fwd(C, B, x, c, chunk, interpret):
    return _forward(C, B, x, c, chunk, interpret), (C, B, x, c)


def _vjp_bwd(chunk, interpret, res, dy):
    C, B, x, c = res
    bt, t, n = C.shape
    h = x.shape[2]
    cT, bT, xT = _transposed(C), _transposed(B), _transposed(x)
    dxT, dcb, dcT = _call(
        _bwd_kernel, cT, xT, h, chunk, 2, ("x", "cb", "c"), "ssd_intra_pallas_bwd",
        interpret, cT, bT, xT, _transposed(dy), _transposed(c))
    Cc = C.reshape(bt, t // chunk, chunk, n)
    Bc = B.reshape(bt, t // chunk, chunk, n)
    dC = jnp.einsum("bnts,bnsd->bntd", dcb, Bc).reshape(bt, t, n)
    dB = jnp.einsum("bnts,bntd->bnsd", dcb, Cc).reshape(bt, t, n)
    return dC, dB, dxT.transpose(0, 2, 1).reshape(x.shape), dcT.transpose(0, 2, 1)


ssd_intra_pallas.defvjp(_vjp_fwd, _vjp_bwd)
