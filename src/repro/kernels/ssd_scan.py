"""Pallas TPU kernel for the chunked SSD (Mamba2-style) recurrence.

One (batch*head) slice per grid row; the chunk axis is the innermost,
sequential grid dimension, with the (Dk x Dv) recurrent state living in
VMEM scratch across chunk iterations — the same carry pattern as the
flash-attention kernel's online-softmax state.

Per chunk (Q tokens):
    intra  = (q k^T ⊙ causal-decay) v
    inter  = exp(c_t) * q_t @ S
    S'     = exp(c_last) * S + sum_s exp(c_last - c_s) k_s v_s^T

All decay exponents are differences of cumulative log-decays and are
<= 0 by construction — no overflow, no rescaling passes.

Its jnp counterpart is ``repro.models.ssm.ssd_chunked`` (per-head q and
k); the oracle for tests is ``ssm.ssd_reference``.  The model's Mamba-2
mixers run ``ssm.ssd_one_group`` instead, whose intra-chunk term is the
fused kernel of ``ssd_intra.py`` (forward and backward); this kernel has
no backward and stays a forward-only kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(q_ref, k_ref, v_ref, lc_ref, o_ref, s_scr, *, chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0].astype(jnp.float32)  # (Q, Dk)
    k = k_ref[0].astype(jnp.float32)  # (Q, Dk)
    v = v_ref[0].astype(jnp.float32)  # (Q, Dv)
    ld = lc_ref[0].astype(jnp.float32)  # (Q, 1) per-step log decay
    # chunk-LOCAL inclusive cumulative decay, as a tril matmul (MXU-friendly)
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (t_i >= s_i).astype(jnp.float32)
    c = jax.lax.dot(tril, ld, preferred_element_type=jnp.float32)  # (Q, 1)

    # intra-chunk: scores[t, s] = (q_t . k_s) * exp(c_t - c_s), s <= t
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    dec = c - c.reshape(1, chunk)  # (Q, Q): c_t - c_s
    dec = jnp.where(t_i >= s_i, jnp.minimum(dec, 0.0), NEG_INF)
    y = jax.lax.dot(
        (scores * jnp.exp(dec)).astype(v.dtype), v, preferred_element_type=jnp.float32
    )

    # inter-chunk: exp(c_t) * q_t @ S_carry
    y += jnp.exp(c) * jax.lax.dot(q, s_scr[...], preferred_element_type=jnp.float32)

    # state update: S' = exp(c_last) S + sum_s exp(c_last - c_s) k_s v_s^T
    c_last = c[chunk - 1, 0]
    kdec = k * jnp.exp(jnp.minimum(c_last - c, 0.0))
    s_scr[...] = jnp.exp(c_last) * s_scr[...] + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    q: jax.Array,  # (BH, T, Dk)
    k: jax.Array,  # (BH, T, Dk)
    v: jax.Array,  # (BH, T, Dv)
    log_decay: jax.Array,  # (BH, T) non-positive
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    BH, T, Dk = q.shape
    Dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    ld = log_decay.astype(jnp.float32)[..., None]  # (BH, T, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, T // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, Dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, Dk), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, Dv), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        interpret=interpret,
        name="ssd_scan_pallas",
    )(q, k, v, ld)
