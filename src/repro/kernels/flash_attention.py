"""Pallas TPU flash attention (causal, online softmax) for long prefill.

Canonical TPU tiling: grid = (batch*heads, q_blocks, kv_blocks) with the
kv axis innermost; running (max, sum, acc) state lives in VMEM scratch and
is re-initialized whenever a new q block starts.  Causally dead kv blocks
are skipped with ``pl.when`` so the kernel does the ~T^2/2 work flash
attention is supposed to do.  Block shapes are (block_q x head_dim) and
(block_kv x head_dim) — multiples of (8, 128) for MXU alignment at the
production head dims (64/128 pad to lanes transparently).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, block_q, block_kv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal block skip: the first key of this block beyond the last query
    @pl.when(kj * block_kv <= qi * block_q + block_q - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        q_idx = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_idx = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + p.sum(axis=1, keepdims=True)
        acc_scr[...] = corr * acc_scr[...] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "interpret"))
def flash_attention_pallas(
    q: jax.Array,  # (BH, T, D)
    k: jax.Array,  # (BH, S, D)
    v: jax.Array,  # (BH, S, D)
    *,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, T, D = q.shape
    S = k.shape[1]
    assert T % block_q == 0 and S % block_kv == 0, (T, S, block_q, block_kv)
    scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_kv=block_kv
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, S // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_pallas",
    )(q, k, v)
