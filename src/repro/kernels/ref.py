"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose vs these)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def relay_mix_ref(mixing: jax.Array, updates: jax.Array) -> jax.Array:
    """(n, n) @ (n, d) in fp32 accumulation."""
    return (
        mixing.astype(jnp.float32) @ updates.astype(jnp.float32)
    ).astype(updates.dtype)


def fused_aggregate_ref(A: jax.Array, tau_up: jax.Array, tau_dd: jax.Array,
                        updates: jax.Array) -> jax.Array:
    """Faithful two-stage oracle for the fused aggregation kernel:
    relay mix (Eq. (3)) then the blind PS sum (Alg. 2 line 5), fp32."""
    n = updates.shape[0]
    m = A.astype(jnp.float32) * tau_dd.astype(jnp.float32).T
    tilde = m @ updates.astype(jnp.float32)
    return (tau_up.astype(jnp.float32) @ tilde) / n


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True) -> jax.Array:
    """q (BH, T, D), k/v (BH, S, D) — dense softmax attention in fp32."""
    BH, T, D = q.shape
    S = k.shape[1]
    s = jnp.einsum("btd,bsd->bts", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (D ** 0.5)
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", w, v.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(q, k, v, log_decay):
    """Sequential SSD recurrence oracle: q/k (BH,T,Dk), v (BH,T,Dv)."""
    import numpy as np

    BH, T, Dk = q.shape
    Dv = v.shape[-1]
    qf, kf, vf = (np.asarray(x, np.float32) for x in (q, k, v))
    a = np.exp(np.asarray(log_decay, np.float32))
    S = np.zeros((BH, Dk, Dv), np.float32)
    out = np.zeros((BH, T, Dv), np.float32)
    for t in range(T):
        S = a[:, t, None, None] * S + np.einsum("bk,bv->bkv", kf[:, t], vf[:, t])
        out[:, t] = np.einsum("bk,bkv->bv", qf[:, t], S)
    return jnp.asarray(out, q.dtype)


def ssd_intra_ref(scores, c, v):
    """The SSD's intra-chunk term, ``y_t = sum_{s<=t} scores[t, s]
    exp(c_t - c_s) v_s`` within each chunk: scores (B, nc, H or 1, Q, Q),
    c (B, nc, H, Q) inclusive cumulative log decays, v (B, nc, Q, H, P).
    Every exponent is non-positive and the mask is exp(-inf) = 0."""
    q = c.shape[-1]
    tri = jnp.tril(jnp.ones((q, q), bool))
    decay = c[..., :, None] - c[..., None, :]  # [t, s] = c_t - c_s
    w = jnp.exp(jnp.where(tri, jnp.minimum(decay, 0.0), -jnp.inf))
    return jnp.einsum("bnhqs,bnshd->bnqhd", scores * w, v)
