"""Linear-recurrence sequence mixers: chunked SSD (Mamba2-style) and
per-channel gated linear attention (RWKV6-style), plus single-token decode.

TPU adaptation (see DESIGN.md §4): GPU Mamba/RWKV kernels are sequential
selective scans with fused shared-memory tiles.  On TPU the idiomatic
formulation is *chunkwise parallel*: the sequence is split into chunks of
``Q`` tokens; within a chunk the recurrence is evaluated as masked
matmuls (MXU work), and a tiny ``lax.scan`` carries the recurrent state
across chunks.  All exponentials are arranged as differences of cumulative
log-decays with non-positive exponents, so the math is overflow-free by
construction (no GLA-style secondary rescaling needed).

Conventions: q/k: (B, T, H, Dk), v: (B, T, H, Dv).
  * SSD  (scalar decay / head):  S_t = a_t S_{t-1} + k_t v_t^T,  y_t = q_t S_t
  * GLA  (per-channel decay):    S_t = diag(w_t) S_{t-1} + k_t v_t^T,
                                 y_t = q_t (S_{t-1} + diag(u) k_t v_t^T)
    (RWKV6 form: the current token enters through the bonus ``u``.)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

Array = jax.Array


def _chunk(x: Array, q: int) -> Array:
    """(B, T, ...) -> (B, T//q, q, ...)."""
    b, t = x.shape[:2]
    assert t % q == 0, f"seq len {t} not divisible by chunk {q}"
    return x.reshape(b, t // q, q, *x.shape[2:])


# ---------------------------------------------------------------------------
# SSD: scalar per-head decay (Mamba2-style), chunked
# ---------------------------------------------------------------------------


def ssd_chunked(
    q: Array, k: Array, v: Array, loga: Array, state: Optional[Array] = None, chunk: int = 64
) -> Tuple[Array, Array]:
    """loga: (B, T, H) non-positive log decays.  Returns (y, final_state);
    state: (B, H, Dk, Dv)."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    f32 = jnp.float32
    qc = _chunk(q, chunk).astype(f32)
    kc = _chunk(k, chunk).astype(f32)
    vc = _chunk(v, chunk).astype(f32)
    lc = _chunk(loga, chunk).astype(f32)  # (B, N, Q, H)
    c = jnp.cumsum(lc, axis=2)  # inclusive cumulative log decay

    # intra-chunk: y_t += sum_{s<=t} exp(c_t - c_s) (q_t . k_s) v_s
    scores = jnp.einsum("bnqhd,bnshd->bnhqs", qc, kc)
    y_intra = ref.ssd_intra_ref(scores, c.transpose(0, 1, 3, 2), vc)

    # chunk summaries
    clast = c[:, :, -1, :]  # (B, N, H)
    # state contribution of each chunk: sum_s exp(c_last - c_s) k_s v_s^T
    kdec = kc * jnp.exp(clast[:, :, None, :] - c)[..., None]
    chunk_states = jnp.einsum("bnshd,bnshe->bnhde", kdec, vc)  # (B,N,H,Dk,Dv)

    if state is None:
        state = jnp.zeros((B, H, Dk, Dv), f32)

    def step(S, inp):
        cs, cl, qdec_y = inp
        # y_inter for this chunk: exp(c_t) q_t . S_carry
        y_in = jnp.einsum("bqhd,bhde->bqhe", qdec_y, S)
        S_new = jnp.exp(cl)[..., None, None] * S + cs
        return S_new, y_in

    qdec = qc * jnp.exp(c)[..., None]  # (B,N,Q,H,Dk)
    # scan over chunks (leading axis N)
    xs = (
        chunk_states.transpose(1, 0, 2, 3, 4),
        clast.transpose(1, 0, 2),
        qdec.transpose(1, 0, 2, 3, 4),
    )
    state, y_inter = jax.lax.scan(step, state.astype(f32), xs)
    y_inter = y_inter.transpose(1, 0, 2, 3, 4)  # (B,N,Q,H,Dv)

    y = (y_intra + y_inter).reshape(B, T, H, Dv).astype(q.dtype)
    return y, state


def ssd_one_group(
    C: Array, B: Array, x: Array, loga: Array, state: Optional[Array] = None, chunk: int = 64
) -> Tuple[Array, Array]:
    """``ssd_chunked`` for one group: C (the queries) and B (the keys),
    (B, T, N), are shared by every head and never broadcast to them.
    x: (B, T, H, P); loga: (B, T, H).  Returns (y (B, T, H, P), final
    state (B, H, N, P)).

    The intra-chunk term is ``ops.ssd_intra`` (a fused kernel on a TPU).
    Between chunks the decay is applied on the x side:
    ``chunk_state_h = B^T (exp(c_last - c)_h ⊙ x_h)`` and
    ``y_inter_h = exp(c)_h ⊙ (C S_h)``."""
    Bt, T, H, P = x.shape
    f32 = jnp.float32
    c = jnp.cumsum(_chunk(loga, chunk).astype(f32), axis=2)  # (B, nc, Q, H)
    y_intra = ops.ssd_intra(
        C.astype(f32), B.astype(f32), x.astype(f32), c.reshape(Bt, T, H), chunk=chunk
    )
    Cc = _chunk(C, chunk).astype(f32)  # (B, nc, Q, N)
    Bc = _chunk(B, chunk).astype(f32)
    xc = _chunk(x, chunk).astype(f32)  # (B, nc, Q, H, P)
    clast = c[:, :, -1, :]  # (B, nc, H)
    # states are carried transposed, (B, H, P, N): N is the minor, lane-dense
    # axis, and each product below is one matmul per chunk, (H P, Q) x (Q, N)
    # or (Q, N) x (N, H P)
    xdec = xc * jnp.exp(clast[:, :, None, :] - c)[..., None]
    chunk_states = jnp.einsum("bnsd,bnshp->nbhpd", Bc, xdec)  # (nc, B, H, P, N)

    if state is None:
        state = jnp.zeros((Bt, H, C.shape[-1], P), f32)

    def step(S, inp):
        cs, cl = inp
        return jnp.exp(cl)[..., None, None] * S + cs, S

    # scan over chunks (leading axis nc); S_in is the state entering each
    S, S_in = jax.lax.scan(
        step, state.astype(f32).transpose(0, 1, 3, 2), (chunk_states, clast.transpose(1, 0, 2))
    )
    # token-minor (B, nc, H, P, Q), as the intra-chunk term leaves the kernel
    y_inter = jnp.einsum("nbhpd,bnqd->bnhpq", S_in, Cc)
    y_inter = y_inter * jnp.exp(c).transpose(0, 1, 3, 2)[..., None, :]
    y = y_intra + y_inter.transpose(0, 1, 4, 2, 3).reshape(Bt, T, H, P)
    state = S.transpose(0, 1, 3, 2)
    return y.astype(x.dtype), state


def ssd_step(q: Array, k: Array, v: Array, loga: Array, state: Array) -> Tuple[Array, Array]:
    """Single-token decode.  q/k (B,H,Dk), v (B,H,Dv), loga (B,H)."""
    f32 = jnp.float32
    a = jnp.exp(loga.astype(f32))[..., None, None]
    S = a * state + jnp.einsum("bhd,bhe->bhde", k.astype(f32), v.astype(f32))
    y = jnp.einsum("bhd,bhde->bhe", q.astype(f32), S)
    return y.astype(q.dtype), S


# ---------------------------------------------------------------------------
# GLA: per-channel decay with bonus (RWKV6-style), chunked
# ---------------------------------------------------------------------------


def gla_chunked(
    r: Array,
    k: Array,
    v: Array,
    logw: Array,
    u: Array,
    state: Optional[Array] = None,
    chunk: int = 32,
) -> Tuple[Array, Array]:
    """RWKV6 wkv with per-channel data-dependent decay.

    r/k/logw: (B, T, H, Dk); v: (B, T, H, Dv); u: (H, Dk) bonus.
    Returns (y (B,T,H,Dv), final state (B,H,Dk,Dv)).
    """
    B, T, H, Dk = r.shape
    Dv = v.shape[-1]
    f32 = jnp.float32
    rc = _chunk(r, chunk).astype(f32)
    kc = _chunk(k, chunk).astype(f32)
    vc = _chunk(v, chunk).astype(f32)
    lw = _chunk(logw, chunk).astype(f32)  # (B,N,Q,H,Dk), <= 0
    c = jnp.cumsum(lw, axis=2)  # inclusive
    cprev = c - lw  # exclusive: decay accumulated before token t

    # intra-chunk, strictly causal: W[t,s] = sum_d r_td k_sd exp(cprev_t - c_s)_d
    # exponent cprev_t - c_s <= 0 for s <= t-1; mask s >= t.
    rt = rc.transpose(0, 1, 3, 2, 4)  # (B,N,H,Q,Dk)
    kt = kc.transpose(0, 1, 3, 2, 4)
    ct = c.transpose(0, 1, 3, 2, 4)
    cpt = cprev.transpose(0, 1, 3, 2, 4)
    dec = jnp.exp(
        jnp.minimum(cpt[..., :, None, :] - ct[..., None, :, :], 0.0)
    )  # (B,N,H,Q,Q,Dk)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    scores = jnp.einsum("bnhtd,bnhsd,bnhtsd->bnhts", rt, kt, dec)
    scores = jnp.where(tri, scores, 0.0)
    vt = vc.transpose(0, 1, 3, 2, 4)  # (B,N,H,Q,Dv)
    y_intra = jnp.einsum("bnhts,bnhse->bnhte", scores, vt)

    # bonus (current token): y_t += (r_t . (u * k_t)) v_t
    bonus = jnp.einsum("bnhtd,hd,bnhtd->bnht", rt, u.astype(f32), kt)
    y_intra = y_intra + bonus[..., None] * vt

    # inter-chunk
    clast = c[:, :, -1]  # (B,N,H,Dk)
    kdec = kc * jnp.exp(jnp.minimum(clast[:, :, None] - c, 0.0))
    chunk_states = jnp.einsum("bnshd,bnshe->bnhde", kdec, vc)
    rdec = rc * jnp.exp(cprev)  # exp(cprev) <= 1

    if state is None:
        state = jnp.zeros((B, H, Dk, Dv), f32)

    def step(S, inp):
        cs, cl, rd = inp
        y_in = jnp.einsum("bqhd,bhde->bqhe", rd, S)
        S_new = jnp.exp(cl)[..., None] * S + cs
        return S_new, y_in

    xs = (
        chunk_states.transpose(1, 0, 2, 3, 4),
        clast.transpose(1, 0, 2, 3),
        rdec.transpose(1, 0, 2, 3, 4),
    )
    state, y_inter = jax.lax.scan(step, state.astype(f32), xs)
    y_inter = y_inter.transpose(1, 0, 2, 3, 4).transpose(0, 1, 3, 2, 4)  # (B,N,H,Q,Dv)

    y = (y_intra + y_inter).transpose(0, 1, 3, 2, 4).reshape(B, T, H, Dv)
    return y.astype(r.dtype), state


def gla_step(
    r: Array, k: Array, v: Array, logw: Array, u: Array, state: Array
) -> Tuple[Array, Array]:
    """Single-token RWKV6 decode.  r/k/logw (B,H,Dk), v (B,H,Dv), u (H,Dk)."""
    f32 = jnp.float32
    rf, kf, vf = r.astype(f32), k.astype(f32), v.astype(f32)
    kv = jnp.einsum("bhd,bhe->bhde", kf, vf)
    y = jnp.einsum("bhd,bhde->bhe", rf, state + u.astype(f32)[None, :, :, None] * kv)
    S = jnp.exp(logw.astype(f32))[..., None] * state + kv
    return y.astype(r.dtype), S


# ---------------------------------------------------------------------------
# Reference (sequential) implementations — oracles for tests
# ---------------------------------------------------------------------------


def ssd_reference(q, k, v, loga, state=None):
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    S = jnp.zeros((B, H, Dk, Dv), jnp.float32) if state is None else state.astype(jnp.float32)
    ys = []
    for t in range(T):
        y, S = ssd_step(q[:, t], k[:, t], v[:, t], loga[:, t], S)
        ys.append(y)
    return jnp.stack(ys, axis=1), S


def gla_reference(r, k, v, logw, u, state=None):
    B, T, H, Dk = r.shape
    Dv = v.shape[-1]
    S = jnp.zeros((B, H, Dk, Dv), jnp.float32) if state is None else state.astype(jnp.float32)
    ys = []
    for t in range(T):
        y, S = gla_step(r[:, t], k[:, t], v[:, t], logw[:, t], u, S)
        ys.append(y)
    return jnp.stack(ys, axis=1), S
