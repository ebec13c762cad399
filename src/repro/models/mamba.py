"""Mamba block (Mamba2/SSD-style, the TPU-native selective SSM).

Used by the jamba and granite hybrid configs.  Per-layer parameters follow the Mamba2
structure: fused in-projection -> (gate z, conv channels xBC, dt), causal
depthwise conv, scalar-per-head decay ``a_t = exp(-exp(A_log) * dt_t)``,
chunked SSD mixer with one group (``ssm.ssd_one_group``: B and C shared by
every head, never broadcast to them), gated RMS norm, out-projection.

Decode carries two states per layer: the conv window (last ``d_conv - 1``
inputs) and the SSD state (B, H, d_state, head_dim).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.telemetry import spans as names

from . import ssm
from .common import ModelConfig, Params, dense_init, split_keys

Array = jax.Array


def _dims(cfg: ModelConfig):
    d_in = cfg.d_inner
    H = cfg.n_ssm_heads
    P = d_in // H  # head dim (the "value" dim of SSD)
    N = cfg.ssm_d_state  # the "key" dim of SSD
    conv_ch = d_in + 2 * N  # x, B, C all pass through the conv
    return d_in, H, P, N, conv_ch


def init_mamba(cfg: ModelConfig, key) -> Params:
    d = cfg.d_model
    d_in, H, P, N, conv_ch = _dims(cfg)
    ks = split_keys(key, ["in", "conv", "out", "dt", "A"])
    dt_floor = 1e-3
    return {
        "w_in": dense_init(ks["in"], (d, 2 * d_in + 2 * N + H), cfg.jdtype),
        "conv_w": dense_init(ks["conv"], (cfg.ssm_conv, conv_ch), cfg.jdtype, scale=0.5),
        "conv_b": jnp.zeros((conv_ch,), cfg.jdtype),
        "dt_bias": jnp.log(
            jnp.exp(
                jnp.exp(
                    jax.random.uniform(ks["dt"], (H,), jnp.float32, jnp.log(dt_floor), 0.0)
                )
            )
            - 1.0
        ).astype(jnp.float32),
        "A_log": jnp.log(1.0 + jnp.arange(1, H + 1, dtype=jnp.float32) % 16),
        "D": jnp.ones((H,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), cfg.jdtype),
        "w_out": dense_init(ks["out"], (d_in, d), cfg.jdtype),
    }


def _causal_conv(x: Array, w: Array, b: Array) -> Array:
    """Depthwise causal conv: x (B, T, C), w (K, C)."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):  # K == 4: tiny unrolled window sum
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return out + b


def _split(cfg: ModelConfig, h: Array):
    d_in, H, P, N, _ = _dims(cfg)
    z, xBC, dt = jnp.split(h, [d_in, 2 * d_in + 2 * N], axis=-1)
    return z, xBC, dt


def _gated_norm(x: Array, z: Array, scale: Array, eps: float) -> Array:
    x = x * jax.nn.silu(z)
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * scale


def mamba_forward(
    cfg: ModelConfig, p: Params, x: Array, state: Optional[Params] = None
) -> Tuple[Array, Optional[Params]]:
    """x (B, T, d) -> (y (B, T, d), final state or None)."""
    with jax.named_scope(names.MODEL_MAMBA):
        return _mamba_forward(cfg, p, x, state)


def _mamba_forward(cfg, p, x, state):
    B, T, d = x.shape
    d_in, H, P, N, conv_ch = _dims(cfg)
    h = x @ p["w_in"]
    z, xBC_pre, dt = _split(cfg, h)
    xBC = jax.nn.silu(_causal_conv(xBC_pre, p["conv_w"], p["conv_b"]))
    xs, Bc, Cc = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    v = xs.reshape(B, T, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,T,H)
    loga = -jnp.exp(p["A_log"]) * dt
    v_in = v * dt[..., None].astype(v.dtype)
    ssm_state = None if state is None else state["ssm"]
    with jax.named_scope(names.MODEL_SSD):
        y, ssm_out = ssm.ssd_one_group(
            Cc, Bc, v_in, loga, state=ssm_state, chunk=min(cfg.ssm_chunk, T)
        )
    y = y + v * p["D"][None, None, :, None].astype(v.dtype)
    y = y.reshape(B, T, d_in)
    y = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps)
    out = y @ p["w_out"]
    new_state = None
    if state is not None:
        conv_tail = jnp.concatenate([state["conv"], xBC_pre], axis=1)[:, -(cfg.ssm_conv - 1) :]
        new_state = {"ssm": ssm_out, "conv": conv_tail}
    return out, new_state


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=None) -> Params:
    d_in, H, P, N, conv_ch = _dims(cfg)
    dt = dtype or cfg.jdtype
    return {
        "ssm": jnp.zeros((batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dt),
    }


def mamba_step(cfg: ModelConfig, p: Params, x: Array, state: Params) -> Tuple[Array, Params]:
    """Single-token decode.  x (B, 1, d)."""
    B = x.shape[0]
    d_in, H, P, N, conv_ch = _dims(cfg)
    h = x[:, 0] @ p["w_in"]
    z, xBC, dt = _split(cfg, h)
    window = jnp.concatenate([state["conv"], xBC[:, None, :]], axis=1)  # (B, K, C)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xBC_t = jax.nn.silu(conv)
    xs, Bc, Cc = jnp.split(xBC_t, [d_in, d_in + N], axis=-1)
    v = xs.reshape(B, H, P)
    k = jnp.broadcast_to(Bc[:, None, :], (B, H, N))
    q = jnp.broadcast_to(Cc[:, None, :], (B, H, N))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    loga = -jnp.exp(p["A_log"]) * dt
    v_in = v * dt[..., None].astype(v.dtype)
    y, ssm_out = ssm.ssd_step(q, k, v_in, loga, state["ssm"])
    y = y + v * p["D"][None, :, None].astype(v.dtype)
    y = y.reshape(B, d_in)
    y = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps)
    out = (y @ p["w_out"])[:, None, :]
    return out, {"ssm": ssm_out, "conv": window[:, 1:]}
