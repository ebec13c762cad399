"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs step-by-step in Python/XLA-CPU, validating the exact
TPU tiling logic.  On a real TPU backend the same call sites compile to
Mosaic.  ``_interpret()`` makes that switch automatic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .fused_aggregate import fused_aggregate_pallas, row_stream_pallas
from .fused_dequant import fused_dequant_aggregate_pallas
from .fused_memory import fused_memory_update_pallas, memory_stream_pallas
from .relay_block import (
    block_fused_aggregate_pallas,
    block_relay_mix_pallas,
    block_row_stream_pallas,
)
from .relay_mix import relay_mix_pallas
from .ssd_intra import ssd_intra_pallas
from .ssd_intra import tiles as ssd_intra_tiles
from .ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def relay_mix(mixing: jax.Array, updates: jax.Array, *, block_d: int = 2048) -> jax.Array:
    """ColRel consensus Dx~ = mixing @ updates; (n, d) streams through VMEM."""
    return relay_mix_pallas(mixing, updates, block_d=block_d, interpret=_interpret())


def fused_aggregate(A: jax.Array, tau_up: jax.Array, tau_dd: jax.Array,
                    updates: jax.Array, *, block_d: int = 2048) -> jax.Array:
    """One-pass ColRel PS delta (1/n) tau_up @ ((A * tau_dd^T) @ updates):
    the (n, d) stack crosses HBM once; output is the (d,) fp32 delta."""
    if _interpret():
        # Non-TPU deployable op: the same collapsed contraction in jnp (one
        # pass over the stack, identical order/accumulation to the kernel).
        # This is wired into every training round, so — unlike the oracle
        # ops above — it must not emulate the tile grid in the interpreter;
        # the kernel's tiling is validated in tests at reduced d.
        n = updates.shape[0]
        w = (tau_up.astype(jnp.float32) @
             (A.astype(jnp.float32) * tau_dd.astype(jnp.float32).T)) / n
        return w @ updates.astype(jnp.float32)
    return fused_aggregate_pallas(A, tau_up, tau_dd, updates, block_d=block_d)


def block_relay_mix(Ab: jax.Array, tau_b: jax.Array, updates: jax.Array,
                    *, block_d: int = 2048) -> jax.Array:
    """Blocked consensus Dx~_c = (A_c * tau_c^T) @ Dx_c over (C, m, m)
    cluster blocks; the dense (n, n) mask is never materialized."""
    return block_relay_mix_pallas(Ab, tau_b, updates, block_d=block_d,
                                  interpret=_interpret())


def block_fused_aggregate(Ab: jax.Array, tau_up: jax.Array, tau_b: jax.Array,
                          updates: jax.Array, *,
                          block_d: int = 2048) -> jax.Array:
    """One-pass blocked ColRel PS delta over (C, m, m) cluster blocks:
    (1/n) sum_c tau_c @ ((A_c * tau_c^T) @ Dx_c); output is (d,) fp32."""
    if _interpret():
        # Non-TPU deployable op: the same per-cluster collapse in jnp
        # (identical contraction order to the kernel) — this is the hot
        # path of every clustered training round and the shard benchmark,
        # so it must not emulate the tile grid in the interpreter; the
        # kernel's tiling is validated in tests at reduced d.
        C, m, _ = Ab.shape
        n = C * m
        w = jnp.einsum(
            "ci,cij->cj",
            tau_up.astype(jnp.float32).reshape(C, m),
            Ab.astype(jnp.float32) * jnp.swapaxes(tau_b, 1, 2).astype(jnp.float32),
        ) / n
        return jnp.einsum("cj,cjk->k", w,
                          updates.astype(jnp.float32).reshape(C, m, -1))
    return block_fused_aggregate_pallas(Ab, tau_up, tau_b, updates,
                                        block_d=block_d)


def fused_dequant_aggregate(A: jax.Array, tau_up: jax.Array, tau_dd: jax.Array,
                            q: jax.Array, scale: jax.Array, *,
                            block_d: int = 2048) -> jax.Array:
    """One-pass quantized ColRel PS delta over the int8 affine wire form:
    the per-client dequant scales fold into the collapsed weight row
    ((1/n) tau_up @ (A * tau_dd^T) * scale^T) @ q, so the int8 stack
    crosses HBM once and the f32 stack is never materialized."""
    if _interpret():
        # Non-TPU deployable op: the identical folded contraction in jnp
        # (same collapse order as the kernel); the kernel's tiling is
        # validated in tests at reduced d.
        n = q.shape[0]
        w = (tau_up.astype(jnp.float32) @
             (A.astype(jnp.float32) * tau_dd.astype(jnp.float32).T)) / n
        return (w * scale.reshape(-1)) @ q.astype(jnp.float32)
    return fused_dequant_aggregate_pallas(A, tau_up, tau_dd, q, scale,
                                          block_d=block_d)


def fused_memory_update(A: jax.Array, tau_up: jax.Array, tau_dd: jax.Array,
                        updates: jax.Array, buffer: jax.Array, *,
                        block_d: int = 2048):
    """One-pass memory-strategy round (select-accumulate-update):
    tilde = (A * tau_dd^T) @ updates; contrib = tau*tilde + (1-tau)*buffer;
    returns (delta (d,), contrib (n, d)) with the (n, d) tilde intermediate
    kept in VMEM (never written to HBM) on the kernel path."""
    if _interpret():
        # Non-TPU deployable op: same math and accumulation order as
        # MemoryStrategy.aggregate (the oracle).
        n = updates.shape[0]
        m = A.astype(jnp.float32) * tau_dd.astype(jnp.float32).T
        tilde = m @ updates.astype(jnp.float32)
        t = tau_up.astype(jnp.float32)[:, None]
        contrib = t * tilde + (1.0 - t) * buffer
        delta = jnp.ones((n,), jnp.float32) @ contrib / n
        return delta, contrib
    return fused_memory_update_pallas(A, tau_up, tau_dd, updates, buffer,
                                      block_d=block_d)


# -- segment streaming (DESIGN.md §14) -----------------------------------
#
# At large d the (n, d) stack itself is the memory bottleneck, so the
# collapsed per-round operands (weight row / realized mask) are computed
# once here and each per-leaf (n, d_i) segment streams through its own
# kernel pass — the monolithic stack never materializes.  The interpret
# paths mirror the monolithic interpret expressions exactly: every output
# column is a function of its own input column only, so per-segment
# outputs equal the corresponding columns of the monolithic pass bitwise.


def mixing_mask(A: jax.Array, tau_dd: jax.Array) -> jax.Array:
    """Realized mixing mask ``A * tau_dd^T`` (n, n) f32 — the monolithic
    kernels recompute it in VMEM per tile; the segment-streaming paths
    hoist it to once per round (O(n^2), free next to the stream)."""
    return A.astype(jnp.float32) * tau_dd.astype(jnp.float32).T


def collapsed_weight_row(A: jax.Array, tau_up: jax.Array,
                         tau_dd: jax.Array) -> jax.Array:
    """The ColRel collapse ``(1/n) tau_up @ (A * tau_dd^T)`` as an (n,)
    f32 row — the carried accumulator of the segment-streaming path,
    identical expression (and accumulation) to the ``fused_aggregate``
    interpret path."""
    n = tau_up.shape[0]
    return (tau_up.astype(jnp.float32) @ mixing_mask(A, tau_dd)) / n


def block_collapsed_weight_row(Ab: jax.Array, tau_up: jax.Array,
                               tau_b: jax.Array) -> jax.Array:
    """Per-cluster collapse ``w_c = (1/n) tau_c @ (A_c * tau_c^T)`` as a
    (C, m) f32 tensor — identical einsum to the ``block_fused_aggregate``
    interpret path."""
    C, m, _ = Ab.shape
    n = C * m
    return jnp.einsum(
        "ci,cij->cj",
        tau_up.astype(jnp.float32).reshape(C, m),
        Ab.astype(jnp.float32) * jnp.swapaxes(tau_b, 1, 2).astype(jnp.float32),
    ) / n


def row_stream(w: jax.Array, segment: jax.Array, *,
               block_d: int = 2048) -> jax.Array:
    """One segment's PS-delta columns ``w @ segment`` ((n,) x (n, d_i) ->
    (d_i,) f32); consumes f32/bf16/int8 segments directly."""
    if _interpret():
        # Same contraction as the fused_aggregate interpret path restricted
        # to this segment's columns — bitwise-equal to the monolithic pass.
        return w @ segment.astype(jnp.float32)
    return row_stream_pallas(w, segment, block_d=block_d)


def block_row_stream(w: jax.Array, segment: jax.Array, *,
                     block_d: int = 2048) -> jax.Array:
    """One segment's blocked PS-delta columns
    ``sum_c w_c @ segment_c`` ((C, m) x (n, d_i) -> (d_i,) f32)."""
    if _interpret():
        # Identical einsum form to the block_fused_aggregate interpret path
        # so per-segment outputs match the monolithic pass bitwise.
        C, m = w.shape
        return jnp.einsum("cj,cjk->k", w,
                          segment.astype(jnp.float32).reshape(C, m, -1))
    return block_row_stream_pallas(w, segment, block_d=block_d)


def memory_stream(mix: jax.Array, tau_up: jax.Array, segment: jax.Array,
                  buf_seg: jax.Array, *, block_d: int = 2048):
    """One segment of the memory-strategy recursion against the
    caller-computed realized mask: returns ``(delta_seg (d_i,),
    contrib_seg (n, d_i))`` — the columns ``fused_memory_update`` would
    produce, without the monolithic stack."""
    if _interpret():
        # Same math and accumulation order as the fused_memory_update
        # interpret path (and hence MemoryStrategy.aggregate, the oracle),
        # restricted to this segment's columns.
        n = segment.shape[0]
        tilde = mix @ segment.astype(jnp.float32)
        t = tau_up.astype(jnp.float32)[:, None]
        contrib = t * tilde + (1.0 - t) * buf_seg
        delta = jnp.ones((n,), jnp.float32) @ contrib / n
        return delta, contrib
    return memory_stream_pallas(mix, tau_up, segment, buf_seg,
                                block_d=block_d)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
                    block_q: int = 128, block_kv: int = 128) -> jax.Array:
    """q/k/v (B, T, H, D) -> (B, T, H, D) causal flash attention.

    GQA is handled by the caller (kv heads already broadcast); here H == KV.
    """
    assert causal, "only causal self-attention is kernelized"
    B, T, H, D = q.shape
    KV = k.shape[2]
    if KV != H:  # broadcast grouped kv heads
        G = H // KV
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    bq = min(block_q, T)
    bkv = min(block_kv, T)
    out = flash_attention_pallas(qf, kf, vf, block_q=bq, block_kv=bkv, interpret=_interpret())
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def ssd_scan(q, k, v, log_decay, *, chunk: int = 64):
    """Chunked SSD recurrence (Mamba2 hot loop), (BH, T, D) layout."""
    return ssd_scan_pallas(q, k, v, log_decay, chunk=chunk, interpret=_interpret())


def ssd_intra(C: jax.Array, B: jax.Array, x: jax.Array, c: jax.Array, *,
              chunk: int) -> jax.Array:
    """Intra-chunk term of the one-group SSD, float32 (Bt, T, H, P):
    ``y_t = sum_{s<=t, same chunk} (C_t . B_s) exp(c_t - c_s) x_s`` per
    head, for C, B (Bt, T, N) shared by every head, x (Bt, T, H, P) and
    c (Bt, T, H) the inclusive cumulative log decays restarted at each
    chunk.  On a TPU, whenever the blocks tile (``ssd_intra.tiles``: the
    chunk a multiple of 128 or the whole sequence, the head size and the
    head block a multiple of 8), the fused kernel keeps every (Q, Q) matrix in VMEM,
    forward and backward."""
    if _interpret() or not ssd_intra_tiles(x.shape[1], x.shape[2], x.shape[3], chunk):
        # Non-TPU deployable op, and the fallback for blocks that do not
        # tile: the same shared-group algebra in jnp, with C B^T taken once
        # per chunk for every head.
        bt, t, h, p = x.shape
        nc = t // chunk
        scores = jnp.einsum("bnqd,bnsd->bnqs", C.reshape(bt, nc, chunk, -1),
                            B.reshape(bt, nc, chunk, -1))
        ch = c.reshape(bt, nc, chunk, h).transpose(0, 1, 3, 2)
        y = ref.ssd_intra_ref(scores[:, :, None], ch, x.reshape(bt, nc, chunk, h, p))
        return y.reshape(bt, t, h, p)
    return ssd_intra_pallas(C, B, x, c, chunk)
