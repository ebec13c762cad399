"""Pallas TPU kernels for the perf-critical hot spots:

* ``relay_mix``       — the paper's relay consensus over flattened updates
                        (bandwidth-bound (n x n) @ (n x d) streaming matmul).
* ``fused_aggregate`` — the full ColRel aggregation (mixing mask + relay
                        mix + tau-weighted blind PS sum) collapsed into one
                        grid pass: the (n, d) stack crosses HBM exactly
                        once and the output shrinks to the (d,) PS delta.
* ``flash_attention`` — causal online-softmax attention for 32k prefill.
* ``ssd_scan``        — chunked SSD recurrence (Mamba2-style scalar decay)
                        with the state carried in VMEM scratch across the
                        sequential chunk grid; forward only.
* ``ssd_intra``       — the one-group SSD's intra-chunk term, forward and
                        backward (``jax.custom_vjp``): C B^T once per
                        chunk, every head's (Q, Q) decay and score matrix
                        in VMEM only.  The Mamba-2 mixers' path on a TPU
                        (``ops.ssd_intra``).

Each kernel ships with a pure-jnp oracle in ``ref.py``; tests sweep
shapes/dtypes in interpret mode and assert_allclose against the oracle.
"""

from . import ops, ref
from .flash_attention import flash_attention_pallas
from .fused_aggregate import fused_aggregate_pallas
from .relay_mix import relay_mix_pallas
from .ssd_intra import ssd_intra_pallas
from .ssd_scan import ssd_scan_pallas

__all__ = [
    "ops",
    "ref",
    "flash_attention_pallas",
    "fused_aggregate_pallas",
    "relay_mix_pallas",
    "ssd_intra_pallas",
    "ssd_scan_pallas",
]
