"""The one-group SSD (``ssm.ssd_one_group``): B and C shared by every
head, the intra-chunk term fused in ``kernels/ssd_intra.py`` on a TPU and
computed by its jnp twin elsewhere.

Oracles: ``ssm.ssd_chunked`` fed B and C broadcast to every head, and
the sequential recurrence ``ssm.ssd_reference``.  Forward outputs, the
final state and the cotangents of C, B, x, the log decays and the
initial state agree to 1e-5 of each array's largest entry (float32 on the
CPU; the sums run in other orders).  The kernel runs in interpret mode.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.ssd_intra import ssd_intra_pallas
from repro.models import ssm

TOL = 1e-5
# (B, T, H, P, N, chunk)
SHAPES = [(2, 64, 4, 8, 16, 16), (1, 96, 3, 16, 8, 32), (2, 128, 2, 32, 16, 64)]


def _inputs(shape, seed=0):
    Bt, T, H, P, N, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    C = jax.random.normal(ks[0], (Bt, T, N))
    B = jax.random.normal(ks[1], (Bt, T, N))
    x = jax.random.normal(ks[2], (Bt, T, H, P))
    loga = -jax.nn.softplus(jax.random.normal(ks[3], (Bt, T, H)))
    s0 = jax.random.normal(ks[4], (Bt, H, N, P))
    dy = jax.random.normal(ks[5], (Bt, T, H, P))
    ds = jax.random.normal(ks[6], (Bt, H, N, P))
    return (C, B, x, loga, s0), (dy, ds)


def _broadcast(C, B, x, loga, s0, chunk):
    shape = x.shape[:3] + C.shape[-1:]
    q, k = jnp.broadcast_to(C[:, :, None], shape), jnp.broadcast_to(B[:, :, None], shape)
    return ssm.ssd_chunked(q, k, x, loga, state=s0, chunk=chunk)


def _recurrence(C, B, x, loga, s0, chunk):
    shape = x.shape[:3] + C.shape[-1:]
    q, k = jnp.broadcast_to(C[:, :, None], shape), jnp.broadcast_to(B[:, :, None], shape)
    return ssm.ssd_reference(q, k, x, loga, state=s0)


def _one_group(C, B, x, loga, s0, chunk):
    return ssm.ssd_one_group(C, B, x, loga, state=s0, chunk=chunk)


def _vjp(fn, args, cts, with_state, chunk):
    """Outputs and the cotangents of (C, B, x, loga[, s0]); without an
    initial state the state starts at 0 and takes no cotangent."""
    if with_state:
        f = lambda C, B, x, loga, s0: fn(C, B, x, loga, s0, chunk)
        primals = args
    else:
        f = lambda C, B, x, loga: fn(C, B, x, loga, None, chunk)
        primals = args[:4]
    out, pull = jax.vjp(jax.jit(f), *primals)
    return out, pull(cts)


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _assert_close(got, want):
    (y, s), grads = got
    (y_w, s_w), grads_w = want
    assert _gap(y, y_w) <= TOL
    assert _gap(s, s_w) <= TOL
    for name, g, w in zip(("C", "B", "x", "loga", "state"), grads, grads_w):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _gap(g, w) <= TOL, name


@pytest.fixture
def kernel_intra(monkeypatch):
    """Route ``ssd_one_group``'s intra-chunk term through the Pallas
    kernel (interpret mode), as on a TPU."""
    monkeypatch.setattr(
        ops, "ssd_intra",
        lambda C, B, x, c, *, chunk: ssd_intra_pallas(C, B, x, c, chunk, True))


@pytest.mark.parametrize("with_state", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_matches_the_oracles(shape, with_state, kernel_intra):
    args, cts = _inputs(shape)
    chunk = shape[-1]
    got = _vjp(_one_group, args, cts, with_state, chunk)
    _assert_close(got, _vjp(_broadcast, args, cts, with_state, chunk))
    _assert_close(got, _vjp(_recurrence, args, cts, with_state, chunk))


@pytest.mark.parametrize("shape", SHAPES)
def test_twin_matches_ssd_chunked(shape):
    """The jnp twin (the CPU's path) equals ssd_chunked on broadcast B and
    C, forward and gradients, and the recurrence (the twin and
    ssd_chunked share ``ref.ssd_intra_ref``)."""
    args, cts = _inputs(shape, seed=1)
    chunk = shape[-1]
    got = _vjp(_one_group, args, cts, True, chunk)
    _assert_close(got, _vjp(_broadcast, args, cts, True, chunk))
    _assert_close(got, _vjp(_recurrence, args, cts, True, chunk))


@pytest.mark.parametrize("shape,kernel", [
    ((1, 256, 2, 8, 16, 128), True),    # the chunk a multiple of 128
    ((2, 64, 3, 16, 8, 64), True),      # one chunk, the whole sequence
    ((2, 60, 3, 8, 16, 12), False),     # a chunk that is no multiple of 8
    ((1, 128, 2, 8, 16, 64), False),    # the lane axis: 64 of 128 tokens
    ((1, 64, 2, 12, 8, 64), False),     # a head of 12 rows
    ((1, 128, 12, 8, 8, 128), False),   # blocks of 6 heads' log decays
])
def test_the_kernel_runs_where_its_blocks_tile(shape, kernel, monkeypatch):
    """As on a TPU, the kernel runs where its blocks tile and the twin
    elsewhere, with the same answer either way."""
    calls = []

    def interpreted(*a):
        calls.append(a[4])
        return ssd_intra_pallas(*a[:5], True)

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "ssd_intra_pallas", interpreted)
    args, cts = _inputs(shape, seed=2)
    chunk = shape[-1]
    _assert_close(_vjp(_one_group, args, cts, True, chunk),
                  _vjp(_broadcast, args, cts, True, chunk))
    assert bool(calls) == kernel


def test_kernel_keeps_no_exp_of_minus_infinity(kernel_intra):
    """A decay that zeroes a chunk's past (log decay -1e4) leaves every
    gradient finite: the kernel masks with a select, not exp(-inf)."""
    args, cts = _inputs(SHAPES[0], seed=3)
    C, B, x, loga, s0 = args
    loga = loga.at[:, 5].set(-1e4)
    (y, s), grads = _vjp(_one_group, (C, B, x, loga, s0), cts, True, 16)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in (y, s, *grads))
    (y_want, _), _ = _vjp(_broadcast, (C, B, x, loga, s0), cts, True, 16)
    assert _gap(y, y_want) <= TOL
