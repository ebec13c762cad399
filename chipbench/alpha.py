"""ColRel's relay weights, worked out by the benchmark from the link
probabilities alone, in float64 (arXiv:2202.11850 Sec. III-IV).

Client ``i`` sends the PS ``tau_i sum_j alpha_ij tau_ji Delta_j``: its
uplink ``tau_i`` (up with probability ``p_i``) carries the weighted sum
of the updates it heard over the links ``j -> i`` (``tau_ji``, up with
probability ``P[j, i]``, ``P[i, i] = 1``).  So update ``j`` reaches the
PS with the coefficient ``c_j = sum_i tau_i alpha_ij tau_ji``.  Links
are independent, except that the two directions of one D2D pair are up
together with probability ``E[i, j]``.  Here ``M[i, j] = alpha_ij``.

- Unbiasedness: ``E[c_j] = sum_i p_i P[j, i] M[i, j] = 1`` for every j.
- The variance the weights are chosen to minimise is the sum of all
  covariances of the coefficients, ``S = sum_{j,l} Cov(c_j, c_l)``:

      S =   sum_i p_i (1 - p_i) (sum_j P[j, i] M[i, j])^2
          + sum_{i,j} p_i P[j, i] (1 - P[j, i]) M[i, j]^2
          + sum_{j != l} p_j p_l D[j, l] M[l, j] M[j, l],
      D = E - P * P^T.

  ``Sbar`` bounds the last term by ``sum_{j,l} p_j p_l D[j, l] M[l,
  j]^2`` and is convex.

COPT-alpha (Algorithm 3) starts from weights that share each update's
expected weight equally among its relays, minimises ``Sbar`` and then
``S`` from there, one column of ``M`` at a time (the weights every
client gives to one update) with the others held.  Each column is a
separable convex quadratic under one linear equality and ``x >= 0``; it
is solved exactly here by sorting the breakpoints of its multiplier.

``S`` is nearly flat along some directions around its minimum: weights
that differ by 2% in single entries can differ in ``S`` by 2e-6.  So a
job's weights are the ones its configured number of sweeps reaches
(:func:`copt_alpha_job`), and :func:`copt_alpha` (sweeps until the
weights stop moving) is the yardstick for how near the optimum they are.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

MAX_SWEEPS = 20_000
TOL = 1e-14
PHASE_TOL = 1e-10  # a sweep that moves the objective less ends a job's phase


def link_model(links: dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(p, P, E)`` of a traffic's ``links``: ``uplink`` (one
    probability a client), ``d2d`` (every pair of clients) and
    ``reciprocal`` (the two directions of a pair up together, or
    independent)."""
    p = np.asarray(links["uplink"], np.float64)
    n = len(p)
    P = np.full((n, n), float(links["d2d"]))
    np.fill_diagonal(P, 1.0)
    E = P.copy() if links["reciprocal"] else P * P.T
    np.fill_diagonal(E, 1.0)
    return p, P, E


def unbiasedness_gap(p, P, M) -> float:
    """``max_j |E[c_j] - 1|``."""
    return float(np.max(np.abs(np.einsum("i,ji,ij->j", p, P, M) - 1.0)))


def variance(p, P, E, M, relaxed: bool = False) -> float:
    """``S`` (or ``Sbar`` where ``relaxed``) of the weights ``M``."""
    u = np.sum(P.T * M, axis=1)
    D = E - P * P.T
    np.fill_diagonal(D, 0.0)
    total = np.sum(p * (1 - p) * u * u)
    total += np.sum(p[:, None] * P.T * (1 - P.T) * M * M)
    pp = p[:, None] * p[None, :]
    if relaxed:
        total += np.sum(pp * D * M.T * M.T)
    else:
        total += np.sum(pp * D * M.T * M)
    return float(total)


def _water_fill(w, h, g) -> np.ndarray:
    """``argmin sum_i h_i x_i^2 / 2 + g_i x_i`` over ``x >= 0`` with
    ``sum_i w_i x_i = 1``, all ``w_i, h_i > 0``: ``x_i = max(0, (lam w_i
    - g_i) / h_i)``, ``lam`` found between sorted breakpoints."""
    t = g / w
    s = w * w / h
    order = np.argsort(t)
    ts, ss = t[order], s[order]
    acc_s = np.cumsum(ss)
    acc_st = np.cumsum(ss * ts)
    lam = (1.0 + acc_st) / acc_s
    nxt = np.append(ts[1:], np.inf)
    m = int(np.flatnonzero(lam <= nxt)[0])
    return np.maximum(0.0, (lam[m] * w - g) / h)


def _column(p, P, E, M, j: int, relaxed: bool) -> np.ndarray:
    """The best weights ``M[:, j]`` for update ``j``, the rest held."""
    n = len(p)
    pji = P[j, :]
    w = p * pji
    active = w > 0
    if np.any(w[active] >= 1.0):
        raise ValueError("a perfect link to the PS; not needed by these topologies")
    D = E - P * P.T
    rest = np.sum(P.T * M, axis=1) - pji * M[:, j]
    g = 2 * p * (1 - p) * rest * pji
    if relaxed:
        h = 2 * w * (1 - w + p[j] * D[j, :] / np.where(active, pji, 1.0))
    else:
        h = 2 * w * (1 - w)
        cross = 2 * p * p[j] * D[:, j] * M[j, :]
        cross[j] = 0.0
        g = g + cross
    x = np.zeros(n)
    x[active] = _water_fill(w[active], h[active], g[active])
    return x


def _initial(p, P) -> np.ndarray:
    """A feasible start: every reachable relay of update ``j`` carries
    an equal share of its expected weight."""
    W = p[:, None] * P.T          # W[i, j] = p_i P[j, i]
    reach = W > 0
    return np.where(reach, 1.0 / (np.maximum(W, 1e-300) * reach.sum(axis=0)), 0.0)


def _sweeps(p, P, E, M, relaxed: bool) -> np.ndarray:
    for _ in range(MAX_SWEEPS):
        before = M.copy()
        for j in range(len(p)):
            M[:, j] = _column(p, P, E, M, j, relaxed)
        if np.max(np.abs(M - before)) <= TOL * np.max(np.abs(M)):
            return M
    raise RuntimeError("COPT-alpha did not converge")


def _job_phase(p, P, E, M, relaxed: bool, sweeps: int) -> np.ndarray:
    """At most ``sweeps`` sweeps, ending early once a sweep moves the
    phase's objective by no more than ``PHASE_TOL`` of itself."""
    prev = variance(p, P, E, M, relaxed)
    for _ in range(sweeps):
        for j in range(len(p)):
            M[:, j] = _column(p, P, E, M, j, relaxed)
        cur = variance(p, P, E, M, relaxed)
        if abs(prev - cur) <= PHASE_TOL * max(1.0, abs(prev)):
            break
        prev = cur
    return M


def copt_alpha_job(p, P, E, sweeps: int) -> np.ndarray:
    """The weights a job with ``sweeps`` COPT-alpha sweeps a phase runs
    with: ``Sbar`` then ``S``, each for at most ``sweeps`` sweeps."""
    M = _job_phase(p, P, E, _initial(p, P), relaxed=True, sweeps=sweeps)
    return _job_phase(p, P, E, M, relaxed=False, sweeps=sweeps)


def copt_alpha(p, P, E) -> np.ndarray:
    """The weights ``M`` (``M[i, j] = alpha_ij``) that minimise ``Sbar``,
    then ``S`` from there."""
    M = _sweeps(p, P, E, _initial(p, P), relaxed=True)
    return _sweeps(p, P, E, M, relaxed=False)
