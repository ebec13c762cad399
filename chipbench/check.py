"""The numbers that decide ``correct``: the timed path against the
reference, each beside its limit.

- ``loss``: over the first three rounds, the largest gap between the
  program's mean client loss and the reference's, over the reference's.
  (Later rounds amplify rounding chaotically; see PERF.md.)
- ``delta``: the same for the norm of each round's aggregate, the
  pseudo-gradient the server optimizer receives.
- ``grad``: the server momentum after the first block, i.e. the
  pseudo-gradients the server optimizer received (after one round it is
  the first one itself).  Per leaf, the gap between the program's norm
  and the reference's, over the larger of that leaf's reference norm
  and the median leaf's; the worst leaf counts.
- ``grad_median``: the median leaf's gap of the same measure.
- ``change`` and ``change_median``: the same two for the parameters'
  change over the compared rounds.
- ``alpha_bias``: the program's relay weights against the unbiasedness
  condition of the traffic's links, ``max_j |E[c_j] - 1|``.
- ``alpha_gap``: the largest gap between an entry of the program's relay
  weights and the reference's (COPT-alpha for the traffic's sweeps), over
  the reference's largest entry.
- ``alpha_excess``: the variance ``S`` of the program's weights over
  that of the benchmark's settled COPT-alpha solve, less 1.

Leaves whose reference momentum norm is under a thousandth of the median
leaf's are left out of the leaf measures: such a leaf moves by round-off
alone.  A number that is not finite reads as infinite.  A cell's limits
file names the numbers it compares; the others are printed only.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from chipbench import alpha

NUMBERS = ("loss", "delta", "grad", "grad_median", "change", "change_median",
           "alpha_bias", "alpha_gap", "alpha_excess")
LOSS_ROUNDS = 3
QUIET_LEAF = 1e-3


def _norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def leaf_gaps(prog_tree, ref_tree) -> np.ndarray:
    p, r = _norms(prog_tree), _norms(ref_tree)
    return np.abs(p - r) / np.maximum(r, np.median(r))


def _finite(x: float) -> float:
    return x if math.isfinite(x) else math.inf


def _delta(a, b):
    return jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b)


def _first_rounds_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Worst relative gap over the first rounds; a round in which both
    read exactly 0 (no update reached the server) has no gap."""
    p = np.asarray(prog[:LOSS_ROUNDS], np.float64)
    r = np.asarray(ref[:LOSS_ROUNDS], np.float64)
    gap = np.abs(p - r)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(gap == 0, 0.0, gap / np.abs(r))
    return np.max(rel)


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` each hold ``losses`` and ``delta_norms`` (per
    compared round), ``momentum_first``, ``params``, ``params0``, where
    they began, and the relay weights ``A``; ``ref`` also the settled
    weights ``A_settled`` and the ``links`` ``(p, P, E)``."""
    m_ref = _norms(ref["momentum_first"])
    keep = m_ref >= QUIET_LEAF * np.median(m_ref)
    grad = leaf_gaps(prog["momentum_first"], ref["momentum_first"])[keep]
    change = leaf_gaps(_delta(prog["params"], prog["params0"]),
                       _delta(ref["params"], ref["params0"]))[keep]
    p, P, E = ref["links"]
    s_ref = alpha.variance(p, P, E, ref["A_settled"])
    A_prog, A_ref = (np.asarray(x, np.float64) for x in (prog["A"], ref["A"]))
    return {k: _finite(float(v)) for k, v in {
        "loss": _first_rounds_gap(prog["losses"], ref["losses"]),
        "delta": _first_rounds_gap(prog["delta_norms"], ref["delta_norms"]),
        "grad": np.max(grad), "grad_median": np.median(grad),
        "change": np.max(change), "change_median": np.median(change),
        "alpha_bias": alpha.unbiasedness_gap(p, P, A_prog),
        "alpha_gap": np.max(np.abs(A_prog - A_ref)) / np.max(np.abs(A_ref)),
        "alpha_excess": alpha.variance(p, P, E, A_prog) / s_ref - 1}.items()}


def detail(prog: dict, ref: dict, top: int = 6) -> dict:
    """Per-round losses and the worst leaves of each leaf measure, with
    their paths, for a look at where a reading comes from."""
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(ref["momentum_first"])[0]]
    out = {"losses": [[float(a), float(b)] for a, b in zip(prog["losses"], ref["losses"])]}
    for name, p_tree, r_tree in (
            ("grad", prog["momentum_first"], ref["momentum_first"]),
            ("change", _delta(prog["params"], prog["params0"]),
             _delta(ref["params"], ref["params0"]))):
        gaps = leaf_gaps(p_tree, r_tree)
        out[name] = [[paths[i], float(gaps[i])] for i in np.argsort(-gaps)[:top]]
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, Optional[float]]) -> bool:
    """Correct when every number that has a limit is within it."""
    return all(numbers[k] <= limits[k] for k in NUMBERS
               if limits.get(k) is not None)


def report(numbers: Dict[str, float], limits: Dict[str, Optional[float]]) -> Dict[str, dict]:
    return {k: {"value": numbers[k], "limit": limits.get(k)} for k in NUMBERS}


def lines(numbers: Dict[str, float], limits: Dict[str, Optional[float]]) -> Sequence[str]:
    out = []
    for k in NUMBERS:
        lim = limits.get(k)
        verdict = ("not compared" if lim is None
                   else "ok" if numbers[k] <= lim else "FAIL")
        out.append(f"check {k}: {numbers[k]!r} limit {lim!r} {verdict}")
    return out
