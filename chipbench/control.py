#!/usr/bin/env python3
"""Readings that set a cell's limits: the sound program, its control and
planted faults, each against the reference, seed by seed, at the cell's
own size on the chip.  The benchmark's runs never call this.

    python3 chipbench/control.py --workload resnet20.paper_chunk8 \\
        --seeds 11 12 13 --control-seeds 11 12 13 --out readings.jsonl

For every seed of ``--seeds``: the sound program (the cell as it runs)
against the reference.  For every seed of ``--control-seeds`` also:

- ``control``: the program's own bfloat16 path (the model's ``dtype``
  switched to ``bfloat16``), the step below the float32 the
  configuration states, against the same reference;
- ``half_batch`` and ``double_client``: faults planted in the reference
  put in the program's place (every local step on half of its minibatch;
  the round's most heavily weighted client counted twice in the
  aggregate);
- ``unchanged``: a round that returns its state unchanged, worked out
  without a run (no momentum, no change, the first loss every round);
- ``alpha_float32``: the program's relay weights held in float32;
- ``copt_half``: relay weights from half of the traffic's COPT-alpha
  sweeps (a shorter set-up).

With ``--detail`` each seed also gets ``settled_alpha`` (the sound
program against the reference run with settled relay weights instead of
the traffic's sweeps), ``highest`` and ``faithful`` (the program with
every product at float32, and with its aggregation kernel switched off),
and each compared round's uplinks and aggregate norms.

Each line of ``--out`` gets the numbers of ``chipbench/check.py``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import assemble, check, harness, spec  # noqa: E402
from chipbench.data import Seeds  # noqa: E402

FAULTS = ("half_batch", "double_client")


def readings(cell: spec.Cell, seed: int, with_control: bool,
             detail: bool = False) -> dict:
    import jax

    k = int(cell.traffic["chunk"])
    seeds = Seeds.from_seed(seed)
    kind = cell.kind()
    job = assemble.build(kind, cell.config, cell.traffic, seeds)
    prog = harness.drive_check_rounds(job, k)
    job.trainer = None
    gc.collect()
    ref = harness.reference_of(job, k)
    out = {"seed": seed, "sound": check.compare(prog, ref)}
    if detail:
        out["sound_detail"] = check.detail(prog, ref)
        # the reference with settled relay weights in place of the
        # traffic's sweeps: how far the weights alone move the numbers
        out["settled_alpha"] = check.compare(
            prog, harness.reference_of(job, k, A=ref["A_settled"]))
        # a second witness: the same program with every product at
        # float32 (Precision.HIGHEST)
        with jax.default_matmul_precision("highest"):
            hi = assemble.build(kind, cell.config, cell.traffic, seeds)
            prog_hi = harness.drive_check_rounds(hi, k)
        hi.trainer = None
        out["highest"] = check.compare(prog_hi, ref)
        out["highest_detail"] = check.detail(prog_hi, ref)
        # a third: the program's aggregation without its kernel
        plain = dict(cell.traffic, strategy_options={"fused": False})
        faithful = assemble.build(kind, cell.config, plain, seeds)
        out["faithful"] = check.compare(harness.drive_check_rounds(faithful, k), ref)
        faithful.trainer = None
        tau_up, _ = job.channel_trace(len(ref["losses"]))
        out["rounds"] = {"tau_up": tau_up.astype(int).tolist(),
                         "delta_norms": [prog["delta_norms"], ref["delta_norms"]]}
    if not with_control:
        return out
    low = assemble.build(kind, cell.config, cell.traffic, seeds, dtype="bfloat16")
    out["control"] = check.compare(harness.drive_check_rounds(low, k), ref)
    low.trainer = None
    gc.collect()
    for fault in FAULTS:
        out[fault] = check.compare(harness.reference_of(job, k, fault), ref)
    rounds = k * math.ceil(3 / k)
    zeros = jax.tree.map(lambda x: 0.0 * x, ref["momentum_first"])
    out["unchanged"] = check.compare(
        {"losses": [ref["losses"][0]] * rounds,
         "delta_norms": [ref["delta_norms"][0]] * rounds,
         "momentum_first": zeros, "params": job.params0,
         "params0": job.params0, "A": job.A}, ref)
    out["alpha_float32"] = check.compare(
        dict(prog, A=job.A.astype(np.float32).astype(np.float64)), ref)
    out["copt_half"] = check.compare(dict(prog, A=_copt(cell, 0.5)), ref)
    return out


def _copt(cell: spec.Cell, share: float) -> np.ndarray:
    """The program's relay weights from ``share`` of the traffic's sweeps."""
    from repro.core import optimize_weights

    sweeps = max(1, round(share * int(cell.traffic["copt_sweeps"])))
    model = assemble.link_model(cell.traffic)
    return optimize_weights(model, sweeps=sweeps, fine_tune_sweeps=sweeps).A


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    ap.add_argument("--detail", action="store_true",
                    help="add per-round losses, the worst leaves, and the "
                         "program at Precision.HIGHEST as a second witness")
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    try:
        harness.check_device(cell.chips, cell.bench_dir)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        for seed in args.seeds:
            r = readings(cell, seed, seed in args.control_seeds, args.detail)
            f.write(json.dumps(r) + "\n")
            f.flush()
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
