#!/usr/bin/env python3
"""One run of one cell, split by the names the program puts on its work.

    python3 chipbench/layer_split.py --workload resnet20.paper_chunk8 \\
        --seed 12345 --seconds 30

Builds the cell's trainer as the harness does, warms it up, and times an
untraced window of ``run(R, chunk=K)`` with the trainer's host-span
totals (``trainer.spans``) taken before and after it.  Then it maps the
executed program's instructions to device scopes
(``FLTrainer.op_scopes(K)``), traces a stretch through the harness's own
traced stretch, and reduces the trace by scope and by host span
(:mod:`chipbench.scopes`).  Prints one JSON object: the window's rate
and, per round, each host span's milliseconds; per traced round, each
scope's device milliseconds and the idle milliseconds under each span;
and the per-layer numbers ``local_train_ms``, ``aggregate_ms`` (with
``fl.flatten``), ``server_step_ms``, ``host_stack_ms``, ``h2d_ms``.
Needs the chip; compares no output (``chipbench/run.py`` does).
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

KERNELS = ("fused_aggregate_pallas", "row_stream_pallas")


def window_split(before: dict, after: dict, rounds: int) -> dict:
    """Milliseconds per round of each host span, and counts and counters
    per round, between two ``Spans.snapshot()`` s."""
    def per_round(key, scale=1.0):
        return {k: (v - before[key].get(k, 0)) * scale / rounds
                for k, v in after[key].items() if v != before[key].get(k, 0)}

    return {"span_ms": per_round("seconds", 1e3), "counts": per_round("counts"),
            "counters": per_round("counters")}


def per_layer(split: dict, traced: dict, traced_rounds: int) -> dict:
    """The five per-layer numbers from a window split and a scoped trace."""
    scope_ms = {k: v * 1e3 / traced_rounds for k, v in traced["scopes"].items()}
    span_ms = split["span_ms"]
    return {"local_train_ms": scope_ms.get("fl.local_sgd", 0.0),
            "aggregate_ms": scope_ms.get("fl.aggregate", 0.0) + scope_ms.get("fl.flatten", 0.0),
            "server_step_ms": scope_ms.get("fl.server_step", 0.0),
            "host_stack_ms": span_ms.get("fl.stack_batches", 0.0),
            "h2d_ms": span_ms.get("fl.h2d", 0.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import assemble, harness, scopes, spec, trace
    from chipbench.data import Seeds

    cell = spec.load_cell(ROOT, args.workload)
    try:
        harness.check_device(cell.chips, cell.bench_dir)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    job = assemble.build(cell.config, cell.traffic, Seeds.from_seed(args.seed))
    tr, k = job.trainer, int(cell.traffic["chunk"])
    tr.metrics.sinks.clear()
    tr.run(k * math.ceil(3 / k), chunk=k)
    rounds = harness.window_rounds(tr, k, args.seconds)

    before = tr.spans.snapshot()
    t = time.perf_counter()
    tr.run(rounds, chunk=k)
    jax.block_until_ready(tr.params)
    window_s = time.perf_counter() - t
    split = window_split(before, tr.spans.snapshot(), rounds)

    t = time.perf_counter()
    op_map = tr.op_scopes(k)
    op_scopes_s = time.perf_counter() - t
    traced_rounds = k * max(3, math.ceil(harness.TRACE_SECONDS * rounds / window_s / k))
    with tempfile.TemporaryDirectory() as tmp:
        keep = pathlib.Path(tmp) / "trace.xplane.pb.gz"
        stretch = harness._traced_stretch(tr, traced_rounds, k, keep=keep)
        traced = scopes.reduce(trace.load(keep), op_map)

    busy_ms = traced["busy_s"] * 1e3 / traced_rounds
    record = {"trace": traced, "traced_rounds": traced_rounds,
              "n_clients": job.trainer.rc.n_clients, "d": job.d,
              "peaks": spec.load_peaks(jax.devices()[0].device_kind, cell.bench_dir),
              "window": {"rounds": rounds, "seconds": window_s}}
    out = {
        "workload": args.workload, "seed": args.seed, "chunk": k,
        "rounds_per_s": rounds / window_s, "window_rounds": rounds,
        "traced_rounds": traced_rounds,
        "traced_rounds_per_s": stretch["rounds_per_s"],
        "busy_ms_per_round": busy_ms,
        "window_idle_ms_per_round": window_s * 1e3 / rounds - busy_ms,
        "idle_share": cell.reader("idle_share")(record),
        "agg_kernel_roofline": cell.reader("agg_kernel_roofline")(record),
        "kernel_prefixes_in_program": sorted(
            {op.split(".")[0] for op in op_map if op.split(".")[0] in KERNELS}),
        "op_scopes_s": op_scopes_s,
        **per_layer(split, traced, traced_rounds),
        "scope_ms": {s: v * 1e3 / traced_rounds for s, v in traced["scopes"].items()},
        "idle_by_span_ms": {s: v * 1e3 / traced_rounds
                            for s, v in traced["idle_by_span"].items()},
        "window": split,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
