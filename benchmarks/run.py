"""Benchmark harness — one benchmark per paper table/figure plus the
kernel micro-benches and the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV (one line per measurement), and
can additionally emit a machine-readable ``BENCH_kernels.json``
(name -> us_per_call) so the perf trajectory is comparable across PRs.

    PYTHONPATH=src python -m benchmarks.run [--only fig2a,theorem1]
    PYTHONPATH=src python -m benchmarks.run --only relay_mix,fused_aggregate --json
    BENCH_ROUNDS=50 PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback


def all_benches():
    from . import (
        async_bench,
        channel_bench,
        ckpt_bench,
        kernels_bench,
        larged_bench,
        paper_figures,
        quant_bench,
        roofline_report,
        scan_bench,
        shard_bench,
        strategy_bench,
        telemetry_bench,
        theory,
    )

    return {
        "fig2a": paper_figures.bench_fig2a,
        "fig2b": paper_figures.bench_fig2b,
        "fig4": paper_figures.bench_fig4_mmwave,
        "theorem1": theory.bench_theorem1,
        "copt_alpha": theory.bench_copt_alpha,
        "relay_mix": kernels_bench.bench_relay_mix,
        "fused_aggregate": kernels_bench.bench_fused_aggregate,
        "flash_attn": kernels_bench.bench_flash_attention,
        "roofline": roofline_report.bench_dryrun_roofline,
        "channel_sampler": channel_bench.bench_channel_sampler,
        "channel_adaptive": channel_bench.bench_channel_adaptive,
        "strategies": strategy_bench.bench_strategy_matrix,
        "quant": quant_bench.bench_quant,
        "scan": scan_bench.bench_scan_engine,
        "shard_bench": shard_bench.bench_shard,
        "telemetry": telemetry_bench.bench_telemetry,
        "ckpt": ckpt_bench.bench_ckpt,
        "async_bench": async_bench.bench_async,
        "larged": larged_bench.bench_larged,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json", default=None,
                    metavar="PATH",
                    help="also write name -> us_per_call as JSON "
                         "(default path: BENCH_kernels.json)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    benches = all_benches()
    names = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    results = {}
    failed = []
    for name in names:
        try:
            for row_name, us, derived in benches[name]():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
                results[row_name] = round(us, 1)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json} ({len(results)} rows)", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
