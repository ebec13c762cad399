#!/usr/bin/env python3
"""Record the small profiler trace that the trace-reduction tests read.

    python3 chipbench/record_trace.py --out chipbench/testdata/trace_small.xplane.pb.gz

Assembles the paper's job at a thin width (ResNet widths 8/16/32, one
block a stage, d = 19,858; one local step of batch 4 over 400 images)
with the fused aggregation kernel, warms up two chunks of 2 rounds, and
traces four rounds through the harness's own traced stretch, which
writes the trace, gzipped, to ``--out``.  Needs the chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

THIN = {"name": "resnet20-thin", "widths": [8, 16, 32], "blocks_per_stage": 1,
        "n_classes": 10, "image_size": 32, "channels": 3, "groups": 8,
        "dtype": "float32"}
CHUNK = 2
SEED = 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import assemble, harness, spec
    from chipbench.data import Seeds

    try:
        harness.check_device(1, spec.BENCH_DIR)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    traffic = dict(spec.load_json(spec.BENCH_DIR / "traffic/paper_chunk8.json"),
                   local_steps=1, batch_size=4, data_size=400, chunk=CHUNK)
    job = assemble.build({"model": THIN}, traffic, Seeds.from_seed(SEED))
    job.trainer.run(2 * CHUNK, chunk=CHUNK)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    reduced = harness._traced_stretch(job.trainer, 2 * CHUNK, CHUNK, keep=out)
    print(json.dumps({k: reduced[k] for k in ("window_s", "busy_s", "top_ops",
                                              "idle_by_label")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
