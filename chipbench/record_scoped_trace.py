#!/usr/bin/env python3
"""Record the profiler trace and scope map that the scope-reduction tests
read.

    python3 chipbench/record_scoped_trace.py \\
        --out chipbench/testdata/trace_scoped.xplane.pb.gz

Assembles the paper's job at a thin width (ResNet widths 8/16/32, one
block a stage, d = 19,858; one local step of batch 4 over 400 images)
with the fused aggregation kernel, warms up two chunks of 2 rounds, and
traces four rounds through the harness's own traced stretch, which
writes the trace, gzipped, to ``--out``.  The trace holds the trainer's
``fl.*`` host spans, and ``--out`` with ``.scopes.json`` for
``.xplane.pb.gz`` holds ``FLTrainer.op_scopes`` for the operations the
trace executed.  (``trace_small.xplane.pb.gz`` is the same job, traced
before the program named its work.)  Needs the chip.
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


def scope_map_path(trace_path) -> pathlib.Path:
    """``<name>.xplane.pb.gz`` -> ``<name>.scopes.json``."""
    p = pathlib.Path(trace_path)
    return p.with_name(p.name.split(".xplane")[0] + ".scopes.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import assemble, harness, spec
    from chipbench.data import Seeds
    from chipbench.kinds import cnn

    try:
        harness.check_device(1, spec.BENCH_DIR)
    except harness.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    traffic = dict(spec.load_json(spec.BENCH_DIR / "traffic/paper_chunk8.json"),
                   local_steps=1, batch_size=4, data_size=400, chunk=CHUNK)
    job = assemble.build(cnn, {"model": THIN}, traffic, Seeds.from_seed(SEED))
    job.trainer.run(2 * CHUNK, chunk=CHUNK)
    op_map = job.trainer.op_scopes(CHUNK)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    reduced = harness._traced_stretch(job.trainer, 2 * CHUNK, CHUNK, op_map, keep=out)
    scope_map_path(out).write_text(json.dumps(
        {op: op_map[op] for op in sorted(reduced["ops"]) if op in op_map},
        indent=0) + "\n")
    print(json.dumps({k: reduced[k] for k in ("window_s", "busy_s", "scopes",
                                              "idle_by_span")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
