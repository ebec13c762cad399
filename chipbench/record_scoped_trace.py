#!/usr/bin/env python3
"""Record the profiler trace and scope map that the scope-reduction tests
read.

    python3 chipbench/record_scoped_trace.py \\
        --out chipbench/testdata/trace_scoped.xplane.pb.gz

The thin job of ``record_trace.py`` (same width, data, seed and chunks),
traced through the harness's own traced stretch, from a program that
names its work: the trace holds the trainer's ``fl.*`` host spans, and
``--out`` with ``.scopes.json`` for ``.xplane.pb.gz`` holds
``FLTrainer.op_scopes`` for the operations the trace executed.  Needs
the chip.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def scope_map_path(trace_path) -> pathlib.Path:
    """``<name>.xplane.pb.gz`` -> ``<name>.scopes.json``."""
    p = pathlib.Path(trace_path)
    return p.with_name(p.name.split(".xplane")[0] + ".scopes.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from chipbench import assemble, harness, scopes, spec, trace
    from chipbench.data import Seeds
    from chipbench.record_trace import CHUNK, SEED, THIN

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
    op_map = job.trainer.op_scopes(CHUNK)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    harness._traced_stretch(job.trainer, 2 * CHUNK, CHUNK, keep=out)
    reduced = scopes.reduce(trace.load(out), op_map)
    scope_map_path(out).write_text(json.dumps(
        {op: op_map[op] for op in sorted(reduced["ops"]) if op in op_map},
        indent=0) + "\n")
    print(json.dumps({k: reduced[k] for k in ("window_s", "busy_s", "scopes",
                                              "idle_by_span")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
