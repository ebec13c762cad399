"""Tiny cells in a temporary copy of the benchmark, for the CPU tests.

``tiny_root(tmp)`` copies ``chipbench/`` and ``BENCHMARK.json`` into
``tmp`` and adds, as new files only, a thin ResNet configuration
(``resnet20-thin``: widths 8/16/32, one block a stage), a traffic mix
of the paper's job cut to 2 local steps of batch 4 over 400 images in
chunks of 2, with COPT-alpha run until it settles (so that the job's relay
weights are also the optimum ``alpha_excess`` measures against), and the limits of
``resnet20.paper_chunk8`` for its cell ``tiny.chunk2``.

``toy_root(tmp)`` copies the same and adds, as new files only, a model
kind the benchmark has never seen: ``tests/chipbench/toy/`` (a kind
module, its FLOP counter, a configuration, a traffic mix, limits, and a
metric that reads a span by name), entered in ``BENCHMARK.json`` as the
cell ``toy.chunk2``.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "tiny.chunk2"
COPT_SETTLED = 300  # the program's COPT-alpha stops on its own before this
THIN = {"name": "resnet20-thin", "widths": [8, 16, 32], "blocks_per_stage": 1,
        "n_classes": 10, "image_size": 32, "channels": 3, "groups": 8,
        "dtype": "float32"}


def tiny_root(tmp: pathlib.Path, chunk: int = 2) -> pathlib.Path:
    tmp = pathlib.Path(tmp)
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "chipbench/configs/tiny.json").write_text(json.dumps(
        {"kind": "cnn", "source": "test", "model": THIN, "reduced": []}))
    traffic = json.loads((REPO / "chipbench/traffic/paper_chunk8.json").read_text())
    traffic.update(local_steps=2, batch_size=4, data_size=400, chunk=chunk,
                   copt_sweeps=COPT_SETTLED)
    (tmp / "chipbench/traffic/tiny_chunk2.json").write_text(json.dumps(traffic))
    shutil.copy(REPO / "chipbench/limits/resnet20.paper_chunk8.json",
                tmp / f"chipbench/limits/{CELL}.json")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny_chunk2", "chips": 1,
                               "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


TOY = REPO / "tests/chipbench/toy"
TOY_CELL = "toy.chunk2"
TOY_METRIC = "dispatch_ms"


def toy_root(tmp: pathlib.Path) -> pathlib.Path:
    tmp = pathlib.Path(tmp)
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(TOY, tmp / "chipbench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "chipbench/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TOY_CELL, "config": "toy",
                               "traffic": "toy_chunk2", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": TOY_METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "host loop", "moves": "rounds_per_s",
        "workloads": [TOY_CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cpu_device(chips, bench_dir):
    """Stands in for the harness's look for a chip: the CPU, with the
    v5e's peaks."""
    import jax

    from chipbench import spec

    return (jax.devices()[:chips], {"platform": "cpu", "kind": "cpu", "count": 1},
            spec.load_peaks("TPU v5 lite", bench_dir))
