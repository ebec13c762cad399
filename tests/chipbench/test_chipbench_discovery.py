"""A configuration, a traffic mix, limits and a per-layer metric added as
new files in a copy of the benchmark are found by their names, and no
file the benchmark already has needs an edit."""

import hashlib
import json

from chipbench_tiny import CELL, REPO, tiny_root

from chipbench import spec

METRIC = '''"""Rounds of the window, halved: a metric added by a later change."""


def read(record):
    return record["window"]["rounds"] / 2
'''


def _digests(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    before = _digests(REPO)
    root = tiny_root(tmp_path)
    (root / "chipbench/metrics/half_window.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "half_window", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "host loop", "moves": "rounds_per_s",
        "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, CELL)
    assert cell.config["model"]["name"] == "resnet20-thin"
    assert cell.traffic["local_steps"] == 2 and cell.traffic["chunk"] == 2
    assert "limits" in cell.limits
    assert [m["name"] for m in cell.per_layer][-1] == "half_window"
    assert cell.reader("half_window")({"window": {"rounds": 8}}) == 4
    assert cell.flops().param_count(cell.config["model"]) == 19_858
    # the existing cells do not see a metric scoped to the new one
    other = spec.load_cell(root, "resnet20.paper_chunk8")
    assert "half_window" not in [m["name"] for m in other.per_layer]
    # every file that was there is unchanged; only new files were added
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "chipbench/configs/tiny.json", "chipbench/traffic/tiny_chunk2.json",
        f"chipbench/limits/{CELL}.json", "chipbench/metrics/half_window.py"}
