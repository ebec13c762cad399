"""A configuration, a traffic mix, limits and a per-layer metric added as
new files in a copy of the benchmark are found by their names, and no
file the benchmark already has needs an edit; nor does a configuration
of a model kind the benchmark has never seen, which then runs through
the harness on the CPU and reads ``correct``."""

import hashlib
import json

import pytest

from chipbench_tiny import (CELL, REPO, TOY, TOY_CELL, TOY_METRIC, cpu_device,
                            tiny_root, toy_root)

from chipbench import harness, scopes, spec, trace
from chipbench.record_scoped_trace import scope_map_path

SCOPED = REPO / "chipbench/testdata/trace_scoped.xplane.pb.gz"

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


def _recorded_stretch(trainer, rounds, k, op_scopes, keep=None):
    """Stands in for the traced stretch, which needs the chip: runs the
    rounds, and returns the reduction of a trace recorded on the chip."""
    trainer.run(rounds, chunk=k)
    op_map = json.loads(scope_map_path(SCOPED).read_text())
    return dict(scopes.reduce(trace.load(SCOPED), op_map), rounds_per_s=1.0)


@pytest.mark.parametrize("traced", [0, 1])
def test_a_new_kind_enters_as_new_files_only(tmp_path, monkeypatch, capsys, traced):
    before = _digests(REPO)
    root = toy_root(tmp_path)
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "chipbench/" + p.relative_to(TOY).as_posix()
        for p in TOY.rglob("*") if p.is_file() and "__pycache__" not in p.parts}
    assert {"chipbench/kinds/toy.py", "chipbench/flops/toy.py",
            f"chipbench/metrics/{TOY_METRIC}.py"} <= set(after) - set(before)

    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(harness, "_traced_stretch", _recorded_stretch)
    rc = harness.main(["--workload", TOY_CELL, "--seed", "2147483659",
                       "--seconds", "0.3", "--trace", str(traced)],
                      root=root, device_check=cpu_device)
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["window_compiles"] == 0
    cell = spec.load_cell(root, TOY_CELL)
    if not traced:
        # the CPU reports no peak memory
        assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
        return
    assert set(result["metrics"]) == {m["name"] for m in cell.per_layer}
    assert result["metrics"][TOY_METRIC]["value"] > 0
    assert result["metrics"][TOY_METRIC]["value"] == pytest.approx(
        result["layers"]["span_ms"]["fl.dispatch"])
    assert result["layers"]["counters"]["h2d_bytes"] > 0
