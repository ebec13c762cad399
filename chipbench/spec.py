"""Finding a cell's files by name: configuration, traffic, limits, metric
readers, and the model kind's module and FLOP counter.  Nothing here
imports JAX or the program."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _load_module(path: pathlib.Path, name: str):
    """The module in ``path``, loaded once a process under ``name`` (a
    file of that name elsewhere, as in another checkout, is loaded
    anew)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod = sys.modules.get(name)
    if mod is not None and pathlib.Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # before it runs, as an import does (dataclasses look there)
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]      # chipbench/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]     # chipbench/traffic/<traffic>.json
    limits: Dict[str, Any]      # chipbench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path
    bench_dir: pathlib.Path

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        """``chipbench/metrics/<metric>.py``'s ``read(record)``."""
        mod = _load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           f"chipbench_metric_{metric}")
        return mod.read

    def kind(self):
        """``chipbench/kinds/<kind>.py`` for the configuration's kind:
        ``program_model(model, dtype)``, ``init_params(model, seed,
        dtype)``, ``client_arrays(model, traffic, seeds)`` and the
        reference's ``loss(model, params, batch)``."""
        kind = self.config["kind"]
        return _load_module(self.bench_dir / "kinds" / f"{kind}.py",
                            f"chipbench_kind_{kind}")

    def flops(self):
        """``chipbench/flops/<kind>.py`` for the configuration's kind."""
        kind = self.config["kind"]
        return _load_module(self.bench_dir / "flops" / f"{kind}.py",
                            f"chipbench_flops_{kind}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, workload: str,
              bench_dir: Optional[pathlib.Path] = None) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with its
    files read from ``bench_dir`` (default: ``root/chipbench``)."""
    root = pathlib.Path(root)
    bench_dir = pathlib.Path(bench_dir) if bench_dir else root / "chipbench"
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench_dir / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root,
        bench_dir=bench_dir,
    )


def load_peaks(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(pathlib.Path(bench_dir) / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[device_kind]
