"""The reduction of a trace by the program's names (``chipbench/scopes.py``),
the harness's split of a window by host span, and the per-layer metrics
read from both, on synthetic intervals and records, on the trace
recorded before the program named its work (``trace_small``), and on one
recorded after (``chipbench/testdata/trace_scoped.xplane.pb.gz`` with its
scope map: the thin job of ``trace_small``, recorded on one TPU v5e by
``python3 chipbench/record_scoped_trace.py``)."""

import json

import pytest

from chipbench_tiny import REPO

from chipbench import harness, scopes, spec, trace
from chipbench.record_scoped_trace import scope_map_path

SMALL = REPO / "chipbench/testdata/trace_small.xplane.pb.gz"
SCOPED = REPO / "chipbench/testdata/trace_scoped.xplane.pb.gz"
SCOPE_METRICS = ("local_train_ms", "aggregate_ms", "server_step_ms")
SPAN_METRICS = ("host_stack_ms", "h2d_ms")


def _readers():
    cell = spec.load_cell(REPO, "resnet20.paper_chunk8")
    names = [m["name"] for m in cell.per_layer]
    assert set(SCOPE_METRICS + SPAN_METRICS) <= set(names)
    return {m: cell.reader(m) for m in SCOPE_METRICS + SPAN_METRICS}


def test_pieces_name_each_stretch_by_its_innermost_span():
    spans = [(0, 100, "fl.block"), (10, 30, "fl.h2d"), (40, 90, "fl.fence")]
    assert scopes.pieces(spans, 0, 120) == [
        (0, 10, "fl.block"), (10, 30, "fl.h2d"), (30, 40, "fl.block"),
        (40, 90, "fl.fence"), (90, 100, "fl.block"), (100, 120, scopes.UNSPANNED)]
    # spans reaching past the window are cut at its edges
    assert scopes.pieces([(-5, 200, "fl.block")], 0, 50) == [(0, 50, "fl.block")]


def test_split_gaps_cuts_gaps_at_span_edges_and_adds_up():
    named = scopes.pieces([(0, 100, "fl.block"), (10, 30, "fl.h2d"),
                           (40, 90, "fl.fence")], 0, 120)
    gaps = [(5, 15), (35, 45), (95, 110)]
    out = scopes.split_gaps(gaps, named)
    assert out == {"fl.block": 15, "fl.h2d": 5, "fl.fence": 5, scopes.UNSPANNED: 10}
    assert sum(out.values()) == sum(b - a for a, b in gaps)


def test_scope_seconds_puts_unmapped_ops_under_unscoped():
    ops = {"fusion.1": {"self_s": 1.0}, "fusion.2": {"self_s": 2.0},
           "fused_aggregate_pallas.3": {"self_s": 0.5}}
    got = scopes.scope_seconds(ops, {"fusion.1": "fl.local_sgd",
                                     "fused_aggregate_pallas.3": "fl.aggregate"})
    assert got == {"fl.local_sgd": 1.0, scopes.UNSCOPED: 2.0, "fl.aggregate": 0.5}


def test_a_trace_without_names_reduces_to_unscoped_and_unspanned():
    reduced = scopes.reduce(trace.load(SMALL), {})
    assert set(reduced["scopes"]) == {scopes.UNSCOPED}
    assert reduced["scopes"][scopes.UNSCOPED] == pytest.approx(reduced["busy_s"])
    assert set(reduced["idle_by_span"]) == {scopes.UNSPANNED}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert reduced["idle_by_span"][scopes.UNSPANNED] == pytest.approx(idle, rel=1e-6)


@pytest.fixture(scope="module")
def scoped():
    op_map = json.loads(scope_map_path(SCOPED).read_text())
    return scopes.reduce(trace.load(SCOPED), op_map), op_map


def test_recorded_scopes_partition_the_busy_time(scoped):
    reduced, op_map = scoped
    assert sum(reduced["scopes"].values()) == pytest.approx(reduced["busy_s"], rel=1e-6)
    for scope in ("fl.local_sgd", "fl.aggregate", "fl.server_step"):
        assert reduced["scopes"].get(scope, 0) > 0, scope
    # the kernel keeps its name and is the aggregation's
    kernels = [op for op, v in reduced["ops"].items() if v["target"] == "tpu_custom_call"]
    assert kernels and all(op.startswith("fused_aggregate_pallas") for op in kernels)
    assert {op_map[op] for op in kernels} == {"fl.aggregate"}
    # local training does most of the device work
    assert max(reduced["scopes"], key=reduced["scopes"].get) == "fl.local_sgd"


def test_recorded_idle_by_span_adds_up_to_the_idle_time(scoped):
    reduced, _ = scoped
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(reduced["idle_by_span"].values()) == pytest.approx(idle, rel=1e-6)
    assert set(reduced["idle_by_span"]) - {scopes.UNSPANNED} <= {
        "fl.block", "fl.channel_trace", "fl.stack_batches", "fl.h2d",
        "fl.dispatch", "fl.fence", "fl.log_rounds"}
    assert reduced["idle_by_span"].get("fl.h2d", 0) > 0


def test_layer_split_numbers_from_a_window_and_a_trace():
    before = {"seconds": {"fl.block": 1.0, "fl.h2d": 0.5},
              "counts": {"fl.block": 2}, "counters": {"h2d_bytes": 100}}
    after = {"seconds": {"fl.block": 3.0, "fl.h2d": 0.9, "fl.stack_batches": 0.2},
             "counts": {"fl.block": 6}, "counters": {"h2d_bytes": 500}}
    split = harness.window_split(before, after, rounds=4)
    assert split["span_ms"] == pytest.approx(
        {"fl.block": 500.0, "fl.h2d": 100.0, "fl.stack_batches": 50.0})
    assert split["counts"] == {"fl.block": 1.0}
    assert split["counters"] == {"h2d_bytes": 100.0}
    traced = {"scopes": {"fl.local_sgd": 0.8, "fl.aggregate": 0.02, "fl.flatten": 0.02,
                         "fl.server_step": 0.004, scopes.UNSCOPED: 0.1}}
    record = {"window": {"spans": split}, "trace": traced, "traced_rounds": 8}
    got = {m: read(record) for m, read in _readers().items()}
    assert got == pytest.approx({"local_train_ms": 100.0, "aggregate_ms": 5.0,
                                 "server_step_ms": 0.5, "host_stack_ms": 50.0,
                                 "h2d_ms": 100.0})
    # a program without the names gives nothing to read, and raises nothing
    empty = {"window": {"spans": harness.window_split(before, before, 4)},
             "trace": {"scopes": {scopes.UNSCOPED: 1.0}}, "traced_rounds": 8}
    assert {m: read(empty) for m, read in _readers().items()} == dict.fromkeys(
        SCOPE_METRICS + SPAN_METRICS)
    # nor does an untraced run, for the device's three
    untraced = dict(record, trace=None, traced_rounds=0)
    assert [_readers()[m](untraced) for m in SCOPE_METRICS] == [None] * 3


def test_scope_readers_on_the_recorded_trace(scoped):
    reduced, _ = scoped
    record = {"trace": reduced, "traced_rounds": 4}
    readers = _readers()
    got = {m: readers[m](record) for m in SCOPE_METRICS}
    assert all(v > 0 for v in got.values()), got
    # the three scopes lie inside the busy time of a traced round
    busy_ms = reduced["busy_s"] * 1e3 / 4
    assert sum(got.values()) <= busy_ms
    assert got["local_train_ms"] == pytest.approx(
        reduced["scopes"]["fl.local_sgd"] * 1e3 / 4)
    assert got["aggregate_ms"] == pytest.approx(
        (reduced["scopes"]["fl.aggregate"] + reduced["scopes"].get("fl.flatten", 0)) * 1e3 / 4)
    assert max(got, key=got.get) == "local_train_ms"
