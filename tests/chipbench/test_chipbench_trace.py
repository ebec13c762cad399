"""The trace reduction, on synthetic intervals and on a trace recorded on
one TPU v5e (``chipbench/testdata/trace_small.xplane.pb.gz``: four
rounds of the thin ResNet in chunks of 2, one local step of batch 4,
n = 10, d = 19,858, with the fused aggregation kernel; recorded through
the harness's traced stretch, the job of
``chipbench/record_scoped_trace.py`` before the program named its
work)."""

import pytest

from chipbench_tiny import REPO

from chipbench import spec, trace

TRACE = REPO / "chipbench/testdata/trace_small.xplane.pb.gz"


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == [(0, 4), (5, 7), (9, 10)]


def test_self_time_subtracts_nested_operations():
    ops = [(0, 100, "%while.1 = (...) while(...)"),
           (10, 30, "%fusion.2 = f32[4] fusion(...)"),
           (40, 45, '%k.3 = f32[4] custom-call(...), custom_call_target="tpu_custom_call"'),
           (120, 130, "%fusion.2 = f32[4] fusion(...)")]
    out = trace._self_times(ops)
    assert out["while.1"][:2] == [pytest.approx(75e-9), 1]
    assert out["fusion.2"][:2] == [pytest.approx(30e-9), 2]
    assert out["k.3"][2] == "tpu_custom_call"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_trace(trace.load(TRACE))


def test_recorded_trace_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.048077206, rel=1e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # self times partition the busy time
    total = sum(op["self_s"] for op in reduced["ops"].values())
    assert total == pytest.approx(reduced["busy_s"], rel=1e-6)
    # idle gaps, by label, add up to the idle time
    idle = sum(s for _, s in reduced["idle_by_label"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_recorded_trace_finds_the_kernel_and_labels_gaps(reduced):
    kernels = {k: v for k, v in reduced["ops"].items()
               if v["target"] == "tpu_custom_call"}
    assert list(kernels) == ["fused_aggregate_pallas.6"]
    assert kernels["fused_aggregate_pallas.6"]["count"] == 4
    labels = [name for name, _ in reduced["idle_by_label"]]
    assert labels[0] == "Transpose"  # host-side layout of the batches
    assert "$logger.py:239 log_rounds" in labels
    assert "$pipeline.py:45 next_batches" in labels


def test_trace_readers_on_the_recorded_trace(reduced):
    cell = spec.load_cell(REPO, "resnet20.paper_chunk8")
    record = {"trace": reduced, "traced_rounds": 4, "n_clients": 10, "d": 19_858,
              "peaks": spec.load_peaks("TPU v5 lite"),
              "window": {"rounds": 40, "seconds": 2.0}}
    idle = cell.reader("idle_share")(record)
    assert idle == pytest.approx(100 * (1 - reduced["busy_s"] / 4 * 40 / 2.0))
    share = cell.reader("agg_kernel_roofline")(record)
    least = 4 * 19_858 * 11 / 819e9
    assert share == pytest.approx(100 * least / (18.919e-6 / 4), rel=1e-3)
    assert 0 < share < 100
    assert cell.reader("agg_kernel_roofline")({"trace": None}) is None
