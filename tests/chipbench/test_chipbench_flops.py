"""FLOP and parameter counts against hand counts from the layer shapes,
and the benchmark's refusals: an unknown chip, and the CPU."""

import json

import pytest

from chipbench_tiny import REPO

from chipbench import harness, spec
from chipbench.flops import cnn


# ResNet-18 at CIFAR size (3x3 stem, no max-pool, [2, 2, 2, 2] blocks at
# 64-512), a width the counters must handle beyond the benchmark's cells.
RESNET18 = {"name": "resnet18_gn", "widths": [64, 128, 256, 512],
            "blocks_per_stage": 2, "n_classes": 10, "image_size": 32,
            "channels": 3, "groups": 8, "dtype": "float32"}


def _model(name):
    if name == "resnet18_gn":
        return RESNET18
    return json.loads((REPO / f"chipbench/configs/{name}.json").read_text())["model"]


def _conv(k, cin, cout, side):
    return k * k * cin * cout * side * side


# Hand counts, stage by stage: (multiply-adds per image, parameters).
HAND = {
    "resnet20": (
        _conv(3, 3, 16, 32)                                  # stem
        + 6 * _conv(3, 16, 16, 32)                           # stage 1
        + _conv(3, 16, 32, 16) + _conv(3, 32, 32, 16) + _conv(1, 16, 32, 16)
        + 4 * _conv(3, 32, 32, 16)                           # stage 2
        + _conv(3, 32, 64, 8) + _conv(3, 64, 64, 8) + _conv(1, 32, 64, 8)
        + 4 * _conv(3, 64, 64, 8)                            # stage 3
        + 64 * 10,                                           # classifier
        272_282),
    "resnet18_gn": (
        _conv(3, 3, 64, 32)
        + 4 * _conv(3, 64, 64, 32)
        + _conv(3, 64, 128, 16) + _conv(3, 128, 128, 16) + _conv(1, 64, 128, 16)
        + 2 * _conv(3, 128, 128, 16)
        + _conv(3, 128, 256, 8) + _conv(3, 256, 256, 8) + _conv(1, 128, 256, 8)
        + 2 * _conv(3, 256, 256, 8)
        + _conv(3, 256, 512, 4) + _conv(3, 512, 512, 4) + _conv(1, 256, 512, 4)
        + 2 * _conv(3, 512, 512, 4)
        + 512 * 10,
        11_172_170),
}


@pytest.mark.parametrize("name,approx", [("resnet20", 40.8e6), ("resnet18_gn", 555e6)])
def test_forward_macs_match_hand_count(name, approx):
    macs = cnn.forward_macs(_model(name))
    assert macs == HAND[name][0]
    assert abs(macs - approx) / approx < 0.005


@pytest.mark.parametrize("name", ["resnet20", "resnet18_gn"])
def test_param_count_matches_hand_count_and_program(name):
    import jax
    import numpy as np

    from chipbench.kinds import cnn as cnn_kind

    model = _model(name)
    assert cnn.param_count(model) == HAND[name][1]
    shapes = jax.eval_shape(cnn_kind.program_model(model).init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == HAND[name][1]


def test_round_flops_counts_every_client_step():
    model = _model("resnet20")
    traffic = {"n_clients": 10, "local_steps": 8, "batch_size": 64}
    assert cnn.round_flops(model, traffic) == 6 * HAND["resnet20"][0] * 5120
    assert abs(cnn.round_flops(model, traffic) - 1.254e12) / 1.254e12 < 0.01


def test_peaks_refuse_unknown_device_kind():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="peaks.json"):
        spec.load_peaks("TPU v9 imaginary")


def test_check_device_refuses_unknown_kind(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.NoChip, match="peaks.json"):
        harness.check_device(1, spec.BENCH_DIR)


def test_measuring_path_refuses_the_cpu(capsys):
    with pytest.raises(harness.NoChip, match="not 'tpu'"):
        harness.check_device(1, spec.BENCH_DIR)
    rc = harness.main(["--workload", "resnet20.paper_chunk8", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=REPO)
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "refused" in out.err
