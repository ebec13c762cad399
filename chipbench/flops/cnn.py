"""Operations and parameters of the CIFAR ResNet, counted from its shapes.

Only convolutions and the dense classifier are counted, at 2 FLOPs per
multiply-add; norms, activations, pooling and the optimizer are left out.
A training step counts three times the forward pass (the forward, the
gradient with respect to activations, and with respect to weights).
"""

from __future__ import annotations

from chipbench.kinds.cnn import cnn_blocks


def forward_macs(model: dict) -> int:
    """Multiply-adds of one image's forward pass (stride-2 convolutions
    with SAME padding halve the side, rounding up)."""
    hw = model["image_size"]
    macs = 9 * model["channels"] * model["widths"][0] * hw * hw
    for _, cin, cout, stride in cnn_blocks(model):
        hw = -(-hw // stride)
        area = hw * hw
        macs += 9 * cin * cout * area + 9 * cout * cout * area
        if stride != 1 or cin != cout:
            macs += cin * cout * area
    return macs + model["widths"][-1] * model["n_classes"]


def param_count(model: dict) -> int:
    """Length of the flattened update ``d``."""
    w0 = model["widths"][0]
    d = 9 * model["channels"] * w0 + 2 * w0
    for _, cin, cout, stride in cnn_blocks(model):
        d += 9 * cin * cout + 9 * cout * cout + 4 * cout
        if stride != 1 or cin != cout:
            d += cin * cout
    return d + model["widths"][-1] * model["n_classes"] + model["n_classes"]


def round_flops(model: dict, traffic: dict) -> float:
    """Model FLOPs of one federated round: every client's T local steps
    of a B-image batch, forward plus backward."""
    images = (int(traffic["n_clients"]) * int(traffic["local_steps"])
              * int(traffic["batch_size"]))
    return 2.0 * 3.0 * forward_macs(model) * images
