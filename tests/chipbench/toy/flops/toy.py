"""Operations and parameters of the toy two-layer classifier."""


def param_count(model: dict) -> int:
    f, h, c = model["features"], model["hidden"], model["n_classes"]
    return f * h + h + h * c + c


def round_flops(model: dict, traffic: dict) -> float:
    """Every client's T steps of a B-row batch, forward plus backward."""
    macs = model["features"] * model["hidden"] + model["hidden"] * model["n_classes"]
    rows = (int(traffic["n_clients"]) * int(traffic["local_steps"])
            * int(traffic["batch_size"]))
    return 2.0 * 3.0 * macs * rows
