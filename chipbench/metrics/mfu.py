"""Model FLOP utilization of the whole round, in percent: the model
FLOPs of a round (``chipbench/flops``) times the window's rounds per
second, over the chip's bf16 peak times the chips used."""


def read(record):
    window = record["window"]
    rate = window["rounds"] / window["seconds"]
    peak = record["peaks"]["bf16_flops_per_s"] * record["chips"]
    return 100.0 * record["round_flops"] * rate / peak
