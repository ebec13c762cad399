"""Rounds per second of the measured window: the rounds of the one
``FLTrainer.run`` call over its wall time, which ends fenced."""


def read(record):
    window = record["window"]
    return window["rounds"] / window["seconds"]
