"""Seconds to assemble the trainer: synthetic data, the partition,
COPT-alpha, the initial weights and the ``FLTrainer`` itself."""


def read(record):
    return record["build_s"]
