"""Seconds of the warm-up: compilation or persistent-cache loads of the
window's programs, plus the warm-up rounds that run them."""


def read(record):
    return record["warmup_s"]
