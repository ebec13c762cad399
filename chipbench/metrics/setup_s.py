"""Seconds from the start of the process to the first round of the
window: imports, device start-up, assembly, compilation or cache loads,
and the warm-up rounds."""


def read(record):
    return record["setup_s"]
