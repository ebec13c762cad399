"""Peak device memory in GB (1e9 bytes): ``peak_bytes_in_use`` of the
fullest chip, read after the window."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
