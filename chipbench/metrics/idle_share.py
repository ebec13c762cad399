"""Share of the measured window, in percent, in which the device is idle:
100 * (1 - busy * R / window), where busy is the device time per round
in the traced stretch (the union of its operations, from the profiler
trace) and R rounds took ``window`` seconds untraced.  The traced
stretch itself runs slower, since the profiler slows the host; its own
idle share is 1 - ``busy_s`` / ``window_s`` of the result's device
record."""


def read(record):
    trace = record.get("trace")
    if not trace or not record.get("traced_rounds"):
        return None
    busy_per_round = trace["busy_s"] / record["traced_rounds"]
    window = record["window"]
    return 100.0 * (1.0 - busy_per_round * window["rounds"] / window["seconds"])
