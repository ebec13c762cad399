"""Share of its memory roofline, in percent, that the ColRel aggregation
kernel reaches.

The aggregation must read the ``(n, d)`` float32 update stack once and
write the ``(d,)`` float32 delta: ``4 * d * (n + 1)`` bytes, whose least
time is that over the chip's HBM bandwidth.  Its operations (``2 n d``
FLOPs) are far below the compute roofline, so bandwidth bounds it.  The
kernel's time is the mean self time of its traced calls: the Pallas
``tpu_custom_call`` operations named ``fused_aggregate_pallas`` (the
monolithic pass) or ``row_stream_pallas`` (the segment-streaming pass,
whose calls of one round together move the same bytes).
"""

KERNELS = ("fused_aggregate_pallas", "row_stream_pallas")


def read(record):
    trace = record.get("trace")
    if not trace or not record.get("traced_rounds"):
        return None
    seconds = sum(op["self_s"] for name, op in trace["ops"].items()
                  if op["target"] == "tpu_custom_call"
                  and name.split(".")[0] in KERNELS)
    if seconds <= 0:
        return None
    n, d = record["n_clients"], record["d"]
    least = 4.0 * d * (n + 1) / record["peaks"]["hbm_bytes_per_s"]
    per_round = seconds / record["traced_rounds"]
    return 100.0 * least / per_round
