"""Device seconds of the fused migrate program's module per traced round,
from the profiler trace."""


def read(record):
    trace = record["trace"]
    if not trace or trace["fused_device_s_per_round"] <= 0.0:
        return None
    return trace["fused_device_s_per_round"]
