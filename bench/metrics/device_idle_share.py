"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / window, from the profiler trace."""


def read(record):
    trace = record["trace"]
    if not trace or trace["busy_s"] <= 0.0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
