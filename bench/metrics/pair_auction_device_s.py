"""Device seconds per traced round of the fused program's ``pair_auction``
scope (the union of its operations' intervals), from the profiler trace."""


def read(record):
    trace = record["trace"]
    if not trace or "pair_auction" not in trace.get("scope_device_s", {}):
        return None
    return trace["scope_device_s"]["pair_auction"]
