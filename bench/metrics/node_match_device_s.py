"""Device seconds per traced round of the fused program's ``node_match``
scope (the union of its operations' intervals), from the profiler trace."""


def read(record):
    trace = record["trace"]
    if not trace or "node_match" not in trace.get("scope_device_s", {}):
        return None
    return trace["scope_device_s"]["node_match"]
