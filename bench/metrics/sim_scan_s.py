"""Seconds per window round of the Simulator's scan for the active and
waiting jobs over the whole trace (the program's ``active_scan`` span)."""


def read(record):
    values = [r["spans"].get("active_scan", 0.0) for r in record["rounds"] if "spans" in r]
    return sum(values) / len(values) if values else None
