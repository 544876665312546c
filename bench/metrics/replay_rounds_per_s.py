"""Whole Simulator rounds completed per second of the window."""


def read(record):
    return len(record["rounds"]) / record["window_s"] if record["rounds"] else None
