"""Set-up seconds: process start to the first window round (imports, trace,
compile or cache load, warm-up rounds)."""


def read(record):
    return record["setup_s"]
