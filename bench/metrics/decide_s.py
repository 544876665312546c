"""Mean ``decide()`` wall time over the window's rounds, timed around the
call by the harness (it ends in the fused program's readout)."""


def read(record):
    rounds = record["rounds"]
    return sum(r["decide_s"] for r in rounds) / len(rounds) if rounds else None
