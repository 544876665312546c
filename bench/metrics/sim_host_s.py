"""Seconds per round the Simulator spends outside ``decide()``: event
application, the active-set scan and ``_advance_round``."""


def read(record):
    rounds = record["rounds"]
    if not rounds:
        return None
    return sum(r["round_s"] - r["decide_s"] for r in rounds) / len(rounds)
