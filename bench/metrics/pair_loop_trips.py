"""Trips of the fused program's vmapped pair-auction loop per window round
(the program's ``fused_pair_trips`` counter): the loop runs until its
slowest pair converges, so its device time follows this, not the sum of
bid iterations."""


def read(record):
    rounds = record["rounds"]
    if not rounds or not any("fused_pair_trips" in r["stats"] for r in rounds):
        return None
    return sum(r["stats"].get("fused_pair_trips", 0) for r in rounds) / len(rounds)
