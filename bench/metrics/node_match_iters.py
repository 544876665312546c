"""Bid iterations of the fused program's node match per window round (the
program's ``fused_node_iters`` counter)."""


def read(record):
    rounds = record["rounds"]
    if not rounds or not any("fused_node_iters" in r["stats"] for r in rounds):
        return None
    return sum(r["stats"].get("fused_node_iters", 0) for r in rounds) / len(rounds)
