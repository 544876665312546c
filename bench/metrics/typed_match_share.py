"""Share of the window's rounds whose node match ran type-feasible on the
device and converged (the program's ``fused_typed_rounds`` counter); a
round served by the host planner counts 0.  ``None`` where the program
keeps no typed counters (``fused_node_price_top`` is in every typed round
that ran the program)."""


def read(record):
    rounds = record["rounds"]
    if not rounds or not any("fused_node_price_top" in r["stats"] for r in rounds):
        return None
    return sum(r["stats"].get("fused_typed_rounds", 0) for r in rounds) / len(rounds)
